"""The control of the ``correct`` comparison: the plain reference put in
the program's place and computed a precision below the bfloat16 the
configurations state (float8 e4m3 operands in every matrix product), on
the requests a run of the cell would compare. Its x0 goes through the
same comparison as a run's served x0 and has to come out not correct;
its smallest ``rel_rms_max`` is the limit's upper end.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13

One JSON line per seed: ``correct`` and the numbers compared, each
beside its limit. This is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import check, generator, run, spec  # noqa: E402


def control_checks(cell: spec.Cell, seed: int, params) -> dict:
    """The comparison of a run, with the control's x0 in place of the
    served one, over the sample a run would draw from the window's
    requests of ``seed``."""
    reqs = generator.generate(cell.traffic, cell.draw_conds, seed,
                              generator.STREAM_WINDOW, 30.0)
    sample = generator.sample_for_check(cell.traffic, reqs, seed)
    low = check.reference_x0(cell, params, sample, precision="fp8")
    return check.compare(cell, params, sample, low,
                         [r.budget for r in sample])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(run.ROOT, args.workload)
    devs = run.find_chip(cell)
    if devs is None:
        return 1
    from chipbench import compiles
    compiles.enable_cache(run.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        params = cell.reference.make_params(cell.config, seed,
                                            device=devs[0])
        checks = control_checks(cell, seed, params)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": check.correct(0, checks),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
