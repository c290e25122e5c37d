"""One traced run of a cell, as ``run.py --trace 1`` makes it, that also
reads the program's own spans and scopes from the trace.

    python3 chipbench/engine_split.py --workload <cell> --seed <n> --seconds <s> [--excerpt PATH]

It runs ``run.py``'s own set-up, window and check, with the trace read
by :func:`chipbench.enginetrace.normalise` and reduced by
:func:`chipbench.enginetrace.reduce` beside ``devtrace.reduce``, and the
window's needed block work counted beside ``drive.window_steps``. Its
JSON line is ``run.py``'s, with the metrics of :data:`PER_LAYER` that
apply to the cell, and under ``engine`` the device's idle time by engine
phase and its time by scope. ``--excerpt`` writes 40 ms of the trace,
from its first ``chipbench.step``, for ``chipbench/tests/data``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from typing import Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402  (run.T_START: set-up from here)
from chipbench import (blockwork, devtrace, drive, enginetrace,  # noqa: E402
                       spec)

#: the per-layer metrics this reading gives: name -> (unit, cells)
PER_LAYER = {
    "engine_idle_share.open": ("%", ("dit-xl-2.tiers-open",)),
    "engine_idle_share.backlog": ("%", ("dit-xl-2-512.tiers-backlog",)),
    "sync_wait_share": ("%", ("dit-xl-2.tiers-open",)),
    "block_matmul_util": ("%", ("dit-xl-2.tiers-open",)),
}
EXCERPT_NS = 40_000_000


@contextlib.contextmanager
def engine_reading(kept: Dict):
    """Within the block, ``run.measure`` reads the trace with
    :mod:`chipbench.enginetrace` (keeping the normalised trace in
    ``kept``) and counts the window's block work."""
    normalise, reduce_, window_steps = (devtrace.normalise, devtrace.reduce,
                                        drive.window_steps)

    def _normalise(path, device_id=0):
        kept["trace"] = enginetrace.normalise(path, device_id)
        return kept["trace"]

    def _reduce(trace, *a, **kw):
        out = reduce_(trace, *a, **kw)
        out["engine"] = kept["engine"] = enginetrace.reduce(trace)
        return out

    def _window_steps(cfg, traffic, served, start, end, t0):
        out = window_steps(cfg, traffic, served, start, end, t0)
        out["block_flops"] = blockwork.window_block_flops(
            cfg, traffic, served, start, end, t0)
        return out

    devtrace.normalise, devtrace.reduce = _normalise, _reduce
    drive.window_steps = _window_steps
    try:
        yield
    finally:
        devtrace.normalise, devtrace.reduce = normalise, reduce_
        drive.window_steps = window_steps


def excerpt(trace: Dict, length_ns: int = EXCERPT_NS) -> Optional[Dict]:
    """``length_ns`` of a normalised trace from 1 ms before its first
    ``chipbench.step``, with a ``chipbench.window`` span of its own; the
    spans and ops that overlap it are kept whole, and each op's
    ``op_name`` is an index into ``op_name_table``."""
    hs = trace["host_spans"]
    steps = [s[1] for s in hs if s[0] == "chipbench.step"]
    if not steps:
        return None
    t0 = steps[0] - 1_000_000
    t1 = t0 + length_ns

    def inside(s, d):
        return s < t1 and s + d > t0

    table: Dict[str, int] = {}
    ops, ids = [], []
    for (n, s, d), meta in zip(trace["device_ops"], trace["op_names"]):
        if inside(s, d):
            ops.append([n, s, d])
            ids.append(table.setdefault(meta, len(table)))
    return {"device_ops": ops,
            "host_spans": [[devtrace.WINDOW_SPAN, t0, length_ns]] + [
                s for s in hs if s[0] != devtrace.WINDOW_SPAN
                and inside(s[1], s[2])],
            "engine_spans": [s for s in trace["engine_spans"]
                             if inside(s[1], s[2])],
            "op_name_table": list(table), "op_name_ids": ids}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", type=Path)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    cell.per_layer.update({n: u for n, (u, cells) in PER_LAYER.items()
                           if cell.name in cells})
    kept: Dict = {}
    with engine_reading(kept):
        result = run.execute(cell, args.seed, args.seconds, trace=True)
    if result is None:
        return 1
    eng = kept["engine"]
    eng["device_s_by_top_scope"] = enginetrace.by_top_scope(
        eng["device_s_by_scope"])
    result["engine"] = eng
    run.log(f"idle by engine phase {eng['idle_s']}")
    run.log(f"device time by scope {eng['device_s_by_top_scope']}")
    if args.excerpt is not None:
        ex = excerpt(kept["trace"])
        if ex is not None:
            args.excerpt.parent.mkdir(parents=True, exist_ok=True)
            args.excerpt.write_text(json.dumps(ex))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
