"""Serve an open-loop cell's traffic with one of its parameters set to
each of a few values, one window each, in one process with one set-up.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --vary rate_per_s=2,3,4,5

For each value one JSON line: the value, requests attempted and failed,
images' worth of denoising steps per second, latency p50 and p90 from
the due time, and the mean latency of the first and second half of the
window's requests; a second half much slower than the first is a queue
that grows. Varying ``rate_per_s`` finds the knee, the highest rate the
engine sustains (a cell's rate is written as a number into its traffic
file); varying ``order_seed`` shows how far the order of one mix's
arrivals moves its tails.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import drive, run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vary", required=True, help="key=v1,v2,...")
    args = ap.parse_args(argv)
    key, values = args.vary.split("=")
    cell = spec.load_cell(run.ROOT, args.workload)
    devs = run.find_chip(cell)
    if devs is None:
        return 1
    pp = run.prepare(cell, args.seed, devs, args.seconds)
    for value in (json.loads(v) for v in values.split(",")):
        traffic = dict(cell.traffic, **{key: value})
        m = run.measure(pp, args.seconds, False, traffic)
        lat = sorted(((s.due, (s.finish or float("inf")) - s.due)
                      for s in m["finished"]), key=lambda e: e[0])
        half = len(lat) // 2
        mean = lambda xs: sum(x for _d, x in xs) / max(1, len(xs))  # noqa
        row = {key: value, "attempted": m["attempted"],
               "failed": m["failed"],
               "latency_p50_s": m["e2e"].get("latency_p50_s"),
               "latency_p90_s": m["e2e"].get("latency_p90_s"),
               "first_half_mean_s": mean(lat[:half]),
               "second_half_mean_s": mean(lat[half:]),
               "img_per_s": m["e2e"]["img_per_s"]}
        print(json.dumps(row), flush=True)
        pp.engine.run()              # drain the load kept on after it
        drive.sync()
    return 0


if __name__ == "__main__":
    sys.exit(main())
