"""Run one cell of BENCHMARK.json once, on the chip this is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window's first instant):
the weights from the seed on the device, the engine that the
configuration's program module builds over the cell's chips, its
warm-set ladder, and warm-up passes of the cell's own traffic (see
:mod:`chipbench.loops`) until passes in a row compile nothing. Then the
measured window, the check of what it served against the plain reference, and
one JSON line on stdout. ``--trace 1`` runs the same window under the
profiler and reports the cell's per-layer metrics instead of its
end-to-end ones. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def percentile(values: List[float], q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


@dataclasses.dataclass
class Prepared:
    """A warmed engine and what the window needs besides."""
    cell: spec.Cell
    seed: int
    dev: Any
    n_devices: int
    peak: Optional[Dict]
    counter: Any
    params: Any
    engine: Any
    load: Any           # the mix's loop (:mod:`chipbench.loops`)


def find_chip(cell: spec.Cell):
    """The devices to run on, or None (with the reason logged) where no
    chip of the kind the cell needs is found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU found: JAX's backend is {devs[0].platform!r}; "
            f"this benchmark runs only on the chip")
        return None
    if len(devs) < cell.chips:
        log(f"the cell needs {cell.chips} chips, JAX found {len(devs)}")
        return None
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under {ROOT / 'src'}: run from a checkout")
        return None
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    return devs


def prepare(cell: spec.Cell, seed: int, devs, seconds: float) -> Prepared:
    """Set-up on the chip: the persistent compile cache, then
    :func:`warm`."""
    from chipbench import compiles, peaks
    peak = peaks.peak_for(devs[0].device_kind)
    log(f"compile cache {compiles.enable_cache(ROOT)}")
    return warm(cell, seed, devs, seconds, peak)


def warm(cell: spec.Cell, seed: int, devs, seconds: float,
         peak: Optional[Dict]) -> Prepared:
    """Weights, engine, warm-set ladder, the mix's loop and its warm-up
    passes; ``seconds`` is the window's length."""
    from chipbench import compiles, drive, generator
    dev = devs[0]
    counter = compiles.CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    log(f"cell {cell.name}: {dev.platform} {dev.device_kind} x{len(devs)}, "
        f"seed {seed}")
    params = cell.reference.make_params(cfg, seed, device=dev)
    engine = cell.program.build(cfg, traffic, params, devs[:cell.chips])
    n_ladder = engine.precapture_warm_set()
    log(f"warm-set ladder: {n_ladder} layouts; {counter.count} executables "
        f"obtained so far, {counter.cache_hits} of them cache hits")
    load = generator.arrival(traffic).Loop(engine, cell, seed, seconds)
    wu = traffic["warmup"]
    quiet = 0
    for p in range(int(wu["max_passes"])):
        c0 = counter.count
        load.warm_pass(p)
        drive.sync()
        log(f"warm-up pass {p}: {counter.count - c0} executables obtained "
            f"{sorted(set(counter.names[c0:]))}")
        # rare pack shapes can first show in a later pass: stop only
        # after ``quiet_passes`` passes in a row obtain nothing
        quiet = quiet + 1 if counter.count == c0 else 0
        if quiet == int(wu["quiet_passes"]):
            break
    log(f"set-up: {counter.count} executables obtained "
        f"({counter.seconds:.1f}s), {counter.cache_hits} of them "
        f"persistent-cache hits, {counter.count - counter.cache_hits} "
        f"compiled")
    return Prepared(cell, seed, dev, len(devs), peak, counter, params,
                    engine, load)


def measure(pp: Prepared, seconds: float, trace: bool,
            traffic: Optional[Dict] = None) -> Dict:
    """The measured window. Returns the run's records: what the metric
    readers read, the end-to-end metrics and the finished requests."""
    import jax
    from chipbench import devtrace, drive, enginetrace
    cell, engine = pp.cell, pp.engine
    cfg = cell.config
    traffic = traffic or cell.traffic
    T = traffic["sampler"]["T"]
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = pp.counter.count
    steps_before = engine.metrics.total_steps
    with drive.span("window"):
        t0 = drive.clock()
        setup_s = t0 - T_START
        start = drive.inflight_steps(engine)
        served = pp.load.window(t0, seconds, traffic)
        drive.sync()
        t1 = drive.clock()
        end = drive.inflight_steps(engine)
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    n_steps = engine.metrics.total_steps - steps_before
    steps = list(engine.metrics.steps)[-n_steps:] if n_steps else []
    log(f"window: {window_s:.3f}s, {n_steps} dispatches, "
        f"{pp.counter.count - c0} executables obtained inside the window "
        f"{pp.counter.names[c0:]}")

    population, unfinished = pp.load.outcome(served, t0, start, end)
    finished = [s for s in population if s.finish is not None]
    failed = len(engine.quarantined) + unfinished
    wk = drive.window_steps(cell, traffic, served, start, end, t0)
    run: Dict = {"cell": cell.name, "config": cfg, "traffic": traffic,
                 "chips": cell.chips, "peak": pp.peak,
                 "window_s": window_s,
                 "steps": [(st.real_tokens, st.packed_tokens)
                           for st in steps],
                 "work": wk, "trace": None, "requests": []}
    e2e = {"img_per_s": wk["request_steps"] / T / window_s,
           "setup_s": setup_s}
    log(f"{wk['request_steps']} request-steps, {len(finished)} of "
        f"{len(population)} requests finished in the window")
    if pp.load.latency:
        lat = [(s.finish if s.finish is not None else t1) - s.due
               for s in population]
        lateness = [s.submitted - s.due for s in population]
        run["requests"] = [{"due": s.due, "admit": s.admit,
                            "finish": s.finish, "budget": s.req.budget}
                           for s in population]
        e2e["latency_p50_s"] = percentile(lat, 50)
        e2e["latency_p90_s"] = percentile(lat, 90)
        log(f"latency p50 {e2e['latency_p50_s']:.4f}s p90 "
            f"{e2e['latency_p90_s']:.4f}s; generator late by p50 "
            f"{percentile(lateness, 50) * 1e3:.3f} ms, max "
            f"{max(lateness) * 1e3:.3f} ms")
    if trace:
        norm = enginetrace.normalise(devtrace.find_xplane(trace_dir),
                                     device_id=pp.dev.id)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr = run["trace"] = devtrace.reduce(norm)
        eng = tr["engine"] = enginetrace.reduce(norm)
        log(f"trace: busy {tr['busy_s']:.4f}s of {tr['window_s']:.4f}s, "
            f"kernels {tr['kernel_s']}")
        log(f"idle by engine phase {eng['idle_s']}")
        log(f"device time by scope "
            f"{enginetrace.by_top_scope(eng['device_s_by_scope'])}; by "
            f"scope path {eng['device_s_by_scope']}")
    return {"run": run, "e2e": e2e, "finished": finished,
            "attempted": len(population), "failed": failed}


def check(cell: spec.Cell, seed: int, params, finished: List) -> Dict:
    """Sample the finished requests from the seed and compare them with
    the plain reference. The caller frees the program's state first."""
    import jax
    from chipbench import check as chk
    from chipbench import generator
    sample = generator.sample_for_check(cell.traffic,
                                        [s.req for s in finished], seed)
    by_index = {s.req.index: s for s in finished}
    x_served = {i: jax.device_get(by_index[r.index].x0)
                for i, r in enumerate(sample)}
    t_ref = time.monotonic()
    checks = chk.compare(cell, params, sample, x_served,
                         [by_index[r.index].budget_served for r in sample])
    log(f"reference: {len(sample)} requests in "
        f"{time.monotonic() - t_ref:.1f}s")
    return checks


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool
            ) -> Optional[Dict]:
    """One run of ``cell``: the result line as a dict, or None where no
    chip of the kind the cell needs is found."""
    devs = find_chip(cell)
    if devs is None:
        return None
    return finish(prepare(cell, seed, devs, seconds), seconds, trace)


def finish(pp: Prepared, seconds: float, trace: bool) -> Dict:
    """The window, its metrics and the check: the result line. It frees
    the program's state before the reference runs."""
    from chipbench import check as chk
    cell = pp.cell
    m = measure(pp, seconds, trace)
    run = m["run"]
    mem = (pp.dev.memory_stats() or {}).get("peak_bytes_in_use")
    if trace:
        wanted = cell.per_layer
        values = {n: spec.metric_reader(n)(run) for n in wanted}
    else:
        wanted = cell.end_to_end
        values = {n: m["e2e"][n] for n in wanted}
    metrics = {n: {"value": values[n], "unit": u}
               for n, u in wanted.items() if values[n] is not None}
    pp.engine = pp.load = None
    gc.collect()
    checks = check(cell, pp.seed, pp.params, m["finished"])
    result = {"correct": chk.correct(m["failed"], checks), "attempted": m["attempted"],
              "failed": m["failed"], "metrics": metrics,
              "device": {"platform": pp.dev.platform,
                         "kind": pp.dev.device_kind, "count": pp.n_devices,
                         "memory_peak_bytes": mem}}
    if trace:
        tr = run["trace"]
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    result = execute(cell, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
