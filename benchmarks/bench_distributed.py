"""Distributed engine scaling: tokens/s and collective bytes per denoise
step vs mesh size on fake CPU devices (DESIGN.md §distributed).

The outer entry (``bench_distributed``, run via ``benchmarks.run --suite
distributed``) re-launches this module in a subprocess with 8 fake host
devices — the flag must be set before jax initializes, and the main bench
process keeps its 1-device view. The inner run sweeps sequence-axis sizes
(1, 2, 4 → Ulysses; 8 → ring on the 4-head smoke model), times warm
sampling, prices the collectives analytically, and emits one ``BENCH``
JSON line plus the usual CSV rows.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SEQ_SIZES = (1, 2, 4, 8)
T = 4
BATCH = 4


def bench_distributed() -> None:
    """Outer harness entry: run the sweep on 8 fake host devices."""
    from repro.launch.mesh import ensure_host_devices
    env = ensure_host_devices(8, dict(os.environ))
    # the child runs on fake CPU devices: keep it off the chip this
    # process may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-m", "benchmarks.bench_distributed"],
                       env=env, capture_output=True, text=True, timeout=1200,
                       cwd=str(Path(__file__).resolve().parents[1]))
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        raise RuntimeError(f"inner distributed bench failed:\n{r.stderr[-2000:]}")
    # the inner subprocess prints the BENCH line; the gate runs out here
    from benchmarks.baseline import check_baseline
    for line in r.stdout.splitlines():
        if line.startswith("BENCH "):
            check_baseline("distributed_seqpar", json.loads(line[len("BENCH "):]))


def _inner() -> None:
    import jax
    import numpy as np

    from benchmarks import common as C
    from repro.configs import get_config
    from repro.diffusion import schedule as sch
    from repro.distributed import ParallelSpec, plan_partition
    from repro.launch.mesh import make_inference_mesh
    from repro.models import dit as dit_mod
    from repro.pipeline import FlexiPipeline, SamplingPlan

    cfg = get_config("dit-xl-2").reduced()
    params = dit_mod.init_dit(cfg, jax.random.PRNGKey(0))
    sched = sch.linear_schedule(100)
    key = jax.random.PRNGKey(1)
    results = []
    for sp in SEQ_SIZES:
        mesh = make_inference_mesh(1, sp) if sp > 1 else None
        parallel = ParallelSpec() if sp > 1 else None
        pipe = FlexiPipeline(params, cfg, sched, mesh=mesh)
        plan = SamplingPlan(T=T, budget=0.6, guidance_scale=1.5,
                            parallel=parallel)
        plan.validate(cfg)
        fs = plan.resolve_schedule(cfg)
        part = plan_partition(cfg, fs, sp, parallel or ParallelSpec())
        jax.block_until_ready(pipe.sample(plan, BATCH, key).x0)   # compile
        times = []
        for i in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(
                pipe.sample(plan, BATCH, jax.random.fold_in(key, i)).x0)
            times.append(time.perf_counter() - t0)
        dt = float(np.median(times))
        # token-steps actually computed (padded, CFG-doubled) per sample
        tok_steps = 2 * sum(n * p.tokens_padded for p, n in part.phases)
        tokens_per_s = BATCH * tok_steps / dt
        bytes_per_step = part.collective_bytes(cfg) / T
        impl = part.phases[0][0].impl if sp > 1 else "none"
        C.csv_row(f"distributed_seq{sp}", dt * 1e6,
                  f"impl={impl};tokens_per_s={tokens_per_s:.0f};"
                  f"collective_bytes_per_step={bytes_per_step:.0f};"
                  f"pad_eff={part.parallel_efficiency(cfg):.3f}")
        results.append({
            "seq": sp, "impl": impl, "wall_s": dt,
            "tokens_per_s": tokens_per_s,
            "collective_bytes_per_step": bytes_per_step,
            "parallel_efficiency": part.parallel_efficiency(cfg),
        })
    print("BENCH " + json.dumps({"name": "distributed_seqpar", "T": T,
                                 "batch": BATCH, "arch": "dit-xl-2:reduced",
                                 "results": results}))


if __name__ == "__main__":
    _inner()
