"""Fig. 9 (FLOPs vs latency / utilization) and Fig. 12 (packing)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.configs import get_config
from repro.core.packing import packing_cost, packed_weak_forward
from repro.core.scheduler import dit_nfe_flops
from repro.models import dit as dit_mod


def bench_fig9_utilization():
    """Wall-time vs FLOPs for each patch mode of the bench DiT (CPU), plus
    the analytic TPU-v5e projection for the paper's full-size models."""
    params, cfg, sched = C.get_flexidit()
    B = 2  # paper's fig-9 batch (CFG pair)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B,) + cfg.dit.latent_shape)
    t = jnp.full((B,), 10.0)
    y = jnp.arange(B) % C.N_CLASSES
    rows = []
    for mode in range(1 + len(cfg.dit.flex_patch_sizes)):
        fn = jax.jit(lambda p, x, t, y, m=mode: dit_mod.dit_forward(
            p, x, t, y, cfg, mode=m))
        us = C.timeit(fn, params, x, t, y)
        fl = B * dit_nfe_flops(cfg, mode)
        gflops = fl / (us * 1e-6) / 1e9
        tok = dit_mod.tokens_for_mode(cfg, mode)
        rows.append((mode, tok, us, gflops))
        C.csv_row(f"fig9_cpu_mode{mode}", us,
                  f"tokens={tok};gflops_per_s={gflops:.2f}")
    # analytic v5e projections for the paper-scale configs
    from repro.launch.roofline import PROJECTION_DEVICE, device_peaks
    peak = device_peaks(PROJECTION_DEVICE).flops_bf16
    for arch in ("t2i-transformer", "video-dit"):
        full = get_config(arch)
        for mode in range(1 + len(full.dit.flex_patch_sizes)):
            fl = dit_nfe_flops(full, mode)
            tok = dit_mod.tokens_for_mode(full, mode)
            us_ideal = fl / peak * 1e6
            C.csv_row(f"fig9_v5e_{arch}_mode{mode}", us_ideal,
                      f"tokens={tok};tflops_per_nfe={fl/1e12:.2f}")
    return rows


def bench_fig12_packing():
    """FLOPs/latency of the 4 CFG-packing approaches: analytic + measured."""
    params, cfg, sched = C.get_flexidit()
    for n_images in (1, 4, 8):
        costs = packing_cost(cfg, 1, n_images)
        best_flops = min(c.flops for c in costs)
        for c in costs:
            C.csv_row(f"fig12_n{n_images}_approach{c.approach}", 0.0,
                      f"flops={c.flops:.3e};calls={c.nfe_calls};"
                      f"norm_flops={c.flops/best_flops:.2f}")
    # measured: packed weak forward (approach 4) vs 4 separate weak calls
    B, r = 2, 4
    key = jax.random.PRNGKey(1)
    xs = jax.random.normal(key, (r, B) + cfg.dit.latent_shape)
    t = jnp.full((B,), 10.0)
    conds = jnp.tile(jnp.arange(B)[None] % C.N_CLASSES, (r, 1))
    packed = jax.jit(lambda p, xs, t, c: packed_weak_forward(
        p, xs, t, c, cfg, mode=1))
    us_packed = C.timeit(packed, params, xs, t, conds)

    single = jax.jit(lambda p, x, t, c: dit_mod.dit_forward(
        p, x, t, c, cfg, mode=1))

    def run_separate(p, xs, t, conds):
        return [single(p, xs[i], t, conds[i]) for i in range(r)]
    us_sep = C.timeit(run_separate, params, xs, t, conds)
    C.csv_row("fig12_measured_packed", us_packed,
              f"separate_us={us_sep:.0f};speedup={us_sep/us_packed:.2f}x")
    return us_packed, us_sep
