"""Segment-aware Pallas flash attention vs the XLA paths (DESIGN.md
§attention-backend).

Serving bucket shapes (DiT-XL/2-512 geometry: 1,024-token rows, weak
segments of 256 tokens) drive three measurements. The kernel's tiles
are sized from the row (``flash_attention.tile_plan``): a 256-token
dit-xl-2 row is one tile, so its 64-token weak segments are computed
then masked and skip nothing; at 1,024 tokens a 512-token tile pair
still holds whole weak segments, which is where skipping can be seen:

* **analytic** — attention FLOPs of a saturated mixed-budget pack under
  dense N² pricing vs the block-sparse ledger (the tiles the kernel
  actually visits), plus the cross-segment block skip rate of REAL
  ``greedy_fit`` packs from the serving bucket menu. Deterministic;
  gated against ``baselines.json`` (``run.py`` fails loudly on
  regression).
* **wall-clock** — one packed-row attention call per backend
  (interpret-mode Pallas on this CPU container is expected to trail the
  fused XLA einsums — the compiled path targets TPU; the number is
  reported for trend-tracking, not gated).
* **zero-recompile** — swapping pack layouts under the fixed bucket
  shape must replay one executable.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

REPEATS = 5


def _bench_cfg():
    """DiT-XL/2-512 token geometry (1,024-token rows, 256-token weak
    segments: dit-xl-2's blocks on the 64x64 latent of a 512 px image;
    ``reduced()`` shrinks the latent, so pin the real grid) at smoke
    width: attention shapes are what matter here."""
    from repro.configs import get_config
    base = get_config("dit-xl-2")
    red = base.reduced()
    f, h, w, c = base.dit.latent_shape
    return dataclasses.replace(
        red, num_layers=4, d_model=128, d_ff=512,
        attn=dataclasses.replace(red.attn, num_heads=8, num_kv_heads=8,
                                 head_dim=16),
        dit=dataclasses.replace(red.dit, latent_shape=(f, 2 * h, 2 * w, c)))


def _time_best(fn, *args):
    import jax
    jax.block_until_ready(fn(*args))          # compile / warm
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_attention() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import AttnConfig
    from repro.core import packing
    from repro.kernels.attention import costing
    from repro.kernels.attention import ops as attn_ops
    from repro.models import attention as attn_mod
    from repro.models import dit as dit_mod
    from repro.serving.batcher import BucketMenu
    from benchmarks.baseline import check_baseline

    cfg = _bench_cfg()
    d = cfg.d_model
    H = cfg.attn.num_heads
    hd = d // H
    N0 = dit_mod.tokens_for_mode(cfg, 0)            # row capacity (1,024)
    N1 = dit_mod.tokens_for_mode(cfg, 1)            # weak segment (256)
    r = packing.pack_ratio(cfg, 1)

    # --- a saturated mixed-budget pack: the steady-state weak-heavy mix
    # a budget<=0.6 menu keeps in flight (most steps are weak phases),
    # assembled by the SAME greedy_fit the engine's cold planner runs
    menu = BucketMenu(cfg, (0, 1), max_tokens_per_step=16 * N0, guided=True)
    req_modes = [0] + [1] * 10
    idx, counts = menu.greedy_fit(req_modes)
    assert len(idx) == len(req_modes), "pack not saturated"
    from repro.pipeline.packed import PackLayout
    layout = PackLayout.for_counts(counts, guided=True, row_capacity=N0)
    seg_modes = layout.segment_modes()

    dense_attn = 0.0
    sparse_attn = 0.0
    rows = packing.assign_rows(
        [dit_mod.tokens_for_mode(cfg, m) for m in seg_modes], N0)
    seg_tokens = [dit_mod.tokens_for_mode(cfg, m) for m in seg_modes]
    L = cfg.num_layers
    for row in rows:
        lengths = [seg_tokens[i] for i in row]
        dense_attn += L * costing.dense_attention_flops(N0, N0, d)
        sparse_attn += L * costing.block_sparse_attention_flops(
            lengths, N0, d)
    reduction = 1.0 - sparse_attn / dense_attn
    active, total = layout.attention_block_stats(cfg)
    skip_rate = 1.0 - active / total

    # pack-level cost through the public ledger (controller pricing path)
    cost_dense = layout.cost(cfg).flops
    cost_sparse = layout.cost(cfg, attn_backend="pallas").flops

    # --- wall-clock at the bucket shape: R packed rows of capacity N0
    R = len(rows)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (R, N0, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (R, N0, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (R, N0, H, hd), jnp.float32)
    seg = np.full((R, N0), -1, np.int32)
    for ri, row in enumerate(rows):
        off = 0
        for si in row:
            seg[ri, off:off + seg_tokens[si]] = si
            off += seg_tokens[si]
    seg_j = jnp.asarray(seg)
    acfg = AttnConfig(num_heads=H, num_kv_heads=H, head_dim=hd,
                      use_rope=False)
    pos = jnp.broadcast_to(jnp.arange(N0, dtype=jnp.int32), (R, N0))

    pallas_fn = jax.jit(lambda q, k, v, s: attn_ops.flash_attention(
        q, k, v, causal=False, segment_ids=s))
    dense_fn = jax.jit(lambda q, k, v, s: attn_mod.gqa_attend(
        q, k, v, attn_mod.make_attention_bias(pos, pos, causal=False,
                                              window=0, q_segment=s,
                                              k_segment=s), acfg))
    blocked_fn = jax.jit(lambda q, k, v, s: attn_mod.blocked_gqa_attend(
        q, k, v, positions=pos, causal=False, window=0, cfg=acfg,
        q_block=128, segment_ids=s))
    us_pallas = _time_best(pallas_fn, q, k, v, seg_j)
    us_dense = _time_best(dense_fn, q, k, v, seg_j)
    us_blocked = _time_best(blocked_fn, q, k, v, seg_j)

    # --- zero recompiles across pack layouts at the fixed bucket shape
    n_before = attn_ops.compile_cache_size()
    alt = np.full((R, N0), -1, np.int32)
    alt[:, :200] = 0                              # a different layout
    jax.block_until_ready(pallas_fn(q, k, v, jnp.asarray(alt)))
    recompiles = attn_ops.compile_cache_size() - n_before

    bench = {
        "name": "attention",
        "row_capacity": N0,
        "weak_segment_tokens": N1,
        "pack_ratio": r,
        "rows": R,
        "pack_segments": len(seg_modes),
        "attn_flops_dense": dense_attn,
        "attn_flops_sparse": sparse_attn,
        "attn_flops_reduction_frac": reduction,
        "attn_block_skip_rate": skip_rate,
        "pack_cost_flops_dense": cost_dense,
        "pack_cost_flops_sparse": cost_sparse,
        "us_pallas_interpret": us_pallas,
        "us_dense": us_dense,
        "us_blocked": us_blocked,
        "recompiles_across_layouts": recompiles,
    }
    print("BENCH " + json.dumps(bench))
    print(f"attention,{us_pallas:.1f},"
          f"sparse_reduction={reduction:.3f};skip={skip_rate:.3f};"
          f"recompiles={recompiles}")
    assert recompiles == 0, "pack-layout switch recompiled the kernel"
    check_baseline("attention", bench)


if __name__ == "__main__":
    bench_attention()
