"""Segment-aware Pallas flash attention as the unified backend
(DESIGN.md §attention-backend).

Property tests (the Pallas interpreter, on the CPU): the kernel matches
the dense XLA reference to ≤1e-4 on randomized pack layouts (ragged
segments, padding, window/softcap combos, GQA ratios), the block map is
always a superset of the elementwise mask, pack-layout switches under a
fixed bucket shape never recompile, and the packed step family (ddim AND
ddpm) is backend-consistent end to end.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prop import given
from repro.configs.base import AttnConfig
from repro.core import packing
from repro.core.flexify import flexify
from repro.core.scheduler import dit_block_flops, dit_nfe_flops
from repro.diffusion import schedule as sch
from repro.kernels.attention import costing
from repro.kernels.attention import flash_attention as fa_mod
from repro.kernels.attention import mask as mask_mod
from repro.kernels.attention import ops as attn_ops
from repro.models import attention as attn_mod
from repro.models import dit as dit_mod
from repro.pipeline.packed import PackLayout, make_packed_step_fn
from repro.pipeline.plan import SamplingPlan

pytestmark = pytest.mark.tier1

TOL = 1e-4


# ---------------------------------------------------------------------------
# Strategies


def pack_case(rng: np.random.Generator):
    """Randomized pack layout: bucket shape, ragged segments + padding,
    feature combo, GQA ratio."""
    S = int(rng.choice([128, 192, 256]))
    bq = int(rng.choice([32, 64]))
    K = int(rng.choice([1, 2, 4]))
    H = K * int(rng.choice([1, 2]))
    hd = int(rng.choice([16, 32]))
    B = int(rng.integers(1, 3))
    softcap = float(rng.choice([0.0, 30.0]))
    causal = bool(rng.integers(0, 2))
    window = int(rng.choice([0, 0, bq]))     # windows only make sense causal
    segs = []
    for _ in range(B):
        n_seg = int(rng.integers(1, 9))
        lengths, left = [], S
        for i in range(n_seg):
            if left <= 1:
                break
            hi = max(2, left // max(1, n_seg - i))
            lengths.append(int(rng.integers(1, hi + 1)))
            left -= lengths[-1]
        segs.append(lengths)                  # rest of the row is padding
    return dict(S=S, bq=bq, B=B, H=H, K=K, hd=hd, softcap=softcap,
                causal=causal, window=window, segs=segs)


def _seg_array(segs, B, S):
    ids = np.full((B, S), -1, np.int32)
    for b, lengths in enumerate(segs):
        off = 0
        for i, n in enumerate(lengths):
            ids[b, off:off + n] = i
            off += n
    return ids


def _dense_ref(q, k, v, seg, cfg, *, causal, window, softcap):
    """XLA reference via the shared-bias dense path (the oracle the
    Pallas kernel must match on real tokens)."""
    B, S = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    bias = attn_mod.make_attention_bias(
        pos, pos, causal=causal, window=window,
        q_segment=None if seg is None else jnp.asarray(seg),
        k_segment=None if seg is None else jnp.asarray(seg))
    return attn_mod.gqa_attend(q, k, v, bias,
                               dataclasses.replace(cfg,
                                                   logit_softcap=softcap))


# ---------------------------------------------------------------------------
# Kernel vs dense reference


@given(pack_case, n=12)
def test_flash_matches_dense_on_random_packs(case):
    S, B, H, K, hd = case["S"], case["B"], case["H"], case["K"], case["hd"]
    ks = jax.random.split(jax.random.PRNGKey(S + H + case["bq"]), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    seg = _seg_array(case["segs"], B, S)
    window = case["window"] if case["causal"] else 0
    cfg = AttnConfig(num_heads=H, num_kv_heads=K, head_dim=hd,
                     use_rope=False, logit_softcap=case["softcap"])
    got = attn_ops.flash_attention(
        q, k, v, causal=case["causal"], softcap=case["softcap"],
        window=window, segment_ids=jnp.asarray(seg),
        block_q=case["bq"], block_k=case["bq"])
    want = _dense_ref(q, k, v, seg, cfg, causal=case["causal"],
                      window=window, softcap=case["softcap"])
    real = seg >= 0
    err = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert err.size and float(err.max()) <= TOL
    # padding rows: no visible key → the kernel returns exact zeros
    if (~real).any():
        np.testing.assert_array_equal(np.asarray(got)[~real], 0.0)


@given(pack_case, n=12)
def test_block_map_is_superset_of_elementwise_mask(case):
    S, B, bq = case["S"], case["B"], case["bq"]
    seg = _seg_array(case["segs"], B, S)
    window = case["window"] if case["causal"] else 0
    bm = np.asarray(mask_mod.attention_block_map(
        seg, seg, block_q=bq, block_k=bq, causal=case["causal"],
        window=window))
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S))
    allowed = np.asarray(mask_mod.position_allowed(
        pos, pos, causal=case["causal"], window=window)
        & mask_mod.segment_allowed(seg, seg))
    nq = S // bq
    tiles = allowed.reshape(B, nq, bq, nq, bq).any(axis=(2, 4))
    # every elementwise-visible pair lives in an active block
    assert not (tiles & ~bm.astype(bool)).any()


def test_flash_matches_blocked_xla_path():
    """Drift guard: the kernel and ``blocked_gqa_attend`` share one mask
    helper — packed outputs must agree on real tokens."""
    B, S, H, hd = 2, 256, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    seg = _seg_array([[100, 60, 30], [128, 128]], B, S)
    cfg = AttnConfig(num_heads=H, num_kv_heads=H, head_dim=hd,
                     use_rope=False)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    blocked = attn_mod.blocked_gqa_attend(
        q, k, v, positions=pos, causal=False, window=0, cfg=cfg,
        q_block=64, segment_ids=jnp.asarray(seg))
    flash = attn_ops.flash_attention(q, k, v, causal=False,
                                     segment_ids=jnp.asarray(seg),
                                     block_q=64, block_k=64)
    real = seg >= 0
    err = np.abs(np.asarray(blocked) - np.asarray(flash))[real]
    assert float(err.max()) <= TOL


def test_zero_recompile_across_pack_layouts():
    """Fixed bucket shape, different pack layouts → ONE executable (the
    block map and segment ids are traced data)."""
    B, S, H, hd = 1, 128, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    layouts = [[[128]], [[64, 64]], [[32, 32, 32, 32]], [[100, 20]], [[50]]]
    sizes = []
    for lay in layouts:
        seg = _seg_array(lay, B, S)
        attn_ops.flash_attention(q, k, v, causal=False,
                                 segment_ids=jnp.asarray(seg),
                                 block_q=32, block_k=32)
        sizes.append(attn_ops.compile_cache_size())
    assert sizes[-1] == sizes[0], f"recompiled across layouts: {sizes}"


# ---------------------------------------------------------------------------
# Backend resolution / plan surface


def test_resolve_backend_rules():
    r = attn_mod.resolve_backend
    assert r("auto", n_tokens=64, segmented=True) == "pallas"
    assert r("auto", n_tokens=64, segmented=False) == "dense"
    assert r("auto", n_tokens=10_000, segmented=False) == "pallas"
    assert r("auto", n_tokens=10_000, segmented=True,
             window_traced=True) == "xla-blocked"
    assert r("xla", n_tokens=64, segmented=True) == "dense"  # legacy alias
    assert r("dense", n_tokens=10_000, segmented=True) == "dense"
    with pytest.raises(ValueError, match="attn_backend"):
        r("cuda", n_tokens=64, segmented=False)
    with pytest.raises(ValueError, match="static window"):
        r("pallas", n_tokens=64, segmented=False, window_traced=True)


def test_plan_validates_attn_backend():
    with pytest.raises(ValueError, match="attn_backend"):
        SamplingPlan(T=4, attn_backend="triton")
    p = SamplingPlan(T=4, attn_backend="pallas")
    assert dataclasses.replace(p, attn_backend="dense").attn_backend == "dense"


# ---------------------------------------------------------------------------
# Packed forward + step family (e2e, ddim AND ddpm)


@pytest.fixture(scope="module")
def flexi(tiny_dit_cfg, trained_like_dit):
    fparams, fcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)])
    return fparams, fcfg, sch.linear_schedule(100)


def test_packed_mixed_forward_backend_consistent(flexi):
    fparams, fcfg, _ = flexi
    key = jax.random.PRNGKey(11)
    groups = ((0, 1), (1, 3))
    xs = [jax.random.normal(jax.random.fold_in(key, g),
                            (n,) + fcfg.dit.latent_shape)
          for g, (m, n) in enumerate(groups)]
    ts = [jnp.full((n,), 50, jnp.int32) for m, n in groups]
    conds = [jnp.arange(n, dtype=jnp.int32) for m, n in groups]
    out = {}
    for be in ("pallas", "dense", "auto"):
        out[be] = packing.packed_mixed_forward(fparams, fcfg, groups, xs, ts,
                                               conds, attn_backend=be)
    for g in range(len(groups)):
        err = np.abs(np.asarray(out["pallas"][g])
                     - np.asarray(out["dense"][g])).max()
        assert float(err) <= TOL
        # packed token streams default to the Pallas kernel
        np.testing.assert_array_equal(np.asarray(out["auto"][g]),
                                      np.asarray(out["pallas"][g]))


@pytest.mark.parametrize("solver", ["ddim", "ddpm"])
def test_packed_step_backend_consistent(flexi, solver):
    fparams, fcfg, sched = flexi
    layout = PackLayout(groups=((0, 1), (1, 2)), guided=True)
    key = jax.random.PRNGKey(13)
    xs = [jax.random.normal(jax.random.fold_in(key, 1),
                            (1,) + fcfg.dit.latent_shape),
          jax.random.normal(jax.random.fold_in(key, 2),
                            (2,) + fcfg.dit.latent_shape)]
    metas = [jnp.asarray([[[60], [40], [3]]], jnp.int32),
             jnp.asarray([[[60, 55], [40, 35], [1, 2]]], jnp.int32)]
    rng = np.random.default_rng(7)
    keys = [jnp.asarray(rng.integers(0, 2**31, (1, 1, 2)).astype(np.uint32)),
            jnp.asarray(rng.integers(0, 2**31, (1, 2, 2)).astype(np.uint32))]
    outs = {}
    for be in ("pallas", "dense"):
        fn = jax.jit(make_packed_step_fn(fcfg, sched, layout, solver=solver,
                                         attn_backend=be))
        outs[be] = fn(fparams, tuple(xs), tuple(metas), tuple(keys))
    for a, b in zip(outs["pallas"], outs["dense"]):
        assert float(np.abs(np.asarray(a) - np.asarray(b)).max()) <= TOL


# ---------------------------------------------------------------------------
# Analytic ledger


def _serving_scale_cfg(tiny_dit_cfg):
    """Analytic-only config at real serving shapes (1024-token rows, so
    the 128-token default block tiles show cross-segment sparsity);
    never instantiated as weights."""
    return dataclasses.replace(
        tiny_dit_cfg,
        dit=dataclasses.replace(tiny_dit_cfg.dit,
                                latent_shape=(1, 64, 64, 4),
                                flex_patch_sizes=((1, 4, 4),)))


def test_block_sparse_pack_pricing(tiny_dit_cfg):
    fcfg = _serving_scale_cfg(tiny_dit_cfg)
    N0 = dit_mod.tokens_for_mode(fcfg, 0)
    r = packing.pack_ratio(fcfg, 1)
    dense_row = packing.packed_row_flops(fcfg, [1] * r, capacity=N0)
    sparse_row = packing.packed_row_flops(fcfg, [1] * r, capacity=N0,
                                          attn_backend="pallas")
    # cross-segment blocks are skipped → strictly cheaper than dense
    assert sparse_row < dense_row
    # a single full-row segment has nothing to skip (block-aligned)
    assert packing.packed_row_flops(fcfg, [0], capacity=N0,
                                    attn_backend="pallas") \
        == pytest.approx(packing.packed_row_flops(fcfg, [0], capacity=N0))
    # the saving is exactly the masked-out score tiles, per layer
    active, total = packing.pack_attention_block_stats(fcfg, [1] * r, N0)
    assert active < total
    d, L = fcfg.d_model, fcfg.num_layers
    # priced at the tiles the kernel launches with for rows of N0 tokens
    plan = fa_mod.tile_plan(N0, N0)
    bq, bk = plan.block_q, plan.block_k
    expect = L * (total - active) * costing.dense_attention_flops(bq, bk, d)
    assert dense_row - sparse_row == pytest.approx(expect)


def test_request_cost_prices_backend(tiny_dit_cfg):
    from repro.serving import request_cost_flops
    fcfg = _serving_scale_cfg(tiny_dit_cfg)
    plan = SamplingPlan(T=4, budget=1.0, guidance_scale=1.5)
    dense = request_cost_flops(fcfg, plan, attn_backend="dense")
    pallas = request_cost_flops(fcfg, plan, attn_backend="pallas")
    # single requests only round up to block granularity — never cheaper
    assert pallas >= dense
    # the default follows the plan's backend ('auto' → pallas pricing)
    assert request_cost_flops(fcfg, plan) == pallas
    assert dit_nfe_flops(fcfg, 0, attn_backend="auto") \
        == dit_nfe_flops(fcfg, 0, attn_backend="pallas")
    assert dit_block_flops(fcfg, 64, attn_backend="dense") \
        == dit_block_flops(fcfg, 64)


def test_metrics_skip_rate():
    from repro.serving.metrics import ServingMetrics
    m = ServingMetrics()
    assert m.attn_block_skip_rate == 0.0
    m.record_attention_blocks(6, 16)
    m.record_attention_blocks(2, 4)
    assert m.attn_block_skip_rate == pytest.approx(1.0 - 8 / 20)
    assert m.summary()["attn_block_skip_rate"] == m.attn_block_skip_rate
