"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import flash_attention as fa_mod
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref
from repro.kernels.patch_embed import ops as pe_ops
from repro.kernels.patch_embed import ref as pe_ref
from repro.kernels.patch_embed.patch_embed import (patch_deembed_pallas,
                                                   patch_embed_pallas)
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref
from test_chip_compile import FLASH_CASES

ATTN_CASES = [
    # B, S, H, K, hd, causal, softcap, window, dtype
    (2, 128, 4, 2, 64, True, 0.0, 0, jnp.float32),
    (1, 256, 4, 4, 64, True, 50.0, 0, jnp.float32),
    (2, 256, 8, 2, 32, True, 0.0, 128, jnp.float32),
    (1, 128, 2, 1, 128, False, 0.0, 0, jnp.float32),
    (1, 256, 4, 2, 64, True, 0.0, 0, jnp.bfloat16),
    (2, 384, 6, 2, 64, True, 30.0, 256, jnp.float32),
]


@pytest.mark.parametrize("case", ATTN_CASES,
                         ids=[f"a{i}" for i in range(len(ATTN_CASES))])
def test_flash_attention_allclose(case):
    B, S, H, K, hd, causal, cap, win, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = attn_ops.flash_attention(q, k, v, causal=causal, softcap=cap,
                                   window=win)
    want = attn_ref.attention_ref(q, k, v, causal=causal, softcap=cap,
                                  window=win)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


# DiT rows, as (row length, segment lengths per row, head dim): at head
# dim 72 a 1,024-token image, four 256-token weak images, a mixed
# 1,024/256 pack, and a 256-token row of four 64-token weak images
# (padding fills the rest); at head dim 64 a joint (MMDiT) row whose
# length is no multiple of the lanes, one segment of 64 + 12 image and
# text tokens and three of 16 + 12, as sd35-medium's tiny test packs them
DIT_PACKS = {
    "one-1024": (1024, [[1024]], 72),
    "four-256": (1024, [[256] * 4], 72),
    "mixed-1024-256": (1024, [[1024], [256] * 3], 72),
    "four-64": (256, [[64] * 4], 72),
    "joint-unaligned-hd64": (173, [[76, 28, 28, 28], [28]], 64),
}


@pytest.mark.parametrize("case", list(DIT_PACKS))
def test_flash_attention_dit_packs(case):
    S, rows, hd = DIT_PACKS[case]
    B, H = len(rows), 4
    seg = np.full((B, S), -1, np.int32)
    for r, lengths in enumerate(rows):
        seg[r, :sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
    seg = jnp.asarray(seg)
    ks = jax.random.split(jax.random.PRNGKey(S + B), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.bfloat16)
               for kk in ks)
    out = attn_ops.flash_attention(q, k, v, causal=False, segment_ids=seg)
    want = attn_ref.attention_ref(q, k, v, causal=False, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("heads", [1, 2, 4, 8])
def test_flash_attention_heads_per_step_gqa(heads):
    """A step's heads share one kv head (heads < group), span whole kv
    groups (heads > group), or match one group: the index maps fetch the
    right kv heads in every case."""
    B, S, H, K, hd = 2, 256, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, K, hd))
    v = jax.random.normal(ks[2], (B, S, K, hd))
    seg = jnp.asarray(np.repeat([[0] * 100 + [1] * 120 + [-1] * 36], B, 0))
    plan = fa_mod.TilePlan(128, 128, heads)
    out = jax.jit(lambda q, k, v, s: fa_mod.flash_attention_planned(
        q, k, v, plan, causal=True, segment_ids=s))(q, k, v, seg)
    want = attn_ref.attention_ref(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_tile_plan_fits_vmem(case):
    """Every shape the chip-compile rehearsal compiles gets whole-head,
    lane-aligned tiles that fit the kernel's VMEM budget."""
    B, S, H, hd, _segmented = FLASH_CASES[case]
    plan = fa_mod.tile_plan(S, S, H, H, hd, 2)
    assert plan.block_q % 128 == 0 and plan.block_k % 128 == 0
    assert plan.block_q <= max(fa_mod.TILE_CAP, 128)
    assert H % plan.heads == 0
    assert fa_mod.step_vmem_bytes(plan, hd, 2, plan.heads) \
        <= fa_mod.VMEM_PLAN
    # padding stays under one lane group per tile
    nq = -(-S // plan.block_q)
    assert nq * plan.block_q - S < 128 * nq


SSD_CASES = [(2, 64, 4, 16, 8, 16), (1, 96, 2, 32, 16, 32),
             (2, 48, 3, 8, 8, 16), (1, 128, 4, 16, 32, 64)]


@pytest.mark.parametrize("case", SSD_CASES,
                         ids=[f"s{i}" for i in range(len(SSD_CASES))])
def test_ssd_kernel_allclose(case):
    B, S, H, P, N, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(S + N), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y_true, h_true = ssd_ref.ssd_recurrence_ref(x, dt, A, Bm, Cm)
    y, h = ssd_ops.ssd(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_true),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_true),
                               atol=2e-3, rtol=2e-3)


PE_CASES = [(512, 64, 256, jnp.float32), (256, 48, 128, jnp.float32),
            (1024, 128, 512, jnp.bfloat16), (256, 16, 64, jnp.float32)]


@pytest.mark.parametrize("case", PE_CASES,
                         ids=[f"p{i}" for i in range(len(PE_CASES))])
def test_patch_embed_allclose(case):
    N, K, d, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(N + d), 3)
    x = jax.random.normal(ks[0], (N, K), dtype)
    w = jax.random.normal(ks[1], (K, d), dtype)
    b = jax.random.normal(ks[2], (d,), dtype)
    got = patch_embed_pallas(x, w, b, block_n=min(256, N),
                             block_d=min(256, d))
    want = pe_ref.patch_embed_ref(x, w, b)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    got2 = patch_deembed_pallas(x, w, b, block_n=min(256, N))
    want2 = pe_ref.patch_deembed_ref(x, w, b)
    np.testing.assert_allclose(np.asarray(got2, np.float32),
                               np.asarray(want2, np.float32),
                               atol=tol, rtol=tol)


def test_flexi_embed_kernel_matches_core_path():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (2, 1, 16, 16, 4))
    w_flex = jax.random.normal(ks[1], (16, 4, 64))
    b = jax.random.normal(ks[2], (64,))
    from repro.core import patch as pm
    for p in [(1, 2, 2), (1, 4, 4)]:
        got = pe_ops.embed_tokens_flex(w_flex, b, x, p, (1, 4, 4))
        want = pm.embed_tokens_flex(w_flex, b, x, p, (1, 4, 4))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)
