"""Pallas TPU flash attention (blocked online softmax, segment-aware).

TPU-native layout: grid ``(batch·q_heads / heads_per_step, num_q_blocks,
num_kv_blocks)``, the kv-block axis iterated sequentially ("arbitrary"
semantics) with the running max / normalizer / accumulator held in VMEM
scratch. Each grid step carries several heads and row-sized tiles, chosen
by :func:`tile_plan` from the shapes: a step has a fixed cost (its DMAs
and pipeline bookkeeping), so a step must hold enough work to hide it.
Supports GQA (kv-head index map), causal masks, sliding windows,
Gemma-style logit soft-capping, and NaViT-style packing segment masks —
the same semantics as the XLA reference in ``repro.models.attention``
(= ``ref.py``'s oracle), sharing its mask algebra via
``kernels.attention.mask`` so the two backends cannot drift.

Block-sparse cross-segment skipping (DESIGN.md §attention-backend): a
host/graph-side block map marks every (q block, kv block) pair whose segment
ranges cannot intersect (including the causal/window envelope), and the
kernel skips the whole score tile under ``pl.when`` — packing's masked-out
work is never issued. The map is int32 DATA (a traced operand), so swapping
pack layouts under a fixed bucket shape replays the same executable.

Padding: sequences are padded internally to block multiples; padded keys
carry segment id -1 and are never attended, padded query rows are sliced
off. Rows whose segment has no visible key (e.g. padding queries) return 0.

Compiled to a Mosaic custom call on a TPU; on the CPU the same kernel
runs through the Pallas interpreter (``repro.kernels.interpret_mode``),
which is how the tests check it against the dense reference.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.attention import mask as mask_mod
from repro.runtime.padding import pad_to, round_up_to_multiple

NEG_INF = -1e30
LANES = 128               # TPU vreg lanes: tiles and statistics align to it
SUBLANES = 8
# A row up to this many tokens is one tile; longer rows split into equal
# tiles no longer than it (TPU v5e sweep, PERF.md §6).
TILE_CAP = 512
# Heads unrolled per turn of a step's head loop: at 1,024 tokens 4 run as
# fast as all 16 unrolled and compile in a third of the time (same sweep).
HEAD_UNROLL = 4
# Scoped VMEM the kernel may use: above Mosaic's 16 MiB default so 16
# heads of 512-token tiles fit, far below v5e's 128 MiB. A plan's own
# estimate stays within three quarters of it, leaving Mosaic's internal
# scratch room (tests/test_chip_compile compiles every main-path shape).
VMEM_LIMIT = 48 * 1024 * 1024
VMEM_PLAN = VMEM_LIMIT * 3 // 4


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One launch's tiling: ``block_q`` x ``block_k`` score tiles, ``heads``
    query heads per grid step."""
    block_q: int
    block_k: int
    heads: int


def row_tile(n: int) -> int:
    """Tile length for a row of ``n`` tokens: the whole row (rounded up to
    lanes) when it fits under ``TILE_CAP``, else the fewest equal
    lane-aligned tiles no longer than that, so padding stays under one
    lane group per tile."""
    n = round_up_to_multiple(max(n, 1), LANES)
    blocks = -(-n // TILE_CAP)
    return round_up_to_multiple(-(-n // blocks), LANES)


def step_vmem_bytes(plan: TilePlan, hd: int, itemsize: int,
                    kv_heads: int) -> int:
    """VMEM one grid step holds: q, k, v, o and the segment ids
    double-buffered, the f32 statistics and accumulator, and the f32
    score, probability and mask tiles of the head being computed."""
    bq, bk, hb = plan.block_q, plan.block_k, plan.heads
    hd_l = round_up_to_multiple(hd, LANES)
    io = (2 * hb * bq + 2 * kv_heads * bk) * hd_l * itemsize
    ids = bq * LANES * 4 + SUBLANES * bk * 4
    scratch = hb * bq * (2 * LANES + hd_l) * 4
    tiles = 4 * bq * bk * 4
    return 2 * (io + ids) + scratch + tiles


def tile_plan(S: int, Sk: int, H: int = 1, K: int = 1, hd: int = LANES,
              itemsize: int = 2, *, block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> TilePlan:
    """Tiles and heads per step for q ``[B, S, H, hd]`` against k/v
    ``[B, Sk, K, hd]``.

    Tiles come from the row lengths alone (:func:`row_tile`), unless
    given, so the cost model (``kernels.attention.costing``) prices the
    tiles the kernel visits without knowing the heads. Heads per step:
    the most that fit ``VMEM_PLAN`` (a divisor of H whose step reads
    whole kv heads: all share one, or each kv group is whole), since
    more heads a step never ran slower (TPU v5e sweep, PERF.md §6).
    """
    bq = block_q or row_tile(S)
    bk = block_k or row_tile(Sk)
    G = H // K
    fits = []
    for hb in range(1, H + 1):
        if H % hb or (G % hb and hb % G):
            continue
        plan = TilePlan(bq, bk, hb)
        if step_vmem_bytes(plan, hd, itemsize, max(1, hb // G)) \
                <= VMEM_PLAN:
            fits.append(plan)
    if not fits:
        raise ValueError(f"a {bq}x{bk} tile at head dim {hd} does not fit "
                         f"{VMEM_PLAN} bytes of VMEM")
    return fits[-1]


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A lane-replicated ``[..., rows, 128]`` statistic cut or widened to
    ``n`` lanes, for an elementwise op against a ``[..., rows, n]``
    tile."""
    lanes = x.shape[-1]
    if n <= lanes:
        return x[..., :n]
    if n % lanes == 0:
        return jnp.tile(x, (1,) * (x.ndim - 1) + (n // lanes,))
    return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))


def _flash_kernel(bmap_ref, *refs, causal: bool, softcap: float,
                  window: int, block_q: int, block_k: int, sm_scale: float,
                  num_q: int, num_kv: int, steps_per_row: int, heads: int,
                  group: int, segmented: bool):
    if segmented:
        (qseg_ref, kseg_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    g = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip the whole score tile when the block map proves it fully masked
    # (cross-segment, outside the window, or acausal). The map is traced
    # data in SMEM (scalar prefetch): layout switches replay this
    # executable.
    @pl.when(bmap_ref[((g // steps_per_row) * num_q + qi) * num_kv + ki] > 0)
    def _visit():
        # one mask for every head of the step. Rank-2 iotas: TPU Mosaic
        # rejects 1-D iota, so the tile path builds full [bq, bk] position
        # grids and uses the elementwise variant of the shared mask
        tile = (block_q, block_k)
        allowed = None
        if causal or window > 0:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, tile, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, tile, 1)
            allowed = mask_mod.position_allowed_grid(
                q_pos, k_pos, causal=causal, window=window)
        if segmented:
            # q ids arrive lane-replicated [bq, 128], kv ids along 8
            # sublanes [8, bk]: a column and a row of the tile
            seg = mask_mod.segment_allowed_grid(qseg_ref[0][:, :1],
                                                kseg_ref[0][:1, :])
            allowed = seg if allowed is None else allowed & seg

        def head(h):
            q = q_ref[h]                               # [bq, hd]
            k = k_ref[h // group]                      # [bk, hd]
            v = v_ref[h // group]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            if allowed is not None:
                s = jnp.where(allowed, s, NEG_INF)

            # Streaming softmax on lane-replicated [bq, 128] statistics.
            # A row with no visible key yet has max NEG_INF; exponents are
            # taken against at least NEG_INF / 2, so its masked scores
            # give exactly 0 (a conservative block map may admit a tile
            # with no visible key — the running max must not poison it).
            m_prev = m_scr[h]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            base = jnp.maximum(m_cur, NEG_INF / 2)
            p = jnp.exp(s - _lanes(base, block_k))
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_scr[h] = m_cur
            acc_scr[h] = acc_scr[h] * _lanes(alpha, acc_scr.shape[2]) \
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        unroll = int(np.gcd(HEAD_UNROLL, heads))

        @pl.loop(0, heads // unroll)
        def _heads(i):
            for j in range(unroll):
                head(i * unroll + j)

    @pl.when(ki == num_kv - 1)
    def _done():
        # every head of the step at once: one op per stage to lower, not
        # one per head
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / _lanes(denom, acc_scr.shape[2])
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "softcap", "window", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, softcap: float = 0.0,
                    window: int = 0,
                    segment_ids: Optional[jax.Array] = None,
                    block_map: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,Sk,K,hd] (GQA) → [B,S,H,hd].

    ``segment_ids``: optional [B, S] int32 shared by queries and keys
    (self-attention packing); tokens attend within their segment only,
    ids < 0 mark padding (never attends, never attended). ``block_map``:
    optional precomputed [B, ceil(S/bq), ceil(Sk/bk)] int32 activity map;
    derived from the segment ids / causal / window envelope when absent.
    Both are traced operands — pack-layout switches never recompile.

    Tiles and heads per step come from :func:`tile_plan`; ``block_q`` /
    ``block_k`` override the tiles. A row shorter than a tile is padded
    up to it, so every compiled tile stays lane-aligned.
    """
    plan = tile_plan(q.shape[1], k.shape[1], q.shape[2], k.shape[2],
                     q.shape[3], q.dtype.itemsize, block_q=block_q,
                     block_k=block_k)
    return flash_attention_planned(q, k, v, plan, causal=causal,
                                   softcap=softcap, window=window,
                                   segment_ids=segment_ids,
                                   block_map=block_map)


def flash_attention_planned(q: jax.Array, k: jax.Array, v: jax.Array,
                            plan: TilePlan, *, causal: bool,
                            softcap: float = 0.0, window: int = 0,
                            segment_ids: Optional[jax.Array] = None,
                            block_map: Optional[jax.Array] = None
                            ) -> jax.Array:
    """:func:`flash_attention` at a given :class:`TilePlan` (traced inside
    the caller's jit; ``benchmarks/flash_sweep.py`` times plans with it)."""
    B, S, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    bq, bk, hb = plan.block_q, plan.block_k, plan.heads
    hkv = max(1, hb // G)                 # kv heads one step reads
    assert H % hb == 0 and (G % hb == 0 or hb % G == 0), (plan, H, K)
    nq = -(-S // bq)
    nk = -(-Sk // bk)
    Sp, Skp = nq * bq, nk * bk

    if segment_ids is not None:
        assert segment_ids.shape == (B, S), (segment_ids.shape, (B, S))
        assert S == Sk, "segment packing is self-attention only"
    segmented = segment_ids is not None or Sp != S or Skp != Sk
    q_seg = k_seg = None
    if segmented:
        q_seg, _ = mask_mod.pad_to_block_multiple(segment_ids, B, S, bq)
        k_seg, _ = mask_mod.pad_to_block_multiple(segment_ids, B, Sk, bk)
    if block_map is None:
        if segmented:
            block_map = mask_mod.attention_block_map(
                q_seg, k_seg, block_q=bq, block_k=bk, causal=causal,
                window=window)
        else:
            env = mask_mod.block_position_envelope(
                nq, nk, bq, bk, causal=causal, window=window)
            # env is static host numpy (window/causal are compile-time
            # here; resolve_backend rejects traced windows for Pallas)
            block_map = jnp.asarray(
                np.broadcast_to(env.astype(np.int32), (B, nq, nk)))  # repro: ignore[trace-host-np]
    assert block_map.shape == (B, nq, nk), (block_map.shape, (B, nq, nk))

    qt = pad_to(q, Sp, axis=1).transpose(0, 2, 1, 3).reshape(B * H, Sp, hd)
    kt = pad_to(k, Skp, axis=1).transpose(0, 2, 1, 3).reshape(B * K, Skp, hd)
    vt = pad_to(v, Skp, axis=1).transpose(0, 2, 1, 3).reshape(B * K, Skp, hd)

    steps_per_row = H // hb
    kernel = functools.partial(
        _flash_kernel, causal=causal, softcap=softcap, window=window,
        block_q=bq, block_k=bk, sm_scale=1.0 / np.sqrt(hd), num_q=nq,
        num_kv=nk, steps_per_row=steps_per_row, heads=hb, group=G,
        segmented=segmented)

    # Step g holds q heads [g·hb, (g+1)·hb) of the [B·H] axis (row
    # g // steps_per_row) and the kv heads they read, block (g·hb // G)
    # // hkv of the [B·K] axis. Mosaic tiles the last two block dims by
    # (8, 128) unless a dim spans the whole array: q ids go in
    # lane-replicated [B, Sp, 128] and kv ids along sublanes [B, 8, Skp],
    # so each id tile is a whole (8, 128) multiple. The block map is
    # flattened into SMEM by scalar prefetch (the kernel's first ref;
    # index maps receive it as a trailing argument).
    def row(g):
        return g // steps_per_row

    def kv_block(g):
        return (g * hb // G) // hkv

    in_specs = []
    inputs = []
    if segmented:
        in_specs += [
            pl.BlockSpec((1, bq, LANES), lambda g, i, j, bm: (row(g), i, 0)),
            pl.BlockSpec((1, SUBLANES, bk),
                         lambda g, i, j, bm: (row(g), 0, j)),
        ]
        inputs += [
            jnp.broadcast_to(q_seg[:, :, None], (B, Sp, LANES)),
            jnp.broadcast_to(k_seg[:, None, :], (B, SUBLANES, Skp)),
        ]
    in_specs += [
        pl.BlockSpec((hb, bq, hd), lambda g, i, j, bm: (g, i, 0)),
        pl.BlockSpec((hkv, bk, hd), lambda g, i, j, bm: (kv_block(g), j, 0)),
        pl.BlockSpec((hkv, bk, hd), lambda g, i, j, bm: (kv_block(g), j, 0)),
    ]
    inputs += [qt, kt, vt]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H // hb, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((hb, bq, hd),
                                   lambda g, i, j, bm: (g, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((hb, bq, LANES), jnp.float32),
                pltpu.VMEM((hb, bq, LANES), jnp.float32),
                pltpu.VMEM((hb, bq, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret_mode(),
        name="flash_attention",
    )(jnp.asarray(block_map, jnp.int32).reshape(-1), *inputs)
    return out.reshape(B, H, Sp, hd).transpose(0, 2, 1, 3)[:, :S]
