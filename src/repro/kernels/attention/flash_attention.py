"""Pallas TPU flash attention (blocked online softmax, segment-aware).

TPU-native layout: grid ``(batch·q_heads, num_q_blocks, num_kv_blocks)``, the
kv-block axis iterated sequentially ("arbitrary" semantics) with the running
max / normalizer / accumulator held in VMEM scratch. Block sizes default to
128 (MXU-aligned). Supports GQA (kv-head index map), causal masks, sliding
windows, Gemma-style logit soft-capping, and NaViT-style packing segment
masks — the same semantics as the XLA reference in ``repro.models.attention``
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

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.attention import mask as mask_mod
from repro.runtime.padding import pad_to

NEG_INF = -1e30


def _flash_kernel(bmap_ref, *refs, causal: bool, softcap: float,
                  window: int, block_q: int, block_k: int, sm_scale: float,
                  num_q: int, num_kv: int, heads: int, segmented: bool):
    if segmented:
        (qseg_ref, kseg_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    bh = pl.program_id(0)
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
    @pl.when(bmap_ref[((bh // heads) * num_q + qi) * num_kv + ki] > 0)
    def _visit():
        q = q_ref[0]                                   # [bq, hd]
        k = k_ref[0]                                   # [bk, hd]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap

        # rank-2 iotas: TPU Mosaic rejects 1-D iota, so the tile path
        # builds full [bq, bk] position grids and uses the elementwise
        # variant of the shared position mask
        tile = (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, tile, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, tile, 1)
        allowed = mask_mod.position_allowed_grid(q_pos, k_pos, causal=causal,
                                                 window=window)
        if segmented:
            # q ids arrive as a [bq, 1] column, kv ids as a [1, bk] row
            allowed &= mask_mod.segment_allowed_grid(qseg_ref[0],
                                                     kseg_ref[0])

        # Streaming softmax with fully-masked-tile safety: probabilities
        # are zeroed where masked (a conservative block map may admit a
        # tile with no visible key — the running max must not poison it).
        s = jnp.where(allowed, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(allowed, jnp.exp(s - m_cur[:, None]), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_cur
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "softcap", "window", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, softcap: float = 0.0,
                    window: int = 0,
                    segment_ids: Optional[jax.Array] = None,
                    block_map: Optional[jax.Array] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,Sk,K,hd] (GQA) → [B,S,H,hd].

    ``segment_ids``: optional [B, S] int32 shared by queries and keys
    (self-attention packing); tokens attend within their segment only,
    ids < 0 mark padding (never attends, never attended). ``block_map``:
    optional precomputed [B, ceil(S/bq), ceil(Sk/bk)] int32 activity map;
    derived from the segment ids / causal / window envelope when absent.
    Both are traced operands — pack-layout switches never recompile.

    Tiles are ``block_q`` x ``block_k`` whatever the sequence length:
    a row shorter than a tile is padded up to it, never given a smaller
    tile, so every compiled tile stays lane-aligned.
    """
    B, S, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = block_q, block_k
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

    kernel = functools.partial(
        _flash_kernel, causal=causal, softcap=softcap, window=window,
        block_q=bq, block_k=bk, sm_scale=1.0 / np.sqrt(hd), num_q=nq,
        num_kv=nk, heads=H, segmented=segmented)

    # Mosaic tiles the last two block dims by (8, 128) unless a dim spans
    # the whole array: q ids go in as [B, Sp, 1] columns and kv ids as
    # [B, 1, Skp] rows, so each tile is (bq, 1) / (1, bk). The block map
    # is flattened into SMEM by scalar prefetch (the kernel's first ref;
    # index maps receive it as a trailing argument).
    in_specs = []
    inputs = []
    if segmented:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda b, i, j, bm, H=H: (b // H, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j, bm, H=H: (b // H, 0, j)),
        ]
        inputs += [q_seg[:, :, None], k_seg[:, None, :]]
    in_specs += [
        pl.BlockSpec((1, bq, hd), lambda b, i, j, bm: (b, i, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, i, j, bm, G=G: (b // G, j, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, i, j, bm, G=G: (b // G, j, 0)),
    ]
    inputs += [qt, kt, vt]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, hd),
                                   lambda b, i, j, bm: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="flash_attention",
    )(jnp.asarray(block_map, jnp.int32).reshape(-1), *inputs)
    return out.reshape(B, H, Sp, hd).transpose(0, 2, 1, 3)[:, :S]
