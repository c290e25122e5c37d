"""Sequence-parallel attention collectives (DESIGN.md §distributed).

Two shard_map implementations over a named sequence axis, both taking
globally-shaped ``q, k, v: [B, N, H, hd]`` whose sequence dim is sharded
over ``axis`` and returning the attention output with the same sharding:

* :func:`ulysses_attention` — DeepSpeed-Ulysses style: ``all_to_all``
  turns the sequence sharding into a head sharding (every shard sees the
  full sequence for H/sp heads), runs the ordinary inner attention —
  ``models.attention.blocked_gqa_attend`` for long sequences, the dense
  GQA path otherwise — then all_to_alls back. Requires H % sp == 0.

* :func:`ring_attention` — K/V chunks rotate around the axis via
  ``ppermute`` while a flash-style running softmax (max / numerator /
  denominator carried in f32) accumulates the output. No head-count
  constraint; this is the fallback for meshes where heads don't divide.

Padding tokens (the engine pads N to a multiple of sp) are masked via
``segment_ids``: real tokens carry segment >= 0, padding carries -1 and
never contributes as a key. Padded query rows produce garbage that the
caller slices off.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import AttnConfig
from repro.models import attention as attn_mod
from repro.runtime.sharding import batch_spec


def _specs(mesh: Mesh, axis: str, batch: int):
    b = batch_spec(batch, mesh)[0]     # the runtime's one batch-axis rule
    return P(b, axis, None, None), P(b, axis)


def _inner_cfg(heads: int, head_dim: int) -> AttnConfig:
    return AttnConfig(num_heads=heads, num_kv_heads=heads,
                      head_dim=head_dim, use_rope=False)


def _dense_attend(q, k, v, seg, cfg: AttnConfig, attn_backend: str = "auto"):
    """Post-all-to-all inner attention on one shard's heads (every shard
    sees the FULL sequence for H/sp heads). The backend selects the
    implementation: 'auto'/'pallas' run the segment-aware Pallas flash
    kernel — padding (segment -1) kv blocks and cross-segment tiles of a
    packed stream are skipped, not computed-then-masked."""
    B, S = q.shape[:2]
    resolved = attn_mod.resolve_backend(attn_backend, n_tokens=S,
                                        segmented=seg is not None)
    if resolved == "pallas":
        from repro.kernels.attention import ops as attn_ops
        return attn_ops.flash_attention(q, k, v, causal=False,
                                        softcap=cfg.logit_softcap,
                                        segment_ids=seg)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if resolved == "xla-blocked":
        return attn_mod.blocked_gqa_attend(q, k, v, positions=pos,
                                           causal=False, window=0, cfg=cfg,
                                           segment_ids=seg)
    bias = attn_mod.make_attention_bias(pos, pos, causal=False, window=0,
                                        q_segment=seg, k_segment=seg)
    return attn_mod.gqa_attend(q, k, v, bias, cfg)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Mesh, axis: str,
                      segment_ids: Optional[jax.Array] = None,
                      attn_backend: str = "auto") -> jax.Array:
    """All-to-all attention: sequence-sharded in, sequence-sharded out."""
    B, N, H, hd = q.shape
    sp = mesh.shape[axis]
    if H % sp != 0:
        raise ValueError(f"ulysses needs heads ({H}) % axis size ({sp}) == 0")
    if N % sp != 0:
        raise ValueError(f"sequence ({N}) must be padded to the axis size "
                         f"({sp}) before ulysses_attention")
    qspec, sspec = _specs(mesh, axis, B)
    cfg = _inner_cfg(H // sp, hd)
    if segment_ids is None:
        segment_ids = jnp.zeros((B, N), jnp.int32)

    def inner(q, k, v, seg):
        # [b, N/sp, H, hd] → [b, N, H/sp, hd]: heads gathered, seq scattered
        qf = jax.lax.all_to_all(q, axis, split_axis=2, concat_axis=1,
                                tiled=True)
        kf = jax.lax.all_to_all(k, axis, split_axis=2, concat_axis=1,
                                tiled=True)
        vf = jax.lax.all_to_all(v, axis, split_axis=2, concat_axis=1,
                                tiled=True)
        segf = jax.lax.all_gather(seg, axis, axis=1, tiled=True)
        o = _dense_attend(qf, kf, vf, segf, cfg, attn_backend=attn_backend)
        return jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(qspec, qspec, qspec, sspec),
                         out_specs=qspec, check_vma=False)(q, k, v,
                                                           segment_ids)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, axis: str,
                   segment_ids: Optional[jax.Array] = None,
                   attn_backend: str = "auto") -> jax.Array:
    """Ring attention: local queries, K/V chunks rotating via ppermute with
    a streaming-softmax accumulator. Works for any head count.
    ``attn_backend`` is accepted for interface parity with
    :func:`ulysses_attention` but unused: the rotating accumulator IS the
    flash-style inner loop (one chunk-sized score tile at a time)."""
    del attn_backend
    B, N, H, hd = q.shape
    sp = mesh.shape[axis]
    if N % sp != 0:
        raise ValueError(f"sequence ({N}) must be padded to the axis size "
                         f"({sp}) before ring_attention")
    qspec, sspec = _specs(mesh, axis, B)
    if segment_ids is None:
        segment_ids = jnp.zeros((B, N), jnp.int32)
    perm = [(j, (j - 1) % sp) for j in range(sp)]
    scale = 1.0 / np.sqrt(hd)

    def inner(q, k, v, seg):
        seg_q = seg

        def accumulate(acc, k_c, v_c, seg_c):
            m, num, den = acc
            s = jnp.einsum("bqhd,bkhd->bqhk", q, k_c,
                           preferred_element_type=jnp.float32) * scale
            from repro.kernels.attention import mask as mask_mod
            mask = mask_mod.segment_allowed(seg_q, seg_c)
            s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(mask[:, :, None, :],
                          jnp.exp(s - m_safe[..., None]), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            num = (num * corr[..., None]
                   + jnp.einsum("bqhk,bkhd->bqhd", p,
                                v_c.astype(jnp.float32)))
            den = den * corr + jnp.sum(p, axis=-1)
            return m_new, num, den

        # local chunk first, then rotate-and-accumulate (sp-1) hops — no
        # dead final rotation, so traffic matches the analytic ledger
        # (partition.ModePartition.collective_bytes_per_nfe)
        acc = (jnp.full(q.shape[:2] + (H,), -jnp.inf, jnp.float32),
               jnp.zeros(q.shape, jnp.float32),
               jnp.zeros(q.shape[:2] + (H,), jnp.float32))
        acc = accumulate(acc, k, v, seg_q)

        def step(carry, _):
            k_c, v_c, seg_c, acc = carry
            k_c = jax.lax.ppermute(k_c, axis, perm)
            v_c = jax.lax.ppermute(v_c, axis, perm)
            seg_c = jax.lax.ppermute(seg_c, axis, perm)
            return (k_c, v_c, seg_c, accumulate(acc, k_c, v_c, seg_c)), None

        (_, _, _, (_, num, den)), _ = jax.lax.scan(
            step, (k, v, seg_q, acc), None, length=sp - 1)
        out = num / jnp.maximum(den, 1e-30)[..., None]
        return out.astype(q.dtype)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(qspec, qspec, qspec, sspec),
                         out_specs=qspec, check_vma=False)(q, k, v,
                                                           segment_ids)


ATTN_FNS = {"ulysses": ulysses_attention, "ring": ring_attention}
