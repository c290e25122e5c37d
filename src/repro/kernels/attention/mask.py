"""Shared attention-mask algebra (DESIGN.md §attention-backend).

One module owns the segment/position mask semantics so the Pallas flash
kernel, the XLA dense path (``models.attention.make_attention_bias``),
the blocked long-sequence path, and the distributed ring/Ulysses inner
loops cannot drift apart:

* :func:`segment_allowed` — the elementwise mask tile. Padding tokens
  carry segment id < 0 and neither attend nor are attended to; real
  tokens attend only within their segment.
* :func:`position_allowed` — causal / sliding-window tile (``window``
  may be a traced int32 scalar; 0 means no window).
* :func:`attention_block_map` — the per-(q block, k block) activity map
  the Pallas kernel uses to SKIP kv blocks whose segment range cannot
  intersect the query block. Built from per-block segment-id intervals,
  it is exact when segment ids are sorted along the row (how
  ``core.packing`` lays packs out) and a conservative superset
  otherwise — the elementwise mask inside the kernel stays the source
  of truth either way. The map is plain int32 DATA: inside jit it is a
  traced array, so swapping pack layouts under a fixed bucket shape
  never recompiles the kernel.

Everything here runs on numpy arrays too (the analytic FLOPs ledger
builds host-side block maps from static pack layouts via
``kernels.attention.costing``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _xp(*arrays):
    """numpy for host values, jnp once anything is traced/device-placed."""
    return jnp if any(isinstance(a, jax.Array) for a in arrays) else np


def segment_allowed(q_seg, k_seg):
    """[..., Sq] x [..., Sk] segment ids → [..., Sq, Sk] bool allowed.

    Tokens attend only within their own segment; ids < 0 mark padding,
    which never attends (query side) nor is attended to (key side).
    """
    return segment_allowed_grid(q_seg[..., :, None], k_seg[..., None, :])


def segment_allowed_grid(qs, ks):
    """Elementwise segment mask over broadcast-compatible grids: the
    Pallas tile path feeds a [bq, 1] query column and a [1, bk] key row."""
    xp = _xp(qs, ks)
    return xp.logical_and(xp.logical_and(qs == ks, qs >= 0), ks >= 0)


def position_allowed_grid(q_pos, k_pos, *, causal: bool, window=0):
    """Elementwise position mask over broadcast-compatible grids.

    The Pallas tile path feeds full [bq, bk] rank-2 position grids (TPU
    Mosaic rejects 1-D iota); the vector variant below feeds expanded
    [..., Sq, 1] x [..., 1, Sk] axes. ``window`` may be a traced int32
    scalar: 0 means full attention, w > 0 keeps only
    |q_pos - k_pos| < w (plus causality when set).
    """
    xp = _xp(q_pos, k_pos, window)
    window = xp.asarray(window, np.int32)
    in_window = xp.logical_and(q_pos - k_pos < window,
                               k_pos - q_pos < window)
    allowed = xp.where(window > 0, in_window, True)
    if causal:
        allowed = xp.logical_and(allowed, q_pos >= k_pos)
    return allowed


def position_allowed(q_pos, k_pos, *, causal: bool, window=0):
    """[..., Sq] x [..., Sk] positions → [..., Sq, Sk] bool allowed."""
    return position_allowed_grid(q_pos[..., :, None], k_pos[..., None, :],
                                 causal=causal, window=window)


def _block_seg_ranges(seg, block: int):
    """[B, S] ids → per-block (min, max) over real (id >= 0) tokens.
    Blocks holding no real token get (BIG, -1), an empty interval."""
    xp = _xp(seg)
    B, S = seg.shape
    assert S % block == 0, (S, block)
    tiles = seg.reshape(B, S // block, block)
    big = np.int32(np.iinfo(np.int32).max)
    lo = xp.min(xp.where(tiles >= 0, tiles, big), axis=2)
    hi = xp.max(xp.where(tiles >= 0, tiles, -1), axis=2)
    return lo, hi


def block_position_envelope(n_q: int, n_k: int, block_q: int, block_k: int, *,
                            causal: bool, window: int = 0) -> np.ndarray:
    """Static [n_q, n_k] bool: can ANY (q, k) pair in the block pair be
    position-visible? Pure numpy — shapes and window are static here."""
    q_lo = np.arange(n_q) * block_q
    q_hi = q_lo + block_q - 1
    k_lo = np.arange(n_k) * block_k
    k_hi = k_lo + block_k - 1
    env = np.ones((n_q, n_k), bool)
    if causal:
        env &= q_hi[:, None] >= k_lo[None, :]
    if int(window) > 0:
        w = int(window)
        env &= (q_lo[:, None] - k_hi[None, :] < w) \
            & (k_lo[None, :] - q_hi[:, None] < w)
    return env


def attention_block_map(q_seg, k_seg, *, block_q: int, block_k: int,
                        causal: bool = False, window: int = 0):
    """[B, Sq] x [B, Sk] segment ids → [B, n_q, n_k] int32 block map
    (1 = the kernel must visit the block, 0 = provably fully masked).

    A block pair is active when the q block's [min, max] real-segment
    interval intersects the k block's AND the static position envelope
    (causal / window over whole blocks) allows at least one pair.
    Always a superset of the exact elementwise mask; exact for
    row-sorted segment ids. ``window`` must be static here (traced
    windows route to the XLA backends, see ``models.attention``).
    """
    xp = _xp(q_seg, k_seg)
    q_lo, q_hi = _block_seg_ranges(q_seg, block_q)
    k_lo, k_hi = _block_seg_ranges(k_seg, block_k)
    active = xp.logical_and(q_lo[:, :, None] <= k_hi[:, None, :],
                            k_lo[:, None, :] <= q_hi[:, :, None])
    env = block_position_envelope(q_lo.shape[1], k_lo.shape[1],
                                  block_q, block_k,
                                  causal=causal, window=window)
    return xp.logical_and(active, xp.asarray(env)[None]).astype(np.int32)


def pad_to_block_multiple(seg: Optional[jax.Array], B: int, S: int,
                          block: int) -> Tuple[jax.Array, int]:
    """Segment ids padded to a block multiple (-1 = padding), synthesizing
    all-zeros ids when none were given. Returns (ids [B, S_pad], S_pad)."""
    xp = _xp(seg)
    target = -(-S // block) * block
    if seg is None:
        seg = xp.zeros((B, S), np.int32)
    if target != S:
        pad = xp.full((B, target - S), -1, np.int32)
        seg = xp.concatenate([seg, pad], axis=1)
    return seg, target
