"""Pure-jnp oracle for the flash attention kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, softcap: float = 0.0,
                  window: int = 0,
                  segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,Sk,K,hd] (GQA) → [B,S,H,hd], f32 math.

    ``segment_ids`` ([B, S], self-attention only): tokens attend within
    their own segment; ids < 0 are padding, which attends to nothing and
    returns 0, and is never attended."""
    B, S, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qh = q.reshape(B, S, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qh, k.astype(jnp.float32))
    s = s / np.sqrt(hd)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((S, Sk), bool)
    if causal:
        mask &= qp >= kp
    if window > 0:
        mask &= (qp - kp < window) & (kp - qp < window)
    mask = jnp.broadcast_to(mask, (B, S, Sk))
    if segment_ids is not None:
        qs = segment_ids[:, :, None]
        ks = segment_ids[:, None, :]
        mask = mask & (qs == ks) & (qs >= 0) & (ks >= 0)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    o = o.reshape(B, S, H, hd)
    if segment_ids is not None:
        o = jnp.where((segment_ids >= 0)[:, :, None, None], o, 0.0)
    return o.astype(q.dtype)
