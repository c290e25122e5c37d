"""Jitted SSD wrapper: Pallas intra-chunk kernel + host-graph inter-chunk
recurrence. Drop-in for ``repro.models.ssm.ssd_chunked``."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd.ssd_chunk import ssd_chunk_pallas


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
        Cm: jax.Array, chunk: int, h0: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, jax.Array]:
    """Same contract as models.ssm.ssd_chunked (pads internally)."""
    B, S, H, P = x.shape
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y_intra, Sc, Ltot = ssd_chunk_pallas(x, dt, A, Bm, Cm, chunk=chunk)
    N = Bm.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    def step(h, inp):
        Sc_c, Ltot_c = inp
        h_new = h * jnp.exp(Ltot_c)[:, :, None, None] + Sc_c
        return h_new, h

    h_final, h_prevs = jax.lax.scan(
        step, h0, (Sc.transpose(1, 0, 2, 3, 4), Ltot.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)      # [B,nc,H,P,N]

    la = (dt * A[None, None, :]).reshape(B, nc, chunk, H)
    L = jnp.cumsum(la, axis=2)
    Cc = Cm.reshape(B, nc, chunk, N)
    y_inter = jnp.einsum("bcqh,bcqn,bchpn->bcqhp", jnp.exp(L), Cc, h_prevs)
    y = y_intra + y_inter.reshape(B, nc * chunk, H, P).astype(y_intra.dtype)
    return y[:, :S], h_final
