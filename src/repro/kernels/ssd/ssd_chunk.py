"""Pallas TPU kernel for the Mamba2 SSD intra-chunk computation.

One grid step processes one (batch, chunk) tile entirely in VMEM:
  y_intra[q] = Σ_{k≤q} (C_q·B_k) · exp(L_q − L_k) · dt_k · x_k
  Sc         = Σ_k exp(L_tot − L_k) · dt_k · x_k ⊗ B_k      (chunk summary)
  Ltot       = Σ_q log a_q
The O(S)-state inter-chunk recurrence stays in a tiny ``lax.scan`` on the
host graph (``ops.ssd``), exactly like the reference ``ssd_chunked``.

VMEM working set per step (Q=128, H≤64, P=64, N=128):
  x [Q,H,P] + M [Q,Q,H] + B/C [Q,N] ≈ 2–6 MB — fits comfortably.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                y_ref, sc_ref, ltot_ref):
    x = x_ref[0].astype(jnp.float32)          # [Q,H,P]
    dt = dt_ref[0].astype(jnp.float32)        # [Q,H]
    A = a_ref[...].astype(jnp.float32)        # [H]
    Bm = b_ref[0].astype(jnp.float32)         # [Q,N]
    Cm = c_ref[0].astype(jnp.float32)         # [Q,N]

    la = dt * A[None, :]                      # [Q,H]
    L = jnp.cumsum(la, axis=0)                # [Q,H]
    Ltot = L[-1]                              # [H]

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    diff = L[:, None, :] - L[None, :, :]      # [Q,Q,H]
    Q = L.shape[0]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = (qi >= ki)[:, :, None]
    decay = jnp.where(causal, jnp.exp(diff), 0.0)
    M = CB[:, :, None] * decay * dt[None, :, :]           # [Q,K,H]
    y = jnp.einsum("qkh,khp->qhp", M, x)                  # [Q,H,P]

    w = jnp.exp(Ltot[None, :] - L) * dt                   # [Q,H]
    sc = jnp.einsum("qh,qhp,qn->hpn", w, x, Bm)           # [H,P,N]

    y_ref[0] = y.astype(y_ref.dtype)
    sc_ref[0] = sc
    ltot_ref[0] = Ltot


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                     Bm: jax.Array, Cm: jax.Array, *, chunk: int):
    """x: [B,S,H,P]; dt: [B,S,H]; A: [H]; Bm/Cm: [B,S,N].
    Returns (y_intra [B,S,H,P], Sc [B,nc,H,P,N], Ltot [B,nc,H])."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    xc = x.reshape(B, nc, chunk, H, P).reshape(B * nc, chunk, H, P)
    dtc = dt.reshape(B, nc, chunk, H).reshape(B * nc, chunk, H)
    Bc = Bm.reshape(B, nc, chunk, N).reshape(B * nc, chunk, N)
    Cc = Cm.reshape(B, nc, chunk, N).reshape(B * nc, chunk, N)

    y, sc, ltot = pl.pallas_call(
        _ssd_kernel,
        grid=(B * nc,),
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((1, chunk, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc, chunk, H, P), x.dtype),
            jax.ShapeDtypeStruct((B * nc, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((B * nc, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
    )(xc, dtc, A, Bc, Cc)
    return (y.reshape(B, nc, chunk, H, P).reshape(B, S, H, P),
            sc.reshape(B, nc, H, P, N),
            ltot.reshape(B, nc, H))
