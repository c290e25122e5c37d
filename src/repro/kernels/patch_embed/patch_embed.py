"""Pallas TPU kernel for the FlexiDiT tokenizer hot-path.

The flexible patch embedding is a strided conv ≡ ``[N, p³·c] × [p³·c, d]``
matmul after patch extraction. On TPU this is an MXU matmul whose LHS is
re-laid-out per patch size; the kernel tiles N and d in 128-aligned VMEM
blocks with the (small) contraction dim resident. The PI-resize projection
is folded into the weight once per mode instantiation (App. C.2), so the
kernel itself is patch-size-agnostic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _embed_kernel(x_ref, w_ref, b_ref, o_ref):
    x = x_ref[...]                     # [bn, K]
    w = w_ref[...]                     # [K, bd]
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc + b_ref[...].astype(jnp.float32)[None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d"))
def patch_embed_pallas(patches: jax.Array, w: jax.Array, b: jax.Array, *,
                       block_n: int = 256, block_d: int = 256) -> jax.Array:
    """patches: [N, K] (K = p_f·p_h·p_w·c); w: [K, d]; b: [d] → [N, d]."""
    N, K = patches.shape
    d = w.shape[1]
    bn = min(block_n, N)
    bd = min(block_d, d)
    assert N % bn == 0 and d % bd == 0, (N, d, bn, bd)

    return pl.pallas_call(
        _embed_kernel,
        grid=(N // bn, d // bd),
        in_specs=[
            pl.BlockSpec((bn, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bd), lambda i, j: (0, j)),
            pl.BlockSpec((bd,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, d), patches.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(),
    )(patches, w, b)


def _deembed_kernel(t_ref, w_ref, b_ref, o_ref):
    t = t_ref[...]                     # [bn, d]
    w = w_ref[...]                     # [d, bk]
    acc = jax.lax.dot_general(t, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc + b_ref[...].astype(jnp.float32)[None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n",))
def patch_deembed_pallas(tokens: jax.Array, w: jax.Array, b: jax.Array, *,
                         block_n: int = 256) -> jax.Array:
    """tokens: [N, d]; w: [d, K_out]; b: [K_out] → [N, K_out]."""
    N, d = tokens.shape
    K = w.shape[1]
    bn = min(block_n, N)
    assert N % bn == 0

    return pl.pallas_call(
        _deembed_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d, K), lambda i: (0, 0)),
            pl.BlockSpec((K,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, K), tokens.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
    )(tokens, w, b)
