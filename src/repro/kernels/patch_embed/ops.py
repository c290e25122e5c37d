"""Jitted wrappers: flexible tokenize/de-tokenize via the Pallas kernels.

Drop-in accelerated versions of ``repro.core.patch.embed_tokens_flex`` /
``deembed_tokens_flex`` (the PI-resize projection is folded into the weight
before the kernel runs, so mode switching costs nothing per NFE).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import patch as patch_mod
from repro.core import resize
from repro.kernels.patch_embed.patch_embed import (patch_deembed_pallas,
                                                   patch_embed_pallas)

Patch = Tuple[int, int, int]


def embed_tokens_flex(w_flex, b, x, p: Patch, p_prime: Patch,
                      block_n: int = 256, block_d: int = 256):
    W = resize.project_embed(w_flex, p, p_prime)            # [pp, c, d]
    K = W.shape[0] * W.shape[1]
    d = W.shape[2]
    patches = patch_mod.patchify(x, p)                      # [B,N,pp,c]
    B, N = patches.shape[:2]
    flat = patches.reshape(B * N, K)
    tok = patch_embed_pallas(flat, W.reshape(K, d).astype(x.dtype),
                             b.astype(x.dtype),
                             block_n=min(block_n, B * N),
                             block_d=min(block_d, d))
    return tok.reshape(B, N, d)


def deembed_tokens_flex(w_flex, b_flex, tok, latent_shape, p: Patch,
                        p_prime: Patch, c_out: int, block_n: int = 256):
    W = resize.project_deembed(w_flex, p, p_prime)          # [d, c, pp]
    Bb = resize.project_deembed_bias(b_flex, p, p_prime)    # [c, pp]
    d = W.shape[0]
    K = W.shape[1] * W.shape[2]
    B, N = tok.shape[:2]
    out = patch_deembed_pallas(tok.reshape(B * N, d),
                               W.reshape(d, K).astype(tok.dtype),
                               Bb.reshape(K).astype(tok.dtype),
                               block_n=min(block_n, B * N))
    # kernel output layout is [.., c*pp]; unpatchify expects [.., pp, c]
    pp = W.shape[2]
    patches = out.reshape(B, N, c_out, pp).transpose(0, 1, 3, 2)
    return patch_mod.unpatchify(patches, latent_shape, p)
