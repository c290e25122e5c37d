"""Needed work of Stable Diffusion 3.5 Medium's transformer (MMDiT-X):
the operations and bytes the algorithm requires, computed from the
configuration file's sizes, and the patch mode of each denoising step
that a relative-compute budget gives.

Counts are of useful work only: each request's real image and text
tokens (no padding, no dummy pack slots), dense attention over one
request's [image; text] tokens (no tile rounding), CFG counted as the
two forward passes it needs. A multiply-add is 2 operations. These
functions are the benchmark's own and import nothing of the program.

Per block, each stream (image: the patch tokens; text: ``text_tokens``)
has q, k, v and output projections, an MLP of ``mlp_ratio``·d and an
adaLN-Zero of 6·d per request; one attention runs over the request's
image and text tokens together. The leading ``dual_attention_layers``
blocks add an image-only self-attention with its own projections and a
3·d modulation. The last block's text stream gives keys and values only,
under a 2·d continuous adaLN, so only image queries attend there.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

T_EMB_DIM = 256          # width of the sinusoidal timestep features


def width(cfg: Dict) -> int:
    return cfg["num_attention_heads"] * cfg["attention_head_dim"]


def patch_sizes(cfg: Dict) -> List[Sequence[int]]:
    """Mode 0 is the powerful patch size, higher modes the weak ones."""
    p = cfg["patch_size"]
    return [[1, p, p]] + list(cfg["flex_patch_sizes"])


def tokens(cfg: Dict, mode: int) -> int:
    """Image tokens of one request at ``mode``'s patch size."""
    p = patch_sizes(cfg)[mode]
    return (cfg["sample_size"] // p[1]) * (cfg["sample_size"] // p[2])


def attention_flops(n_q: int, n_k: int, d: int) -> float:
    """QK^T and PV of n_q queries over n_k keys, all heads, one layer."""
    return 4.0 * n_q * n_k * d


def attention_bytes(n_q: int, n_k: int, d: int, itemsize: int = 2
                    ) -> float:
    """Least HBM traffic of one attention: q, k and v read once, the
    output written once."""
    return (2.0 * n_q + 2.0 * n_k) * d * itemsize


def _attentions(cfg: Dict, n: int):
    """(n_q, n_k, layers) of every attention of one forward: the joint
    attention of all but the last block, the last block's image queries
    over both streams' keys, the dual blocks' image-only attention."""
    s = n + cfg["text_tokens"]
    L, n_dual = cfg["num_layers"], len(cfg["dual_attention_layers"])
    return [(s, s, L - 1), (n, s, 1), (n, n, n_dual)]


def _stream(cfg: Dict, n: int) -> float:
    """q, k, v, output projection, MLP and adaLN of one stream's n
    tokens, one block."""
    d = width(cfg)
    return (2.0 * n * d * 4 * d + 2.0 * 2 * n * d * cfg["mlp_ratio"] * d
            + 2.0 * d * 6 * d)


def block_flops(cfg: Dict, mode: int) -> float:
    """Every block's matmuls over one request at ``mode``, attention's
    own QK^T and PV left out (they are the flash kernel's)."""
    d, T = width(cfg), cfg["text_tokens"]
    n, L = tokens(cfg, mode), cfg["num_layers"]
    n_dual = len(cfg["dual_attention_layers"])
    full = _stream(cfg, n) + _stream(cfg, T)
    last = _stream(cfg, n) + 2.0 * T * d * 2 * d + 2.0 * d * 2 * d
    dual = 2.0 * n * d * 4 * d + 2.0 * d * 3 * d
    return (L - 1) * full + last + n_dual * dual


def nfe_flops(cfg: Dict, mode: int) -> float:
    """One forward pass over one request at ``mode``."""
    d, T = width(cfg), cfg["text_tokens"]
    n = tokens(cfg, mode)
    npix = math.prod(patch_sizes(cfg)[mode])
    attn = sum(k * attention_flops(nq, nk, d)
               for nq, nk, k in _attentions(cfg, n))
    return (block_flops(cfg, mode) + attn
            + 2.0 * n * npix * cfg["in_channels"] * d     # patch embedding
            + 2.0 * n * d * npix * cfg["out_channels"]    # de-embedding
            + 2.0 * d * 2 * d                             # final adaLN
            + 2.0 * T * cfg["context_dim"] * d            # context embedder
            + 2.0 * (cfg["pooled_dim"] * d + d * d)       # pooled-text MLP
            + 2.0 * (T_EMB_DIM * d + d * d))              # timestep MLP


def step_modes(cfg: Dict, T: int, budget: float, weak_mode: int = 1
               ) -> List[int]:
    """Patch mode of each of T denoising steps for a relative-compute
    budget: the fewest weak steps, taken first, whose compute relative
    to T powerful steps is at most the budget (FlexiDiT section 3.3)."""
    full = nfe_flops(cfg, 0)
    weak = nfe_flops(cfg, weak_mode)
    for t_weak in range(T + 1):
        rel = (t_weak * weak + (T - t_weak) * full) / (T * full)
        if rel <= budget + 1e-12:
            return [weak_mode] * t_weak + [0] * (T - t_weak)
    raise ValueError(f"budget {budget} is below the all-weak floor")


def request_flops(cfg: Dict, modes: Sequence[int], guided: bool) -> float:
    """Model work of the given denoising steps of one request."""
    mult = 2 if guided else 1
    return mult * sum(nfe_flops(cfg, m) for m in modes)


def request_attention(cfg: Dict, modes: Sequence[int], guided: bool,
                      itemsize: int = 2) -> Dict[str, float]:
    """Needed attention operations and bytes of the given steps: the
    joint and the image-only attentions."""
    mult = 2 if guided else 1
    d = width(cfg)
    fl = by = 0.0
    for m in modes:
        for nq, nk, k in _attentions(cfg, tokens(cfg, m)):
            fl += k * attention_flops(nq, nk, d)
            by += k * attention_bytes(nq, nk, d, itemsize)
    return {"flops": mult * fl, "bytes": mult * by}


def request_block_flops(cfg: Dict, modes: Sequence[int], guided: bool
                        ) -> float:
    """Block matmul work of the given denoising steps of one request."""
    mult = 2 if guided else 1
    return mult * sum(block_flops(cfg, m) for m in modes)
