"""Needed work of the class-conditioned FlexiDiT configurations: the
operations and bytes the algorithm requires, computed from a
configuration file's sizes, and the patch mode of each denoising step
that a relative-compute budget gives.

Counts are of useful work only: real segments (no padding rows, no dummy
pack slots), dense per-segment attention (no tile rounding), CFG counted
as the two forward passes it needs. A multiply-add is 2 operations.
These functions are the benchmark's own; they import nothing of the
program, so a change to the program's cost model cannot move them.

The blocks' dense matmuls (:func:`block_flops`) are, per token per
layer, 2·d·3d for q, k and v, 2·d·d for the output projection and
2·2·d·d_ff for the MLP, and per segment per layer 2·d·6d for the adaLN
modulation; attention's own QK^T and PV are the flash kernel's and are
left out.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

T_EMB_DIM = 256          # width of the sinusoidal timestep features


def patch_sizes(cfg: Dict) -> List[Sequence[int]]:
    """Mode 0 is the powerful patch size, higher modes the weak ones."""
    return [cfg["patch_size"]] + list(cfg["flex_patch_sizes"])


def tokens(cfg: Dict, mode: int) -> int:
    """Tokens of one image at ``mode``'s patch size."""
    p = patch_sizes(cfg)[mode]
    f, h, w, _ = cfg["latent_shape"]
    return (f // p[0]) * (h // p[1]) * (w // p[2])


def attention_flops(n: int, d: int) -> float:
    """QK^T and PV of one n-token segment, all heads, one layer."""
    return 4.0 * n * n * d


def attention_bytes(n: int, d: int, itemsize: int = 2) -> float:
    """Least HBM traffic of one segment's attention, one layer: q, k and
    v read once, the output written once."""
    return 4.0 * n * d * itemsize


def layer_flops(cfg: Dict, n: int) -> float:
    """One transformer block over one n-token image."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return (2.0 * n * d * 3 * d          # q, k, v projections
            + 2.0 * n * d * d            # output projection
            + attention_flops(n, d)
            + 2.0 * 2 * n * d * f        # MLP in and out
            + 2.0 * d * 6 * d)           # adaLN modulation (per image)


def nfe_flops(cfg: Dict, mode: int) -> float:
    """One forward pass over one image at ``mode``."""
    d = cfg["d_model"]
    n = tokens(cfg, mode)
    npix = math.prod(patch_sizes(cfg)[mode])
    c_in = cfg["latent_shape"][-1]
    c_out = 2 * c_in if cfg["learn_sigma"] else c_in
    return (cfg["num_layers"] * layer_flops(cfg, n)
            + 2.0 * n * npix * c_in * d              # patch embedding
            + 2.0 * n * d * npix * c_out             # de-embedding
            + 2.0 * (T_EMB_DIM * d + d * d)          # timestep MLP
            + 2.0 * d * 2 * d)                       # final adaLN


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
    """Needed attention operations and bytes of the given steps."""
    mult = 2 if guided else 1
    d, L = cfg["d_model"], cfg["num_layers"]
    fl = sum(attention_flops(tokens(cfg, m), d) for m in modes)
    by = sum(attention_bytes(tokens(cfg, m), d, itemsize) for m in modes)
    return {"flops": mult * L * fl, "bytes": mult * L * by}


def block_flops(cfg: Dict, mode: int) -> float:
    """Every block's matmuls over one image at ``mode``."""
    d, f = cfg["d_model"], cfg["d_ff"]
    n = tokens(cfg, mode)
    per_layer = (n * (2.0 * d * 3 * d + 2.0 * d * d + 2.0 * 2 * d * f)
                 + 2.0 * d * 6 * d)
    return cfg["num_layers"] * per_layer


def request_block_flops(cfg: Dict, modes: Sequence[int], guided: bool
                        ) -> float:
    """Block matmul work of the given denoising steps of one request."""
    mult = 2 if guided else 1
    return mult * sum(block_flops(cfg, m) for m in modes)
