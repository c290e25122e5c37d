"""Needed dense work of the DiT blocks' matmuls, beside
:mod:`chipbench.work`: the operations the algorithm requires of the
blocks' projections, counted over real segments only (no padding rows,
no dummy pack slots), CFG as the two forward passes it needs. Per token
per layer: 2·d·3d for q, k and v, 2·d·d for the output projection and
2·2·d·d_ff for the MLP; per segment per layer, 2·d·6d for the adaLN
modulation. Attention's own QK^T and PV are the flash kernel's and are
left out.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

from chipbench import work


def block_flops(cfg: Dict, mode: int) -> float:
    """Every block's matmuls over one image at ``mode``."""
    d, f = cfg["d_model"], cfg["d_ff"]
    n = work.tokens(cfg, mode)
    per_layer = (n * (2.0 * d * 3 * d + 2.0 * d * d + 2.0 * 2 * d * f)
                 + 2.0 * d * 6 * d)
    return cfg["num_layers"] * per_layer


def request_block_flops(cfg: Dict, modes: Sequence[int], guided: bool
                        ) -> float:
    """Block matmul work of the given denoising steps of one request."""
    mult = 2 if guided else 1
    return mult * sum(block_flops(cfg, m) for m in modes)


def window_block_flops(cfg: Dict, traffic: Dict, served: List,
                       start: Dict[int, int], end: Dict[int, int],
                       t0: float) -> float:
    """Block matmul work of the request-steps that
    :func:`chipbench.drive.window_steps` counts for the same window."""
    T = traffic["sampler"]["T"]
    guided = traffic["sampler"]["guidance_scale"] != 0.0
    total = 0.0
    for s in served:
        if s.finish is not None and s.finish < t0:
            continue
        a = start.get(s.rid, 0)
        b = T if s.finish is not None else end.get(s.rid, a)
        if b > a:
            total += request_block_flops(
                cfg, work.step_modes(cfg, T, s.req.budget)[a:b], guided)
    return total
