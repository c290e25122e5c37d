"""FlexiDiT inference scheduler (§3.3) + analytic FLOPs accounting.

The scheduler assigns a *mode* (patch size index) to each denoising step:
weak mode for the first ``T_weak`` steps, powerful mode for the rest. FLOPs
are counted analytically per NFE (mul+add counted separately, paper App C.1)
so compute budgets in benchmarks match the paper's reporting convention.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ModelConfig
from repro.models import dit as dit_mod


@dataclasses.dataclass(frozen=True)
class FlexiSchedule:
    """phases: ((mode, n_steps), ...) executed in order from t=T-1 down."""
    phases: Tuple[Tuple[int, int], ...]

    @property
    def total_steps(self) -> int:
        return sum(n for _, n in self.phases)

    def split_timesteps(self, timesteps: np.ndarray) -> List[Tuple[int, np.ndarray]]:
        """Split a descending timestep ladder across phases."""
        assert len(timesteps) == self.total_steps, (len(timesteps), self)
        out, i = [], 0
        for mode, n in self.phases:
            out.append((mode, timesteps[i:i + n]))
            i += n
        return out

    @staticmethod
    def weak_first(T: int, T_weak: int, weak_mode: int = 1) -> "FlexiSchedule":
        """The paper's scheduler: weak for the first T_weak steps."""
        assert 0 <= T_weak <= T
        return FlexiSchedule(((weak_mode, T_weak), (0, T - T_weak)))

    @staticmethod
    def powerful_first(T: int, T_weak: int, weak_mode: int = 1) -> "FlexiSchedule":
        """Ablation scheduler (App. B.4, shown to be worse)."""
        return FlexiSchedule(((0, T - T_weak), (weak_mode, T_weak)))


# ---------------------------------------------------------------------------
# Analytic FLOPs (mul + add counted separately → factor 2 per MAC)


def dit_block_flops(cfg: ModelConfig, n_tokens: int,
                    text_len: Optional[int] = None,
                    attn_backend: str = "dense") -> float:
    """FLOPs of all transformer blocks over ``n_tokens`` tokens (batch 1).

    Split out from :func:`dit_nfe_flops` so the distributed engine can
    price sequence padding exactly: padded tokens flow through the blocks
    only, never the (de-)embedding (``distributed.partition``).

    ``attn_backend='pallas'``/``'auto'`` prices self-attention at the
    tiles the flash kernel launches (``flash_attention.tile_plan``, sized
    from the row, rounded up to lanes) instead of the exact N² — what the
    device actually issues when the Pallas backend serves the request
    (DESIGN.md §attention-backend).
    """
    if cfg.dit.conditioning == "joint":
        return _joint_block_flops(cfg, n_tokens, text_len, attn_backend)
    N = n_tokens
    d, L, f = cfg.d_model, cfg.num_layers, cfg.d_ff
    per_layer = 0.0
    per_layer += 2 * N * d * (3 * d)          # qkv proj
    per_layer += 2 * N * d * d                # out proj
    if attn_backend in ("pallas", "auto"):
        from repro.kernels.attention import costing
        per_layer += costing.block_sparse_attention_flops([N], N, d)
    else:
        per_layer += 2 * 2 * N * N * d        # QK^T and PV
    per_layer += 2 * 2 * N * d * f            # mlp in/out
    per_layer += 2 * d * 6 * d                # adaLN linear (per sample)
    if cfg.dit.conditioning == "text":
        T = text_len or cfg.dit.text_len
        dc = cfg.dit.text_dim or d
        per_layer += 2 * N * d * d            # xattn q
        per_layer += 2 * 2 * T * dc * d       # xattn k,v
        per_layer += 2 * 2 * N * T * d        # scores + values
        per_layer += 2 * N * d * d            # xattn out
    return float(L * per_layer)


def _attention_flops(n_q: int, n_k: int, d: int, attn_backend: str) -> float:
    """QK^T and PV of n_q queries over n_k keys; at the tiles the flash
    kernel visits under 'pallas'/'auto'."""
    if attn_backend in ("pallas", "auto"):
        from repro.kernels.attention import costing
        return costing.block_sparse_attention_flops([n_k], n_k, d) \
            * n_q / n_k
    return 2.0 * 2 * n_q * n_k * d


def _joint_block_flops(cfg: ModelConfig, n_img: int,
                       text_len: Optional[int], attn_backend: str) -> float:
    """Every MMDiT(-X) block over one request's ``n_img`` image and
    ``text_len`` text tokens: per stream q/k/v, output projection, MLP and
    adaLN; one attention over both streams' tokens; in the dual blocks an
    image-only attention and its adaLN; in the last, text keys and values
    under a continuous adaLN, and image queries alone."""
    T = text_len or cfg.dit.text_len
    d, f, L = cfg.d_model, cfg.d_ff, cfg.num_layers
    S = n_img + T

    def stream(n):          # q, k, v, out projection, MLP, adaLN
        return 2 * n * d * 4 * d + 2 * 2 * n * d * f + 2 * d * 6 * d

    full = stream(n_img) + stream(T) + _attention_flops(S, S, d,
                                                        attn_backend)
    dual = (2 * n_img * d * 4 * d + 2 * d * 3 * d
            + _attention_flops(n_img, n_img, d, attn_backend))
    last = (stream(n_img) + 2 * T * d * 2 * d + 2 * d * 2 * d
            + _attention_flops(n_img, S, d, attn_backend))
    return float((L - 1) * full + cfg.dit.dual_attention_layers * dual
                 + last)


def dit_nfe_flops(cfg: ModelConfig, mode: int = 0,
                  text_len: Optional[int] = None,
                  attn_backend: str = "dense") -> float:
    """FLOPs of one DiT forward (batch 1) at the given patch mode."""
    N = dit_mod.tokens_for_mode(cfg, mode)
    d = cfg.d_model
    p = dit_mod.patch_sizes(cfg)[mode]
    c_in = cfg.dit.latent_shape[-1]
    c_out = dit_mod.c_out_dim(cfg)
    npix = int(np.prod(p))

    total = dit_block_flops(cfg, N, text_len, attn_backend=attn_backend)
    total += 2 * N * npix * c_in * d          # embed
    total += 2 * N * d * npix * c_out         # de-embed
    total += 2 * d * 2 * d                    # final adaLN
    if cfg.dit.conditioning == "joint":
        T = text_len or cfg.dit.text_len
        total += 2 * T * (cfg.dit.text_dim or d) * d          # context embed
        total += 2 * (cfg.dit.pooled_dim * d + d * d)         # pooled MLP
    return float(total)


def lora_nfe_overhead(cfg: ModelConfig, mode: int) -> float:
    """Extra FLOPs/NFE when LoRAs stay unmerged (paper §3.2):
    N·(d_in·r + r·d_out) per adapted projection."""
    if cfg.dit.lora_rank <= 0 or mode == 0:
        return 0.0
    N = dit_mod.tokens_for_mode(cfg, mode)
    d, L, f, r = cfg.d_model, cfg.num_layers, cfg.d_ff, cfg.dit.lora_rank
    per_layer = 0.0
    for d_in, d_out in [(d, d)] * 4 + [(d, f), (f, d)]:
        per_layer += 2 * N * (d_in * r + r * d_out)
    return float(L * per_layer)


def schedule_flops(cfg: ModelConfig, schedule: FlexiSchedule, *,
                   cfg_scale_active: bool = True,
                   guidance_modes: Optional[Sequence[Tuple[int, int]]] = None,
                   lora_unmerged: bool = False,
                   attn_backend: str = "dense") -> float:
    """Total denoising FLOPs for a batch-1 sample under the scheduler.

    ``guidance_modes``: optional per-phase (mode_cond, mode_uncond) for CFG;
    default both at the phase's mode. Without CFG each step is one NFE.
    """
    total = 0.0
    for i, (mode, n) in enumerate(schedule.phases):
        def nfe(m: int) -> float:
            fl = dit_nfe_flops(cfg, m, attn_backend=attn_backend)
            if lora_unmerged:
                fl += lora_nfe_overhead(cfg, m)
            return fl
        if cfg_scale_active:
            mc, mu = (guidance_modes[i] if guidance_modes is not None
                      else (mode, mode))
            total += n * (nfe(mc) + nfe(mu))
        else:
            total += n * nfe(mode)
    return total


def relative_compute(cfg: ModelConfig, schedule: FlexiSchedule, **kw) -> float:
    """Compute fraction vs the all-powerful baseline with the same T."""
    base = FlexiSchedule(((0, schedule.total_steps),))
    return schedule_flops(cfg, schedule, **kw) / schedule_flops(cfg, base, **kw)
