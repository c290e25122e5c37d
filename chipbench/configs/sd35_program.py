"""The program under test for Stable Diffusion 3.5 Medium: the program's
``ServingEngine`` over a ``FlexiPipeline`` of its joint-attention DiT
(MMDiT-X), on the configuration's rectified-flow schedule, with the
traffic mix's sampler (flow Euler, CFG against the null prompt) at each
budget tier; and each request's condition, its text encoders' outputs
drawn from the seed.

The engine runs on one chip and advances one denoising step a dispatch:
a step of this model is 0.1-1 s of device work, so a dispatch's host cost
is far below it, and the warm set stays a handful of executables.
Everything here that touches the program goes through its public entry
points, imported when a build needs them.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench import spec

_dit = spec.load_module(spec.HERE / "configs" / "dit_program.py")
_work = spec.load_module(spec.HERE / "configs" / "sd35_work.py")


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import AttnConfig, DiTConfig, ModelConfig
    H, hd = cfg["num_attention_heads"], cfg["attention_head_dim"]
    d = _work.width(cfg)
    dual = list(cfg["dual_attention_layers"])
    if d != cfg["caption_projection_dim"]:
        raise ValueError(f"{cfg['name']}: {H} x {hd} heads != caption "
                         f"projection {cfg['caption_projection_dim']}")
    if dual != list(range(len(dual))):
        raise ValueError(f"{cfg['name']}: the dual blocks must lead")
    if cfg["qk_norm"] != "rms_norm" or cfg["in_channels"] \
            != cfg["out_channels"]:
        raise ValueError(f"{cfg['name']}: this module makes RMS QK-norm "
                         f"and equal in/out channels")
    p, s = cfg["patch_size"], cfg["sample_size"]
    return ModelConfig(
        name=cfg["name"], family="dit", num_layers=cfg["num_layers"],
        d_model=d, d_ff=cfg["mlp_ratio"] * d, vocab_size=0,
        attn=AttnConfig(num_heads=H, num_kv_heads=H, head_dim=hd,
                        use_rope=False, qk_norm=True),
        dit=DiTConfig(latent_shape=(1, s, s, cfg["in_channels"]),
                      patch_size=(1, p, p),
                      flex_patch_sizes=tuple(tuple(q) for q in
                                             cfg["flex_patch_sizes"]),
                      underlying_patch_size=tuple(
                          cfg["underlying_patch_size"]),
                      conditioning="joint", text_len=cfg["text_tokens"],
                      text_dim=cfg["context_dim"],
                      pooled_dim=cfg["pooled_dim"],
                      dual_attention_layers=len(dual),
                      pos_embed_max_size=cfg["pos_embed_max_size"],
                      learn_sigma=False, lora_rank=cfg["lora_rank"]),
        mlp_activation="gelu", norm_type="layernorm",
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"],
        max_seq_len=_work.tokens(cfg, 0) + cfg["text_tokens"])


def build(cfg: Dict, traffic: Dict, params, devices):
    """A frozen engine over ``params`` on one device, on the
    configuration's flow schedule."""
    if len(devices) != 1:
        raise ValueError(f"{cfg['name']}: the engine serves from one chip; "
                         f"the cell gives {len(devices)}")
    ns = cfg["noise_schedule"]
    if ns["kind"] != "flow":
        raise ValueError(f"{cfg['name']}: noise schedule {ns['kind']!r}; "
                         f"this module makes the rectified-flow one")
    from repro.diffusion.flow import FlowSchedule
    from repro.pipeline import FlexiPipeline, SamplingPlan
    from repro.serving import ServingEngine
    mcfg = model_config(cfg)
    _dit.check_layout(params, mcfg)
    pipe = FlexiPipeline(params, mcfg, FlowSchedule(ns["steps"],
                                                    ns["shift"]))
    s = traffic["sampler"]
    plans = {float(b): SamplingPlan(T=s["T"], budget=float(b),
                                    solver=s["solver"],
                                    guidance_scale=s["guidance_scale"])
             for b, _w in traffic["tiers"]}
    # frozen serving: after warm-up, only compiled layouts run
    return ServingEngine(pipe, plans, policy=traffic["policy"],
                         allow_cold=False, steps_per_dispatch=1)


def draw_conds(cfg: Dict, rng, n: int) -> List[Tuple[np.ndarray,
                                                     np.ndarray]]:
    """Each of ``n`` requests' text encoder outputs, bf16: the context
    ``[text_tokens, context_dim]``, whose first ``clip_tokens`` rows are
    CLIP-L and CLIP-G's ``clip_width`` features zero-padded to
    ``context_dim`` and the rest T5-XXL's, and the pooled CLIP vector
    ``[pooled_dim]``; standard normal where not padding."""
    import ml_dtypes
    T, dc = cfg["text_tokens"], cfg["context_dim"]
    out = []
    for _ in range(n):
        ctx = rng.standard_normal((T, dc), dtype=np.float32)
        ctx[:cfg["clip_tokens"], cfg["clip_width"]:] = 0.0
        pooled = rng.standard_normal(cfg["pooled_dim"], dtype=np.float32)
        out.append((ctx.astype(ml_dtypes.bfloat16),
                    pooled.astype(ml_dtypes.bfloat16)))
    return out
