"""Config dataclasses for the repro framework.

Every architecture (the 10 assigned LM-family archs + the paper's own DiT
configs) is described by a ``ModelConfig``. Shapes (train_4k / prefill_32k /
decode_32k / long_500k and the DiT shapes) are described by ``ShapeConfig``.

Configs are plain dataclasses — no framework magic — so they can be
constructed statically in ``src/repro/configs/<arch>.py`` and reduced for
smoke tests via ``reduced()``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Attention


@dataclass(frozen=True)
class AttnConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    # RoPE
    rope_theta: float = 10_000.0
    use_rope: bool = True
    # Gemma-style logit soft-capping (0 disables).
    logit_softcap: float = 0.0
    # Qwen-style bias on the QKV projections.
    qkv_bias: bool = False
    # Sliding-window size for *local* layers (0 = full attention).
    sliding_window: int = 0
    # Pattern of local(L)/global(G) layers, tiled over depth. "G" = all global.
    # gemma3: "LLLLLG" (5 local : 1 global); gemma2: "LG" alternating.
    local_global_pattern: str = "G"
    # QK-norm (RMS over head_dim) — used by Emu-style DiTs and gemma3.
    qk_norm: bool = False

    def window_for_layer(self, layer: int) -> int:
        """Static per-layer window (0 = full)."""
        pat = self.local_global_pattern
        kind = pat[layer % len(pat)]
        return self.sliding_window if kind == "L" else 0


# ---------------------------------------------------------------------------
# MoE


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int = 0
    # d_ff of each routed expert (deepseek-moe uses fine-grained experts).
    expert_d_ff: int = 0
    # capacity factor for sort-based dispatch.
    capacity_factor: float = 1.25
    # router jitter / z-loss coefficients.
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2


# ---------------------------------------------------------------------------
# SSM (Mamba2 / SSD)


@dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    num_heads: int = 0        # SSD heads; 0 → derived as d_inner // head_dim
    head_dim: int = 64
    expand: int = 2           # d_inner = expand * d_model
    chunk_size: int = 64      # SSD chunk length
    conv_width: int = 4       # depthwise conv width


# ---------------------------------------------------------------------------
# DiT / FlexiDiT


@dataclass(frozen=True)
class DiTConfig:
    # Latent input: (frames, height, width, channels). frames=1 → image.
    latent_shape: Tuple[int, int, int, int] = (1, 32, 32, 4)
    # Pre-trained ("powerful") patch size (p_f, p_h, p_w).
    patch_size: Tuple[int, int, int] = (1, 2, 2)
    # Additional ("weak") patch sizes the model is flexified to.
    flex_patch_sizes: Tuple[Tuple[int, int, int], ...] = ((1, 4, 4),)
    # Underlying patch size p' the flexible embed weights are stored at.
    underlying_patch_size: Tuple[int, int, int] = (1, 4, 4)
    # Conditioning: 'class' (adaLN label embedding), 'text' (cross-attn),
    # 'joint' (MMDiT, SD3: text tokens run through their own weights and
    # share every block's attention with the image tokens; the last
    # block only gives the text's keys and values), 'none'
    conditioning: str = "class"
    num_classes: int = 1000
    text_len: int = 77
    text_dim: int = 0            # 0 → d_model
    # 'joint': width of the pooled text vector added to the timestep's
    pooled_dim: int = 0
    # 'joint' (MMDiT-X): the leading blocks whose image stream also runs
    # an image-only self-attention
    dual_attention_layers: int = 0
    # side of the sin-cos grid positions are cropped from (centred; SD3's
    # pos_embed_max_size); 0: positions of the latent's own patch centres
    pos_embed_max_size: int = 0
    learn_sigma: bool = True     # c_out = 2 * c_in
    # LoRA conversion recipe (Sec 3.2); 0 = shared-params recipe (Sec 3.1).
    lora_rank: int = 0


# ---------------------------------------------------------------------------
# Model


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio | dit
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attn: Optional[AttnConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    dit: Optional[DiTConfig] = None
    # Activation for the MLP: 'swiglu' | 'gelu' | 'geglu'
    mlp_activation: str = "swiglu"
    norm_type: str = "rmsnorm"   # rmsnorm | layernorm
    tie_embeddings: bool = False
    # Gemma-style final-logit softcap (0 disables).
    final_logit_softcap: float = 0.0
    # Gemma multiplies embeddings by sqrt(d_model).
    scale_embeddings: bool = False
    # Gemma-2/3 style post-attention/post-ffw norms in addition to pre-norms.
    use_post_norm: bool = False
    # VLM: insert a cross-attention layer every k self-attn layers (0 = none).
    cross_attn_every: int = 0
    vision_tokens: int = 0
    # audio (whisper): encoder layers (decoder layers = num_layers).
    encoder_layers: int = 0
    audio_frames: int = 0
    # dtypes
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # remat policy for training: 'none' | 'block' | 'dots'
    remat: str = "block"
    # Unroll layer/block scans into straight-line HLO. Used by the dry-run
    # cost calibration: XLA cost_analysis counts while-loop bodies ONCE, so
    # FLOPs/collectives inside lax.scan are undercounted by ~L×. The dry-run
    # compiles unrolled 1- and 2-layer variants and extrapolates (see
    # launch/dryrun.py); the scanned compile is kept for the memory proof.
    unroll: bool = False
    # KV-cache storage dtype for decode: "compute" (bf16) or "int8"
    # (per-(position, head) absmax quantization — §Perf addendum: decode is
    # HBM-bound on weights+cache; int8 halves cache bytes).
    kv_cache_dtype: str = "compute"
    # Megatron-style sequence parallelism: shard the residual stream's
    # sequence dim over the model axis between blocks. Cuts saved-activation
    # memory (→ fewer grad-accumulation microbatches → less collective
    # traffic) and converts activation all-reduces into rs/ag pairs. §Perf.
    sequence_parallel: bool = False
    max_seq_len: int = 8192

    # ---- derived -----------------------------------------------------------
    @property
    def head_dim(self) -> int:
        assert self.attn is not None
        return self.attn.head_dim

    def num_params(self) -> int:
        """Analytic parameter count (approximate; embeddings included)."""
        d, f, L, V = self.d_model, self.d_ff, self.num_layers, self.vocab_size
        total = 0
        if self.family != "dit":
            total += V * d                       # token embedding
            if not self.tie_embeddings:
                total += V * d                   # lm head
        att = 0
        if self.attn is not None:
            a = self.attn
            att = d * a.num_heads * a.head_dim + 2 * d * a.num_kv_heads * a.head_dim \
                + a.num_heads * a.head_dim * d
        mlp_mult = 3 if self.mlp_activation in ("swiglu", "geglu") else 2
        ffn = mlp_mult * d * f if f else 0
        moe = 0
        if self.moe is not None:
            m = self.moe
            e_ff = m.expert_d_ff or f
            moe = m.num_experts * mlp_mult * d * e_ff \
                + m.num_shared_experts * mlp_mult * d * e_ff + d * m.num_experts
            ffn = 0
        ssm = 0
        if self.ssm is not None:
            s = self.ssm
            d_in = s.expand * d
            nheads = s.num_heads or max(1, d_in // s.head_dim)
            # in-proj (z, x), B/C projections, dt head bias, out-proj (mamba2)
            ssm = d * 2 * d_in + d * 2 * s.state_dim + d * nheads + d_in * d
        per_layer = att + ffn + moe + ssm + 2 * d  # + norms
        total += L * per_layer
        return total

    def active_params(self) -> int:
        """Active parameters per token (for MoE rooflines)."""
        if self.moe is None:
            return self.num_params()
        d, L = self.d_model, self.num_layers
        m = self.moe
        mlp_mult = 3 if self.mlp_activation in ("swiglu", "geglu") else 2
        e_ff = m.expert_d_ff or self.d_ff
        dense = self.num_params() - L * m.num_experts * mlp_mult * d * e_ff
        active = L * m.num_experts_per_tok * mlp_mult * d * e_ff
        return dense + active

    def reduced(self, **overrides: Any) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        d = 64
        attn = None
        if self.attn is not None:
            a = self.attn
            kv = max(1, min(2, a.num_kv_heads))
            attn = replace(
                a, num_heads=4, num_kv_heads=kv if 4 % kv == 0 else 1,
                head_dim=16, sliding_window=min(a.sliding_window, 32) if a.sliding_window else 0,
            )
        moe = None
        if self.moe is not None:
            moe = replace(self.moe, num_experts=4,
                          num_experts_per_tok=min(2, self.moe.num_experts_per_tok),
                          num_shared_experts=min(1, self.moe.num_shared_experts),
                          expert_d_ff=32 if self.moe.expert_d_ff else 0)
        ssm = None
        if self.ssm is not None:
            ssm = replace(self.ssm, state_dim=16, head_dim=16, chunk_size=16)
        dit = None
        if self.dit is not None:
            dit = replace(self.dit, latent_shape=(self.dit.latent_shape[0] if
                          self.dit.latent_shape[0] == 1 else 4, 16, 16, 4),
                          num_classes=10, text_len=8)
        kw: dict = dict(
            num_layers=2, d_model=d, d_ff=128 if self.d_ff else 0,
            vocab_size=256 if self.vocab_size else 0,
            attn=attn, moe=moe, ssm=ssm, dit=dit,
            encoder_layers=2 if self.encoder_layers else 0,
            audio_frames=16 if self.audio_frames else 0,
            vision_tokens=8 if self.vision_tokens else 0,
            cross_attn_every=2 if self.cross_attn_every else 0,
            param_dtype="float32", compute_dtype="float32",
            max_seq_len=128, remat="none",
        )
        kw.update(overrides)
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Shapes


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str    # 'train' | 'prefill' | 'decode'


LM_SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)


def get_shape(name: str) -> ShapeConfig:
    for s in LM_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


# Archs for which long_500k is skipped (pure full attention — see DESIGN.md).
LONG_CONTEXT_OK = {"mamba2-130m", "hymba-1.5b", "gemma3-4b", "gemma2-9b"}


def cell_is_skipped(arch: str, shape: str) -> Optional[str]:
    """Return a skip-reason string if this (arch, shape) cell is skipped."""
    if shape == "long_500k" and arch not in LONG_CONTEXT_OK and not arch.startswith("dit"):
        return "pure full-attention arch: long_500k needs sub-quadratic mixing (DESIGN.md)"
    return None


# ---------------------------------------------------------------------------
# Training


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 1e-4
    weight_decay: float = 0.0
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1_000
    schedule: str = "cosine"            # cosine | linear | constant
    ema_rate: float = 0.9999
    microbatch: int = 0                 # 0 = no gradient accumulation
    zero_sharded_opt_state: bool = True
    grad_compression: str = "none"      # none | int8_ef
    opt_dtype: str = "float32"          # bf16 moments for 100B+ models
    seed: int = 0
