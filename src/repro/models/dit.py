"""Diffusion Transformer (DiT) with FlexiDiT patch-size modes.

Covers the paper's three model classes and the joint text-image form:
  * class-conditioned DiT (adaLN-Zero, DiT-XL/2 style)      — cfg.dit.conditioning == 'class'
  * text-conditioned T2I/Emu (cross-attention conditioning) — 'text'
  * video DiT (3D patches; same blocks, longer sequences)   — latent_shape[0] > 1
  * MMDiT(-X) (SD3 / SD3.5: text and image tokens, each through its own
    weights, share one attention; dual blocks add image-only
    self-attention)                                          — 'joint'

A *mode* is an index into ``patch_sizes(cfg) = [p_powerful, *flex sizes]``.
mode 0 is the pre-trained ("powerful") patch size; higher modes are "weak".
Mode selection is static (token count changes), so each mode jit-compiles to
its own executable — exactly the two-executable scheme used at inference.

LoRA recipe (§3.2): ``blocks.lora`` holds per-new-mode adapters on the self-
attention and MLP projections (cross-attention deliberately frozen, App. C.2).
mode 0 never touches LoRAs / the patch-size embedding / the per-mode LN, so
the pre-trained forward pass is preserved bit-exactly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import patch as patch_mod
from repro.core import resize
from repro.models.common import (ParamSpec, dtype_of, init_tree, layer_norm,
                                 softcap, spec_tree, stack_schema,
                                 timestep_embedding)

Params = Dict[str, Any]
Patch = Tuple[int, int, int]

T_EMB_DIM = 256


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["delta", "refresh"], meta_fields=["split"])
@dataclasses.dataclass
class BlockCache:
    """Cross-step activation cache handed to :func:`dit_forward`
    (DESIGN.md §cache): ``delta`` is the deep-block residual recorded at
    the last refresh ([B_eff, N, d], matching the token stream),
    ``refresh`` a traced scalar bool, and ``split`` the static number of
    shallow blocks that always recompute. When present, the forward
    returns ``(out, new_delta)`` and the deep blocks [split, L) only run
    on refresh steps (``lax.cond`` — skip steps pay shallow compute
    only, then replay ``delta``)."""
    delta: jax.Array
    refresh: jax.Array
    split: int


def patch_sizes(cfg: ModelConfig) -> Tuple[Patch, ...]:
    return (cfg.dit.patch_size,) + tuple(cfg.dit.flex_patch_sizes)


def tokens_for_mode(cfg: ModelConfig, mode: int) -> int:
    return patch_mod.num_tokens(cfg.dit.latent_shape, patch_sizes(cfg)[mode])


def segment_tokens(cfg: ModelConfig, mode: int) -> int:
    """Tokens one request's NFE puts in a packed row at ``mode``: its
    image tokens, and under joint conditioning its text tokens too."""
    n = tokens_for_mode(cfg, mode)
    return n + cfg.dit.text_len if cfg.dit.conditioning == "joint" else n


def c_out_dim(cfg: ModelConfig) -> int:
    c_in = cfg.dit.latent_shape[-1]
    return 2 * c_in if cfg.dit.learn_sigma else c_in


# ---------------------------------------------------------------------------
# Schema


def _lora_pair(d_in: int, d_out: int, n_new: int, r: int) -> Params:
    return {"a": ParamSpec((n_new, d_in, r), (None, "embed", None), scale=0.02),
            "b": ParamSpec((n_new, r, d_out), (None, None, "embed"), init="zeros")}


def _qk_norm_schema(cfg: ModelConfig) -> Params:
    """Learned per-head RMS-norm scales of q and k, where the config
    states QK-norm."""
    if not cfg.attn.qk_norm:
        return {}
    hd = cfg.attn.head_dim
    return {"q_norm": ParamSpec((hd,), (None,), init="ones"),
            "k_norm": ParamSpec((hd,), (None,), init="ones")}


def _mlp_schema(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    return {"w_in": ParamSpec((d, cfg.d_ff), ("embed", "mlp")),
            "b_in": ParamSpec((cfg.d_ff,), ("mlp",), init="zeros"),
            "w_out": ParamSpec((cfg.d_ff, d), ("mlp", "embed")),
            "b_out": ParamSpec((d,), ("embed",), init="zeros")}


def _ada_schema(d: int, n: int) -> Params:
    return {"w": ParamSpec((d, n * d), ("embed", "mlp"), init="zeros"),
            "b": ParamSpec((n * d,), ("mlp",), init="zeros")}


def _joint_attn_schema(cfg: ModelConfig, kv_only: bool = False) -> Params:
    """q, k, v and output projections with biases (SD3's ``Attention``);
    ``kv_only``: keys and values alone."""
    d = cfg.d_model
    names = ("k", "v") if kv_only else ("q", "k", "v", "o")
    s: Params = {}
    for n in names:
        s[f"w{n}"] = ParamSpec((d, d), ("embed", "heads") if n != "o"
                               else ("heads", "embed"))
        s[f"b{n}"] = ParamSpec((d,), ("embed",), init="zeros")
    norms = _qk_norm_schema(cfg)
    if kv_only:
        norms.pop("q_norm", None)
    return {**s, **norms}


def joint_block_schema(cfg: ModelConfig, dual: bool = False,
                       last: bool = False) -> Params:
    """One MMDiT block: an image stream ``x`` and a text stream ``c``,
    each with its own adaLN-Zero, attention projections and MLP. A dual
    (MMDiT-X) block's image stream adds ``attn2``, an image-only
    self-attention, and its three modulations (``ada2``: SD3.5's 9·d
    adaLN split 6·d + 3·d). The last block's text stream only gives keys
    and values, under a continuous adaLN (scale, shift)."""
    d = cfg.d_model
    x: Params = {"ada": _ada_schema(d, 6), "attn": _joint_attn_schema(cfg),
                 "mlp": _mlp_schema(cfg)}
    if dual:
        x["ada2"] = _ada_schema(d, 3)
        x["attn2"] = _joint_attn_schema(cfg)
    if last:
        c: Params = {"ada": _ada_schema(d, 2),
                     "attn": _joint_attn_schema(cfg, kv_only=True)}
    else:
        c = {"ada": _ada_schema(d, 6), "attn": _joint_attn_schema(cfg),
             "mlp": _mlp_schema(cfg)}
    return {"x": x, "c": c}


def joint_layer_groups(cfg: ModelConfig) -> Tuple[Tuple[str, int], ...]:
    """The joint model's blocks, in order, as ``(params key, depth)``
    groups: the leading dual blocks, the plain ones, the last."""
    n_dual = cfg.dit.dual_attention_layers
    n_plain = cfg.num_layers - 1 - n_dual
    if n_plain < 0:
        raise ValueError(f"{n_dual} dual blocks leave no last block in "
                         f"{cfg.num_layers} layers")
    return tuple((k, n) for k, n in (("dual_blocks", n_dual),
                                     ("blocks", n_plain), ("last_block", 1))
                 if n)


def dit_block_schema(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    dc = cfg.dit.text_dim or d
    n_new = len(cfg.dit.flex_patch_sizes)
    r = cfg.dit.lora_rank
    s: Params = {
        "ada": {"w": ParamSpec((d, 6 * d), ("embed", "mlp"), init="zeros"),
                "b": ParamSpec((6 * d,), ("mlp",), init="zeros")},
        "attn": {"wq": ParamSpec((d, d), ("embed", "heads")),
                 "wk": ParamSpec((d, d), ("embed", "heads")),
                 "wv": ParamSpec((d, d), ("embed", "heads")),
                 "wo": ParamSpec((d, d), ("heads", "embed")),
                 **_qk_norm_schema(cfg)},
        "mlp": _mlp_schema(cfg),
    }
    if cfg.dit.conditioning == "text":
        s["xattn"] = {"wq": ParamSpec((d, d), ("embed", "heads")),
                      "wk": ParamSpec((dc, d), ("embed", "heads")),
                      "wv": ParamSpec((dc, d), ("embed", "heads")),
                      "wo": ParamSpec((d, d), ("heads", "embed"), init="zeros")}
    if r > 0 and n_new > 0:
        s["lora"] = {
            "attn": {k: _lora_pair(d, d, n_new, r) for k in ("wq", "wk", "wv", "wo")},
            "mlp": {"w_in": _lora_pair(d, cfg.d_ff, n_new, r),
                    "w_out": _lora_pair(cfg.d_ff, d, n_new, r)},
        }
    return s


def dit_schema(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    dit = cfg.dit
    pp = dit.underlying_patch_size
    c_in = dit.latent_shape[-1]
    n_modes = 1 + len(dit.flex_patch_sizes)
    s: Params = {
        "embed": {"w_flex": ParamSpec((int(np.prod(pp)), c_in, d),
                                      (None, None, "embed")),
                  "b": ParamSpec((d,), ("embed",), init="zeros")},
        "deembed": {"w_flex": ParamSpec((d, c_out_dim(cfg), int(np.prod(pp))),
                                        ("embed", None, None), init="zeros"),
                    "b_flex": ParamSpec((c_out_dim(cfg), int(np.prod(pp))),
                                        (None, None), init="zeros")},
        "t_embed": {"w1": ParamSpec((T_EMB_DIM, d), (None, "embed")),
                    "b1": ParamSpec((d,), ("embed",), init="zeros"),
                    "w2": ParamSpec((d, d), ("embed", "mlp")),
                    "b2": ParamSpec((d,), ("embed",), init="zeros")},
        "final": {"ada": {"w": ParamSpec((d, 2 * d), ("embed", "mlp"), init="zeros"),
                          "b": ParamSpec((2 * d,), ("mlp",), init="zeros")}},
    }
    if dit.conditioning == "joint":
        for key, n in joint_layer_groups(cfg):
            block = joint_block_schema(cfg, dual=key == "dual_blocks",
                                       last=key == "last_block")
            s[key] = block if key == "last_block" else stack_schema(block, n)
        dc, dp = dit.text_dim or d, dit.pooled_dim
        s["context_embed"] = {"w": ParamSpec((dc, d), (None, "embed")),
                              "b": ParamSpec((d,), ("embed",), init="zeros")}
        s["pooled_embed"] = {"w1": ParamSpec((dp, d), (None, "embed")),
                             "b1": ParamSpec((d,), ("embed",), init="zeros"),
                             "w2": ParamSpec((d, d), ("embed", "mlp")),
                             "b2": ParamSpec((d,), ("embed",), init="zeros")}
        # the null prompt's encoder outputs: CFG's unconditional segment
        s["null_text"] = {"context": ParamSpec((dit.text_len, dc),
                                               (None, None), init="zeros"),
                          "pooled": ParamSpec((dp,), (None,), init="zeros")}
    else:
        s["blocks"] = stack_schema(dit_block_schema(cfg), cfg.num_layers)
    if n_modes > 1:
        s["ps_embed"] = ParamSpec((n_modes - 1, d), (None, "embed"), init="zeros")
        s["ps_ln"] = {"scale": ParamSpec((n_modes - 1, d), (None, "embed"), init="zeros"),
                      "bias": ParamSpec((n_modes - 1, d), (None, "embed"), init="zeros")}
    if dit.lora_rank > 0 and n_modes > 1:
        # LoRA recipe (§3.2): brand-new (de-)embedding layers per new patch
        # size — the shared flex weights stay frozen so the pre-trained
        # forward pass is bit-exact at mode 0.
        s["embed_new"] = {}
        s["deembed_new"] = {}
        for m, p in enumerate(dit.flex_patch_sizes, start=1):
            npix = int(np.prod(p))
            s["embed_new"][f"m{m}"] = {
                "w": ParamSpec((npix, c_in, d), (None, None, "embed")),
                "b": ParamSpec((d,), ("embed",), init="zeros")}
            s["deembed_new"][f"m{m}"] = {
                "w": ParamSpec((d, c_out_dim(cfg), npix), ("embed", None, None),
                               init="zeros"),
                "b": ParamSpec((c_out_dim(cfg), npix), (None, None), init="zeros")}
    if dit.conditioning == "class":
        s["class_embed"] = ParamSpec((dit.num_classes + 1, d), (None, "embed"),
                                     init="embed")
    elif dit.conditioning == "text":
        dc = dit.text_dim or d
        s["text_proj"] = ParamSpec((dc, dc), (None, "embed"))
    return s


def init_dit(cfg: ModelConfig, key: jax.Array) -> Params:
    return init_tree(dit_schema(cfg), key, dtype_of(cfg.param_dtype))


def dit_partition_specs(cfg: ModelConfig, rules: Dict[str, Any]) -> Params:
    return spec_tree(dit_schema(cfg), rules)


# ---------------------------------------------------------------------------
# Forward


def _linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
            lora: Optional[Params] = None, mode: int = 0,
            lora_scale: float = 2.0) -> jax.Array:
    y = jnp.einsum("...d,de->...e", x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    if lora is not None and mode > 0:
        a = lora["a"][mode - 1].astype(x.dtype)
        bb = lora["b"][mode - 1].astype(x.dtype)
        r = a.shape[-1]
        y = y + jnp.einsum("...r,re->...e", jnp.einsum("...d,dr->...r", x, a), bb,
                           preferred_element_type=jnp.float32) * (lora_scale / r)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _modulate(x: jax.Array, shift: jax.Array, scale: jax.Array) -> jax.Array:
    return x * (1.0 + scale[:, None]) + shift[:, None]


def _qk_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6
             ) -> jax.Array:
    """RMS norm over each head's ``[..., hd]`` features, times a learned
    scale (SD3's ``RMSNorm(head_dim, eps=1e-6)``)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array,
            segment_ids: Optional[jax.Array] = None, unroll: bool = False,
            attn_backend: str = "auto") -> jax.Array:
    """Bidirectional attention of q, k, v ``[B, N, H, hd]`` on the
    backend ``attn_backend`` resolves to; tokens attend within their
    segment, ids < 0 mark padding. Returns ``[B, N, H, hd]``."""
    B, N, H, hd = q.shape
    from repro.models import attention as attn_mod
    resolved = attn_mod.resolve_backend(attn_backend, n_tokens=N,
                                        segmented=segment_ids is not None)
    if resolved == "pallas":
        # segment-aware flash kernel with block-sparse cross-segment
        # skipping: packed rows never issue fully-masked score tiles
        from repro.kernels.attention import ops as attn_ops
        return attn_ops.flash_attention(q, k, v, causal=False,
                                        segment_ids=segment_ids)
    if resolved == "xla-blocked":
        # long (possibly packed) video sequences: flash-style blocked path
        # with q blocks sharded over the model axis; segment ids thread
        # through so packed CFG never materializes [B,H,N,N] scores
        from repro.configs.base import AttnConfig
        acfg = AttnConfig(num_heads=H, num_kv_heads=H, head_dim=hd,
                          use_rope=False)
        pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
        return attn_mod.blocked_gqa_attend(q, k, v, positions=pos,
                                           causal=False, window=0, cfg=acfg,
                                           unroll=unroll,
                                           segment_ids=segment_ids)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    if segment_ids is not None:
        from repro.kernels.attention import mask as mask_mod
        mask = mask_mod.segment_allowed(segment_ids, segment_ids)
        scores = scores + jnp.where(mask, 0.0, -1e30)[:, None]
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _mha(p: Params, x: jax.Array, num_heads: int, *,
         lora: Optional[Params] = None, mode: int = 0,
         segment_ids: Optional[jax.Array] = None,
         unroll: bool = False, parallel: Optional[Any] = None,
         attn_backend: str = "auto") -> jax.Array:
    B, N, d = x.shape
    hd = d // num_heads
    la = (lora or {})
    q = _linear(x, p["wq"], lora=la.get("wq"), mode=mode).reshape(B, N, num_heads, hd)
    k = _linear(x, p["wk"], lora=la.get("wk"), mode=mode).reshape(B, N, num_heads, hd)
    v = _linear(x, p["wv"], lora=la.get("wv"), mode=mode).reshape(B, N, num_heads, hd)
    if "q_norm" in p:                    # the config states QK-norm
        with jax.named_scope("qk_norm"):
            q = _qk_norm(q, p["q_norm"])
            k = _qk_norm(k, p["k_norm"])
    if parallel is not None and parallel.sp > 1:
        # sequence-parallel engine: Ulysses all-to-all / ring attention over
        # the mesh's sequence axis (repro.distributed, DESIGN.md
        # §distributed); padding tokens carry segment id -1. The backend
        # selects the post-all-to-all inner attend (Ulysses).
        o = parallel.attend(q, k, v, segment_ids=segment_ids)
    else:
        o = _attend(q, k, v, segment_ids, unroll, attn_backend)
    return _linear(o.reshape(B, N, d), p["wo"], lora=la.get("wo"), mode=mode)


def _cross_mha(p: Params, x: jax.Array, kv: jax.Array, num_heads: int,
               kv_mask: Optional[jax.Array] = None) -> jax.Array:
    B, N, d = x.shape
    hd = d // num_heads
    q = _linear(x, p["wq"]).reshape(B, N, num_heads, hd)
    k = _linear(kv, p["wk"]).reshape(B, kv.shape[1], num_heads, hd)
    v = _linear(kv, p["wv"]).reshape(B, kv.shape[1], num_heads, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    if kv_mask is not None:
        scores = scores + jnp.where(kv_mask[:, None, None], 0.0, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return _linear(o.reshape(B, N, d), p["wo"])


def _ln(x: jax.Array) -> jax.Array:
    """LayerNorm without learned affine (DiT blocks use adaLN modulation)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)


def dit_block_apply(p: Params, x: jax.Array, c: jax.Array, cfg: ModelConfig, *,
                    mode: int = 0, text: Optional[jax.Array] = None,
                    text_mask: Optional[jax.Array] = None,
                    segment_ids: Optional[jax.Array] = None,
                    parallel: Optional[Any] = None,
                    attn_backend: str = "auto") -> jax.Array:
    H = cfg.attn.num_heads
    ada = _linear(jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype),
                  p["ada"]["w"], p["ada"]["b"])
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(ada, 6, axis=-1)
    lora = p.get("lora", {})
    h = _modulate(_ln(x), sh1, sc1)
    x = x + g1[:, None] * _mha(p["attn"], h, H, lora=lora.get("attn"),
                               mode=mode, segment_ids=segment_ids,
                               unroll=cfg.unroll, parallel=parallel,
                               attn_backend=attn_backend)
    if "xattn" in p and text is not None:
        x = x + _cross_mha(p["xattn"], _ln(x), text, H, kv_mask=text_mask)
    h2 = _modulate(_ln(x), sh2, sc2)
    mlp_lora = lora.get("mlp", {})
    h2 = _linear(h2, p["mlp"]["w_in"], p["mlp"]["b_in"],
                 lora=mlp_lora.get("w_in"), mode=mode)
    h2 = jax.nn.gelu(h2.astype(jnp.float32), approximate=True).astype(x.dtype)
    h2 = _linear(h2, p["mlp"]["w_out"], p["mlp"]["b_out"],
                 lora=mlp_lora.get("w_out"), mode=mode)
    return x + g2[:, None] * h2


@functools.lru_cache(maxsize=64)
def _pos_embed_np(latent_shape: Tuple[int, int, int, int], p: Patch,
                  d: int) -> np.ndarray:
    coords = patch_mod.patch_centers(latent_shape, p)
    return patch_mod.sincos_pos_embed(d, coords)


@functools.lru_cache(maxsize=64)
def _cropped_pos_embed_np(latent_shape: Tuple[int, int, int, int], p: Patch,
                          p0: Patch, d: int, max_size: int) -> np.ndarray:
    """SD3's positions (diffusers' ``PatchEmbed`` with
    ``pos_embed_max_size``): sin-cos features of a ``max_size``² grid
    whose coordinates run in units of ``max_size / base`` (base: the
    powerful grid's side), centre-cropped to the powerful grid; the first
    half of the width encodes the column, the second the row. A coarser
    patch ``p`` takes the mean coordinate of the powerful patches it
    covers."""
    F, H, W, _ = latent_shape
    if F != 1 or p[0] != 1:
        raise ValueError("cropped sin-cos positions are for images")
    gh, gw = H // p0[1], W // p0[2]
    if max(gh, gw) > max_size:
        raise ValueError(f"a {gh}x{gw} grid exceeds pos_embed_max_size "
                         f"{max_size}")
    scale = gh / max_size

    def coords(n, start, s):
        return (start + s * np.arange(n) + (s - 1) / 2.0) * scale

    def sincos(pos):
        omega = 1.0 / 10_000.0 ** (np.arange(d // 4) / (d / 4.0))
        arg = pos[:, None] * omega[None]
        return np.concatenate([np.sin(arg), np.cos(arg)], axis=1)

    rows = sincos(coords(H // p[1], (max_size - gh) // 2, p[1] // p0[1]))
    cols = sincos(coords(W // p[2], (max_size - gw) // 2, p[2] // p0[2]))
    emb = np.concatenate(
        [np.broadcast_to(cols[None], (len(rows),) + cols.shape),
         np.broadcast_to(rows[:, None], (len(rows), len(cols), d // 2))],
        axis=-1)
    return emb.reshape(-1, d).astype(np.float32)


def pos_embed_np(cfg: ModelConfig, latent_shape: Tuple[int, int, int, int],
                 mode: int) -> np.ndarray:
    """Positional embedding ``[N_mode, d]`` of ``mode``'s tokens."""
    p = patch_sizes(cfg)[mode]
    if cfg.dit.pos_embed_max_size:
        return _cropped_pos_embed_np(latent_shape, p, cfg.dit.patch_size,
                                     cfg.d_model, cfg.dit.pos_embed_max_size)
    return _pos_embed_np(latent_shape, p, cfg.d_model)


def condition_vector(params: Params, t: jax.Array, cond: Any,
                     cfg: ModelConfig, dtype: jnp.dtype) -> jax.Array:
    """c = t_emb (+ class emb). t: [B] float; cond: labels [B] or None."""
    te = timestep_embedding(t, T_EMB_DIM).astype(dtype)
    te = _linear(te, params["t_embed"]["w1"], params["t_embed"]["b1"])
    te = jax.nn.silu(te.astype(jnp.float32)).astype(dtype)
    te = _linear(te, params["t_embed"]["w2"], params["t_embed"]["b2"])
    if cfg.dit.conditioning == "class" and cond is not None:
        te = te + jnp.take(params["class_embed"], cond, axis=0).astype(dtype)
    return te


def joint_condition_vector(params: Params, t: jax.Array, pooled: jax.Array,
                           cfg: ModelConfig, dtype: jnp.dtype) -> jax.Array:
    """SD3's conditioning vector: the timestep MLP plus the pooled-text
    MLP. t: [B] float; pooled: [B, pooled_dim]."""
    pe = params["pooled_embed"]
    h = _linear(pooled.astype(dtype), pe["w1"], pe["b1"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(dtype)
    return (condition_vector(params, t, None, cfg, dtype)
            + _linear(h, pe["w2"], pe["b2"]))


def embed_mode_tokens(params: Params, x_t: jax.Array, cfg: ModelConfig,
                      mode: int,
                      latent_shape: Optional[Tuple[int, int, int, int]] = None
                      ) -> jax.Array:
    """Tokenize [B,F,H,W,C] latents at ``mode``'s patch size: per-mode (or
    flex) patch embedding + positional embedding + per-mode LN. Shared by
    the plain forward and the packed (NaViT-style) paths so packed
    segments see bit-identical token streams."""
    dit = cfg.dit
    ls = latent_shape or dit.latent_shape
    p = patch_sizes(cfg)[mode]
    pp = dit.underlying_patch_size
    dtype = dtype_of(cfg.compute_dtype)
    x_t = x_t.astype(dtype)
    if mode > 0 and "embed_new" in params:
        pn = params["embed_new"][f"m{mode}"]
        patches = patch_mod.patchify(x_t, p)
        tok = jnp.einsum("bnqc,qcd->bnd", patches, pn["w"].astype(dtype),
                         preferred_element_type=jnp.float32).astype(dtype)
        tok = tok + pn["b"].astype(dtype)
    else:
        tok = patch_mod.embed_tokens_flex(params["embed"]["w_flex"],
                                          params["embed"]["b"], x_t, p, pp)
    pos = jnp.asarray(pos_embed_np(cfg, ls, mode), dtype)
    tok = tok + pos[None]
    if mode > 0:
        tok = tok + params["ps_embed"][mode - 1].astype(dtype)[None, None]
        tok = layer_norm(tok, 1.0 + params["ps_ln"]["scale"][mode - 1],
                         params["ps_ln"]["bias"][mode - 1])
    return tok


def deembed_mode_tokens(params: Params, tok: jax.Array, cfg: ModelConfig,
                        mode: int,
                        latent_shape: Optional[Tuple[int, int, int, int]] = None
                        ) -> jax.Array:
    """Project [B, N_mode, d] tokens back to [B,F,H,W,c_out] latents
    (inverse of :func:`embed_mode_tokens`, minus the final adaLN which the
    caller applies)."""
    dit = cfg.dit
    ls = latent_shape or dit.latent_shape
    p = patch_sizes(cfg)[mode]
    pp = dit.underlying_patch_size
    dtype = tok.dtype
    if mode > 0 and "deembed_new" in params:
        pn = params["deembed_new"][f"m{mode}"]
        patches = jnp.einsum("bnd,dcq->bnqc", tok, pn["w"].astype(dtype),
                             preferred_element_type=jnp.float32)
        patches = (patches
                   + pn["b"].T.astype(jnp.float32)[None, None]).astype(dtype)
        return patch_mod.unpatchify(patches, ls, p)
    return patch_mod.deembed_tokens_flex(params["deembed"]["w_flex"],
                                         params["deembed"]["b_flex"], tok,
                                         ls, p, pp, c_out_dim(cfg))


def split_blocks(blocks: Params, split: int) -> Tuple[Params, Params]:
    """Slice a stacked block tree into (shallow [0, split), deep
    [split, L)) for the cached forward path."""
    return (jax.tree.map(lambda a: a[:split], blocks),
            jax.tree.map(lambda a: a[split:], blocks))


def dit_forward(params: Params, x_t: jax.Array, t: jax.Array, cond: Any,  # repro: traced
                cfg: ModelConfig, *, mode: int = 0,
                text_mask: Optional[jax.Array] = None,
                latent_shape: Optional[Tuple[int, int, int, int]] = None,
                parallel: Optional[Any] = None,
                block_cache: Optional[BlockCache] = None,
                attn_backend: str = "auto") -> Any:
    """Denoiser NFE.  x_t: [B,F,H,W,C]; t: [B]; cond: labels [B] int32 (class)
    or text embeddings [B,T,dc] (text). Returns [B,F,H,W,c_out].

    ``parallel``: optional ``distributed.engine.SeqParallel`` — tokens are
    padded to the sequence-axis size, scattered across the mesh, and each
    block's attention runs the Ulysses/ring collective; the per-mode token
    count (and hence the sharding) changes at FlexiSchedule phase
    boundaries, which is handled here by re-padding per call.

    ``block_cache``: optional cross-step activation cache (DESIGN.md
    §cache). When given, the return value is ``(out, new_delta)``: on
    refresh steps the deep blocks run and the fresh residual
    ``h_deep - h_shallow`` is returned for the caller to carry; on skip
    steps only the shallow blocks run and the cached delta is replayed.
    A refresh step computes the exact uncached forward (the output IS
    the deep stack's result, not ``shallow + delta`` re-added), which is
    what makes refresh-every-step bit-identical to no cache at all."""
    dit = cfg.dit
    if dit.conditioning == "joint":
        raise ValueError("joint-attention (MMDiT) models run through the "
                         "packed step: core.packing.packed_mixed_forward")
    ls = latent_shape or dit.latent_shape
    dtype = dtype_of(cfg.compute_dtype)
    tok = embed_mode_tokens(params, x_t, cfg, mode, ls)

    n_real = tok.shape[1]
    seg_ids = None
    if parallel is not None and parallel.sp > 1:
        if block_cache is not None:
            raise ValueError("the activation cache does not compose with "
                             "sequence-parallel execution yet (ROADMAP)")
        tok, seg_ids = parallel.pad_and_shard(tok)

    text = None
    if dit.conditioning == "text":
        text = _linear(cond.astype(dtype), params["text_proj"])
        c = condition_vector(params, t, None, cfg, dtype)
    else:
        c = condition_vector(params, t, cond, cfg, dtype)

    def body(h, bp):
        h = dit_block_apply(bp, h, c, cfg, mode=mode, text=text,
                            text_mask=text_mask, segment_ids=seg_ids,
                            parallel=parallel, attn_backend=attn_backend)
        return h, None

    if cfg.remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    from repro.models.common import scan_or_unroll
    new_delta = None
    if block_cache is None:
        tok, _ = scan_or_unroll(body, tok, params["blocks"], cfg.unroll)
    else:
        shallow, deep = split_blocks(params["blocks"],
                                     block_cache.split)
        tok, _ = scan_or_unroll(body, tok, shallow, cfg.unroll)

        def _refresh(args):
            h_s, _delta = args
            h_d, _ = scan_or_unroll(body, h_s, deep, cfg.unroll)
            return h_d, h_d - h_s

        def _replay(args):
            h_s, delta = args
            return h_s + delta, delta

        tok, new_delta = jax.lax.cond(block_cache.refresh, _refresh,
                                      _replay, (tok, block_cache.delta))
    if parallel is not None and tok.shape[1] != n_real:
        tok = parallel.unshard(tok, n_real)

    ada = _linear(jax.nn.silu(c.astype(jnp.float32)).astype(dtype),
                  params["final"]["ada"]["w"], params["final"]["ada"]["b"])
    sh, sc = jnp.split(ada, 2, axis=-1)
    tok = _modulate(_ln(tok), sh, sc)
    out = deembed_mode_tokens(params, tok, cfg, mode, ls)
    return out if block_cache is None else (out, new_delta)


def eps_prediction(out: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Extract the ε-prediction (first c_in channels when learning Σ)."""
    c_in = cfg.dit.latent_shape[-1]
    return out[..., :c_in] if cfg.dit.learn_sigma else out
