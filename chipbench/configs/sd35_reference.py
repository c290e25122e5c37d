"""Plain reference of Stable Diffusion 3.5 Medium's transformer and its
rectified-flow Euler sampler with classifier-free guidance, and the
seeded weights that the program and the reference both run.

It follows the published model (Esser et al. 2024, "Scaling Rectified
Flow Transformers"; SD3.5's MMDiT-X, diffusers' ``SD3Transformer2DModel``
and ``FlowMatchEulerDiscreteScheduler``) in straightforward ``jax.numpy``,
float32 under ``jax.default_matmul_precision("highest")``, one request's
[image; text] tokens per row, dense attention, no packing, batching of
requests or kernels. It imports nothing of the program.

- Conditioning: timestep features ``[cos, sin]`` of σ·1000 through a
  256 → d → d MLP, plus the pooled text through a pooled_dim → d → d MLP.
  The context (text encoders' hidden states) through a linear
  context_dim → d.
- Each block: per stream (image, text) LayerNorm, adaLN-Zero (shift,
  scale, gate for attention and MLP), q, k, v with biases, per-head RMS
  norm of q and k (learned scale, eps 1e-6), one softmax attention over
  the request's image and text tokens, output projections, a 4·d tanh-GELU
  MLP. The dual blocks (the first ``dual_attention_layers``) add an
  image-only self-attention on the block's normed input under three more
  modulations. The last block's text stream has a continuous adaLN
  (scale, shift) and gives keys and values only.
- Out: a continuous adaLN (scale, shift), then the linear de-embedding.
- Sampler: σ evenly spaced between the shifted σ of the first and of the
  last training timestep, then shifted, σ' = 3σ / (1 + 2σ); Euler steps
  x ← x + (σ_next − σ)·v, the last to σ = 0; v = v_null + s·(v_text −
  v_null) against the null prompt's encoder outputs.

Departures from the published model, as the program defines it:
- the weak mode (FlexiDiT section 3.1, shared-parameter recipe): patch 4,
  a patch-size embedding and LayerNorm on its tokens, weak steps first;
  the patch embedding and de-embedding are one set of flexible weights at
  patch 4 instantiated at each patch size through the pseudo-inverse of
  bilinear upsampling (at patch 2 too, in place of SD3's convolution);
- the weak mode's positions: each weak token takes the mean position of
  the patch-2 tokens it covers on the centre-cropped 384 grid;
- weights, the null prompt and each request's conditions are seeded.

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with one absmax scale per tensor, the step below
the bfloat16 that the configuration states.

Memory: the weights stay in bf16; each block's are upcast inside the
layer scan, one block at a time, and attention runs over query chunks,
so the reference fits beside the served weights on one chip.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec

_dit = spec.load_module(spec.HERE / "configs" / "dit_reference.py")

HI = jax.lax.Precision.HIGHEST
T_EMB_DIM = 256
EPS = 1e-6
Q_CHUNK = 512            # queries per attention chunk


# ---------------------------------------------------------------------------
# Weights


def _width(cfg: Dict) -> int:
    return cfg["num_attention_heads"] * cfg["attention_head_dim"]


def _attn_layout(d: int, hd: int, kv_only: bool = False) -> Dict:
    names = ("k", "v") if kv_only else ("q", "k", "v", "o")
    lay = {}
    for n in names:
        lay[f"w{n}"] = ((d, d), 1.0 / math.sqrt(d))
        lay[f"b{n}"] = ((d,), 0.02)
    # learned RMS-norm scales: 1 + N(0, 0.25^2)
    lay["k_norm"] = ((hd,), 0.25, 1.0)
    if not kv_only:
        lay["q_norm"] = ((hd,), 0.25, 1.0)
    return lay


def _stream_layout(d: int, hd: int, f: int) -> Dict:
    s = 1.0 / math.sqrt(d)
    return {"ada": {"w": ((d, 6 * d), 0.5 * s), "b": ((6 * d,), 0.02)},
            "attn": _attn_layout(d, hd),
            "mlp": {"w_in": ((d, f), s), "b_in": ((f,), 0.02),
                    "w_out": ((f, d), 1.0 / math.sqrt(f)),
                    "b_out": ((d,), 0.02)}}


def _stacked(lay, n: int):
    return jax.tree.map(lambda leaf: ((n,) + leaf[0],) + leaf[1:], lay,
                        is_leaf=_is_leaf)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], tuple)


def block_groups(cfg: Dict) -> List[Tuple[str, int]]:
    """The blocks, in order, as ``(params key, depth)``: the leading dual
    blocks, the plain ones, the last."""
    n_dual = len(cfg["dual_attention_layers"])
    n_plain = cfg["num_layers"] - 1 - n_dual
    return [(k, n) for k, n in (("dual_blocks", n_dual),
                                ("blocks", n_plain), ("last_block", 1)) if n]


def weight_layout(cfg: Dict) -> Dict:
    """The parameter tree the program takes, as ``(shape, std[, mean])``
    leaves."""
    d, hd = _width(cfg), cfg["attention_head_dim"]
    f = cfg["mlp_ratio"] * d
    s, b = 1.0 / math.sqrt(d), 0.02
    c_in, c_out = cfg["in_channels"], cfg["out_channels"]
    npp = math.prod(cfg["underlying_patch_size"])
    dc, dp = cfg["context_dim"], cfg["pooled_dim"]
    lay = {
        "embed": {"w_flex": ((npp, c_in, d), 1.0 / math.sqrt(npp * c_in)),
                  "b": ((d,), b)},
        "deembed": {"w_flex": ((d, c_out, npp), s),
                    "b_flex": ((c_out, npp), b)},
        "t_embed": {"w1": ((T_EMB_DIM, d), 1.0 / math.sqrt(T_EMB_DIM)),
                    "b1": ((d,), b), "w2": ((d, d), s), "b2": ((d,), b)},
        "pooled_embed": {"w1": ((dp, d), 1.0 / math.sqrt(dp)),
                         "b1": ((d,), b), "w2": ((d, d), s),
                         "b2": ((d,), b)},
        "context_embed": {"w": ((dc, d), 1.0 / math.sqrt(dc)),
                          "b": ((d,), b)},
        "null_text": {"context": ((cfg["text_tokens"], dc), 1.0),
                      "pooled": ((dp,), 1.0)},
        "final": {"ada": {"w": ((d, 2 * d), 0.5 * s), "b": ((2 * d,), b)}},
    }
    n_flex = len(cfg["flex_patch_sizes"])
    if n_flex:
        lay["ps_embed"] = ((n_flex, d), b)
        lay["ps_ln"] = {"scale": ((n_flex, d), 0.1),
                        "bias": ((n_flex, d), b)}
    for key, n in block_groups(cfg):
        x = _stream_layout(d, hd, f)
        c = _stream_layout(d, hd, f)
        if key == "dual_blocks":
            x["ada2"] = {"w": ((d, 3 * d), 0.5 * s), "b": ((3 * d,), b)}
            x["attn2"] = _attn_layout(d, hd)
        if key == "last_block":
            c = {"ada": {"w": ((d, 2 * d), 0.5 * s), "b": ((2 * d,), b)},
                 "attn": _attn_layout(d, hd, kv_only=True)}
            lay[key] = {"x": x, "c": c}
        else:
            lay[key] = _stacked({"x": x, "c": c}, n)
    return lay


def make_params(cfg: Dict, seed: int, device=None):
    """Every weight from the seed in one jitted call on the device, in the
    dtype the configuration serves in."""
    leaves, treedef = jax.tree.flatten(weight_layout(cfg), is_leaf=_is_leaf)
    dtype = jnp.dtype(cfg["param_dtype"])

    def build(key):
        out = []
        for i, leaf in enumerate(leaves):
            shape, std = leaf[0], leaf[1]
            mean = leaf[2] if len(leaf) > 2 else 0.0
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((mean + std * z).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    key = _dit.seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# Model


def patch_sizes(cfg: Dict) -> List[Tuple[int, int, int]]:
    p = cfg["patch_size"]
    return [(1, p, p)] + [tuple(q) for q in cfg["flex_patch_sizes"]]


def pos_embed(cfg: Dict, p: Tuple[int, int, int]) -> np.ndarray:
    """SD3's sin-cos positions: a pos_embed_max_size² grid in units of
    max_size / base (base: the patch-2 grid's side), centre-cropped to the
    patch-2 grid; the first half of the width encodes the column, the
    second the row. A patch-4 token takes the mean position of the patch-2
    tokens it covers."""
    d, m = _width(cfg), cfg["pos_embed_max_size"]
    base = cfg["sample_size"] // cfg["patch_size"]
    r = p[1] // cfg["patch_size"]
    n = cfg["sample_size"] // p[1]
    pos = ((m - base) // 2 + r * np.arange(n) + (r - 1) / 2.0) * base / m
    omega = 1.0 / 10_000.0 ** (np.arange(d // 4) / (d / 4.0))
    arg = pos[:, None] * omega[None]
    axis = np.concatenate([np.sin(arg), np.cos(arg)], 1)     # [n, d/2]
    col = np.broadcast_to(axis[None], (n, n, d // 2))
    row = np.broadcast_to(axis[:, None], (n, n, d // 2))
    return np.concatenate([col, row], -1).reshape(n * n, d).astype(
        np.float32)


def _layer_norm(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + EPS) * scale


def forward(P, x, t, context, pooled, cfg: Dict, mode: int,
            precision: str = "f32"):
    """Velocity prediction of one forward pass. x: [B, 1, H, W, C] f32;
    t: [B] (σ·1000); context: [B, text_tokens, context_dim]; pooled:
    [B, pooled_dim]. ``P`` stays in its own dtype: each block's weights
    are upcast to float32 inside the layer scan."""
    q8 = _dit._quantizer(precision)

    def mm(eq, a, b):
        return jnp.einsum(eq, q8(a), q8(b), precision=HI)

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    d, H = _width(cfg), cfg["num_attention_heads"]
    hd = cfg["attention_head_dim"]
    ls = (1, cfg["sample_size"], cfg["sample_size"], cfg["in_channels"])
    p = patch_sizes(cfg)[mode]
    pp = tuple(cfg["underlying_patch_size"])
    top = f32({k: v for k, v in P.items()
               if k not in ("dual_blocks", "blocks", "last_block")})

    Qe = jnp.asarray(_dit._q_embed(p, pp))                  # [np, npp]
    w_e = jnp.einsum("qp,pcd->qcd", Qe, top["embed"]["w_flex"], precision=HI)
    tok = mm("bnqc,qcd->bnd", _dit._patchify(x, p), w_e) + top["embed"]["b"]
    tok = tok + jnp.asarray(pos_embed(cfg, p))[None]
    if mode > 0:
        tok = tok + top["ps_embed"][mode - 1]
        tok = (_layer_norm(tok) * (1.0 + top["ps_ln"]["scale"][mode - 1])
               + top["ps_ln"]["bias"][mode - 1])

    half = T_EMB_DIM // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    arg = t.astype(jnp.float32)[:, None] * freqs[None]
    te = jnp.concatenate([jnp.cos(arg), jnp.sin(arg)], -1)
    te = mm("bk,kd->bd", te, top["t_embed"]["w1"]) + top["t_embed"]["b1"]
    te = mm("bk,kd->bd", jax.nn.silu(te), top["t_embed"]["w2"]) \
        + top["t_embed"]["b2"]
    pe = top["pooled_embed"]
    pc = mm("bk,kd->bd", pooled, pe["w1"]) + pe["b1"]
    pc = mm("bk,kd->bd", jax.nn.silu(pc), pe["w2"]) + pe["b2"]
    sc = jax.nn.silu(te + pc)                                  # [B, d]
    ctx = mm("btk,kd->btd", context, top["context_embed"]["w"]) \
        + top["context_embed"]["b"]

    def heads(a):
        return a.reshape(a.shape[0], a.shape[1], H, hd)

    def qkv(ap, h, kv_only=False):
        def proj(n):
            return heads(mm("bnd,de->bne", h, ap[f"w{n}"]) + ap[f"b{n}"])
        k = _rms(proj("k"), ap["k_norm"])
        v = proj("v")
        q = None if kv_only else _rms(proj("q"), ap["q_norm"])
        return q, k, v

    def attend(q, k, v):
        """Softmax attention, chunked over queries."""
        outs = []
        for a in range(0, q.shape[1], Q_CHUNK):
            s = mm("bqhd,bkhd->bhqk", q[:, a:a + Q_CHUNK], k) / math.sqrt(hd)
            outs.append(mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v))
        o = jnp.concatenate(outs, 1)
        return o.reshape(o.shape[0], o.shape[1], d)

    def modulation(ada, n):
        out = mm("bk,kd->bd", sc, ada["w"]) + ada["b"]
        return [a[:, None] for a in jnp.split(out, n, -1)]

    def mlp(mp, h):
        h = jax.nn.gelu(mm("bnd,df->bnf", h, mp["w_in"]) + mp["b_in"],
                        approximate=True)
        return mm("bnf,fd->bnd", h, mp["w_out"]) + mp["b_out"]

    def block(carry, bp, last=False):
        img, txt = carry
        bp = f32(bp)
        px, pcx = bp["x"], bp["c"]
        n = img.shape[1]
        sh1, s1, g1, sh2, s2, g2 = modulation(px["ada"], 6)
        nx = _layer_norm(img)
        qx, kx, vx = qkv(px["attn"], nx * (1 + s1) + sh1)
        if last:
            c_s, c_sh = modulation(pcx["ada"], 2)
            _, kc, vc = qkv(pcx["attn"], _layer_norm(txt) * (1 + c_s) + c_sh,
                            kv_only=True)
            o = attend(qx, jnp.concatenate([kx, kc], 1),
                       jnp.concatenate([vx, vc], 1))
        else:
            csh1, cs1, cg1, csh2, cs2, cg2 = modulation(pcx["ada"], 6)
            qc, kc, vc = qkv(pcx["attn"],
                             _layer_norm(txt) * (1 + cs1) + csh1)
            o = attend(jnp.concatenate([qx, qc], 1),
                       jnp.concatenate([kx, kc], 1),
                       jnp.concatenate([vx, vc], 1))
        out = img + g1 * (mm("bnd,de->bne", o[:, :n], px["attn"]["wo"])
                          + px["attn"]["bo"])
        if "attn2" in px:
            sh3, s3, g3 = modulation(px["ada2"], 3)
            q2, k2, v2 = qkv(px["attn2"], nx * (1 + s3) + sh3)
            out = out + g3 * (mm("bnd,de->bne", attend(q2, k2, v2),
                                 px["attn2"]["wo"]) + px["attn2"]["bo"])
        img = out + g2 * mlp(px["mlp"], _layer_norm(out) * (1 + s2) + sh2)
        if not last:
            txt = txt + cg1 * (mm("bnd,de->bne", o[:, n:], pcx["attn"]["wo"])
                               + pcx["attn"]["bo"])
            txt = txt + cg2 * mlp(pcx["mlp"],
                                  _layer_norm(txt) * (1 + cs2) + csh2)
        return (img, txt), None

    h = (tok, ctx)
    for key, _n in block_groups(cfg):
        if key == "last_block":
            h, _ = block(h, P[key], last=True)
        else:
            h, _ = jax.lax.scan(block, h, P[key])
    s, sh = modulation(top["final"]["ada"], 2)
    out = _layer_norm(h[0]) * (1 + s) + sh
    Qd = Qe.T                                                # [npp, np]
    w_d = jnp.einsum("dcp,pq->dcq", top["deembed"]["w_flex"], Qd,
                     precision=HI)
    b_d = jnp.einsum("cp,pq->cq", top["deembed"]["b_flex"], Qd, precision=HI)
    out = mm("bnd,dcq->bnqc", out, w_d) + b_d.T[None, None]
    return _dit._unpatchify(out, ls, p)


# ---------------------------------------------------------------------------
# Sampler


def sigmas(sched: Dict, T: int) -> np.ndarray:
    """The T descending σ of the Euler sampler (diffusers'
    ``FlowMatchEulerDiscreteScheduler.set_timesteps``)."""
    shift, n = sched["shift"], sched["steps"]

    def shifted(s):
        return shift * s / (1.0 + (shift - 1.0) * s)
    return shifted(np.linspace(shifted(1.0), shifted(1.0 / n), T))


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode",
                                             "precision"))
def _euler_step(P, x, context, pooled, sigma, sigma_next, guidance, *,
                cfg_items, mode, precision):
    """One guided flow Euler step for a batch at one σ."""
    cfg = dict(cfg_items)
    B = x.shape[0]
    null = P["null_text"]
    ctx = jnp.concatenate([context, jnp.broadcast_to(
        null["context"].astype(jnp.float32)[None], context.shape)])
    pool = jnp.concatenate([pooled, jnp.broadcast_to(
        null["pooled"].astype(jnp.float32)[None], pooled.shape)])
    t = jnp.full((2 * B,), sigma * dict(cfg["noise_schedule"])["steps"])
    v = forward(P, jnp.concatenate([x, x]), t, ctx, pool, cfg, mode,
                precision)
    v_c, v_u = jnp.split(v, 2)
    return x + (sigma_next - sigma) * (v_u + guidance * (v_c - v_u))


def sample(P, cfg: Dict, keys: np.ndarray,
           conds: Sequence[Tuple[np.ndarray, np.ndarray]],
           modes: Sequence[int], sampler: Dict,
           precision: str = "f32") -> np.ndarray:
    """x0 of a batch of requests that share one step schedule (``modes``,
    one patch mode per step), each conditioned on its (context, pooled)
    text encoder outputs in ``conds``, by the traffic mix's ``sampler``
    (``T`` flow Euler steps, ``guidance_scale``). ``keys``: [B, 2]
    uint32, each request's PRNG key; its prior is ``normal(key, (1,
    *latent_shape))``."""
    if sampler["solver"] != "flow_euler":
        raise ValueError(f"the reference samples by flow Euler, not "
                         f"{sampler['solver']!r}")
    ls = (1, cfg["sample_size"], cfg["sample_size"], cfg["in_channels"])
    x = jnp.concatenate([jax.random.normal(jnp.asarray(k, jnp.uint32),
                                           (1,) + ls) for k in keys])
    context = jnp.stack([jnp.asarray(c[0], jnp.float32) for c in conds])
    pooled = jnp.stack([jnp.asarray(c[1], jnp.float32) for c in conds])
    sig = sigmas(cfg["noise_schedule"], sampler["T"])
    items = _freeze(cfg)
    with jax.default_matmul_precision("highest"):
        for i, s in enumerate(sig):
            s_next = sig[i + 1] if i + 1 < len(sig) else 0.0
            x = _euler_step(P, x, context, pooled, jnp.float32(s),
                            jnp.float32(s_next),
                            jnp.float32(sampler["guidance_scale"]),
                            cfg_items=items, mode=int(modes[i]),
                            precision=precision)
    return np.asarray(x)


def _freeze(cfg: Dict):
    def fz(v):
        if isinstance(v, (list, tuple)):
            return tuple(fz(u) for u in v)
        if isinstance(v, dict):
            return tuple(sorted((k, fz(u)) for k, u in v.items()))
        return v
    return tuple(sorted((k, fz(v)) for k, v in cfg.items()
                        if k in MODEL_KEYS))


MODEL_KEYS = ("num_layers", "num_attention_heads", "attention_head_dim",
              "mlp_ratio", "dual_attention_layers", "in_channels",
              "out_channels", "patch_size", "sample_size",
              "pos_embed_max_size", "flex_patch_sizes",
              "underlying_patch_size", "noise_schedule")
