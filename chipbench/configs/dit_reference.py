"""Plain reference of the class-conditioned FlexiDiT sampler (DDIM,
classifier-free guidance), and the seeded weights that the program and
the reference both run.

It follows DiT (Peebles & Xie 2023: adaLN-Zero blocks, sin-cos positions,
learned sigma) and FlexiDiT (Anagnostidis et al. 2025, section 3: one set
of flexible patch-embedding weights instantiated at each patch size
through the pseudo-inverse of bilinear upsampling, a patch-size embedding
and LayerNorm for the weak mode, weak steps first), in straightforward
``jax.numpy``, float32, ``Precision.HIGHEST``, one image per row, dense
attention, no packing and no kernels. It imports nothing of the program.

Departures from the published DiT, as the program defines the model:
the de-embedding is the flexible weight instantiated at the patch size
(no separate final linear), positions are sin-cos of patch centres in
latent pixels split over (frame, height, width), and the timestep
features are ``[cos, sin]``.

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with one absmax scale per tensor, the step below
the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
T_EMB_DIM = 256
LN_EPS = 1e-6


# ---------------------------------------------------------------------------
# Weights


def weight_layout(cfg: Dict) -> Dict:
    """The parameter tree the program takes, as ``(shape, std)`` leaves."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    c_in = cfg["latent_shape"][-1]
    c_out = 2 * c_in if cfg["learn_sigma"] else c_in
    npp = math.prod(cfg["underlying_patch_size"])
    n_flex = len(cfg["flex_patch_sizes"])
    s = 1.0 / math.sqrt(d)
    b = 0.02
    lay = {
        "embed": {"w_flex": ((npp, c_in, d), 1.0 / math.sqrt(npp * c_in)),
                  "b": ((d,), b)},
        "deembed": {"w_flex": ((d, c_out, npp), s),
                    "b_flex": ((c_out, npp), b)},
        "t_embed": {"w1": ((T_EMB_DIM, d), 1.0 / math.sqrt(T_EMB_DIM)),
                    "b1": ((d,), b), "w2": ((d, d), s), "b2": ((d,), b)},
        "final": {"ada": {"w": ((d, 2 * d), 0.5 * s), "b": ((2 * d,), b)}},
        "blocks": {
            "ada": {"w": ((L, d, 6 * d), 0.5 * s), "b": ((L, 6 * d), b)},
            "attn": {k: ((L, d, d), s) for k in ("wq", "wk", "wv", "wo")},
            "mlp": {"w_in": ((L, d, f), s), "b_in": ((L, f), b),
                    "w_out": ((L, f, d), 1.0 / math.sqrt(f)),
                    "b_out": ((L, d), b)}},
        "class_embed": ((cfg["num_classes"] + 1, d), 0.3),
    }
    if n_flex:
        lay["ps_embed"] = ((n_flex, d), b)
        lay["ps_ln"] = {"scale": ((n_flex, d), 0.1), "bias": ((n_flex, d), b)}
    return lay


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_params(cfg: Dict, seed: int, device=None):
    """Every weight from the seed in one jitted call on the device, in the
    dtype the configuration serves in."""
    layout = weight_layout(cfg)
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    dtype = jnp.dtype(cfg["param_dtype"])

    def build(key):
        out = [(jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std).astype(dtype)
               for i, (shape, std) in enumerate(leaves)]
        return jax.tree.unflatten(treedef, out)

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# Model


def patch_sizes(cfg: Dict) -> List[Tuple[int, int, int]]:
    return [tuple(cfg["patch_size"])] + [tuple(p) for p in
                                         cfg["flex_patch_sizes"]]


def _upsample_matrix(a: Tuple[int, ...], pp: Tuple[int, ...]) -> np.ndarray:
    """Linear upsampling of a flattened patch from shape a to pp."""
    n_in = math.prod(a)
    basis = np.eye(n_in, dtype=np.float32).reshape((n_in,) + tuple(a))
    with jax.ensure_compile_time_eval():
        up = jax.image.resize(jnp.asarray(basis), (n_in,) + tuple(pp),
                              method="linear")
    return np.asarray(up, np.float64).reshape(n_in, math.prod(pp)).T


@functools.lru_cache(maxsize=None)
def _q_embed(a: Tuple[int, ...], pp: Tuple[int, ...]) -> np.ndarray:
    """Pseudo-inverse of the upsampling: [prod(a), prod(pp)]."""
    return np.linalg.pinv(_upsample_matrix(a, pp)).astype(np.float32)


def _patchify(x, p):
    B, F, H, W, C = x.shape
    pf, ph, pw = p
    x = x.reshape(B, F // pf, pf, H // ph, ph, W // pw, pw, C)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    return x.reshape(B, -1, pf * ph * pw, C)


def _unpatchify(tok, latent_shape, p):
    F, H, W, _ = latent_shape
    pf, ph, pw = p
    B, _, _, C = tok.shape
    x = tok.reshape(B, F // pf, H // ph, W // pw, pf, ph, pw, C)
    x = x.transpose(0, 1, 4, 2, 5, 3, 6, 7)
    return x.reshape(B, F, H, W, C)


def _pos_embed(latent_shape, p, d) -> np.ndarray:
    """Sin-cos features of the patch centres in latent pixels; the width
    is split over (frame, height, width), the frame taking the rest."""
    F, H, W, _ = latent_shape
    axes = [(np.arange(n // q) + 0.5) * q for n, q in zip((F, H, W), p)]
    grid = np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(-1, 3)
    d_axis = d // 3
    parts = []
    for ax in range(3):
        dd = d - 2 * d_axis if ax == 0 else d_axis
        half = dd // 2
        freqs = 1.0 / 10_000.0 ** (np.arange(half) / max(1, half))
        arg = grid[:, ax:ax + 1] * freqs[None]
        emb = np.concatenate([np.sin(arg), np.cos(arg)], 1)
        parts.append(np.pad(emb, ((0, 0), (0, dd - emb.shape[1]))))
    return np.concatenate(parts, 1).astype(np.float32)


def _quantizer(precision: str):
    if precision == "f32":
        return lambda a: a
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)

    def q(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / fmax
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    return q


def _layer_norm(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS)


def forward(P, x, t, labels, cfg: Dict, mode: int, precision: str = "f32"):
    """Epsilon prediction of one forward pass. x: [B, F, H, W, C] f32;
    t: [B]; labels: [B] int (num_classes is the null label)."""
    q = _quantizer(precision)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b), precision=HI)

    d, H = cfg["d_model"], cfg["num_heads"]
    hd = cfg["head_dim"]
    ls = tuple(cfg["latent_shape"])
    p = patch_sizes(cfg)[mode]
    pp = tuple(cfg["underlying_patch_size"])
    Qe = jnp.asarray(_q_embed(p, pp))                       # [np, npp]
    w_e = jnp.einsum("qp,pcd->qcd", Qe, P["embed"]["w_flex"], precision=HI)
    tok = mm("bnqc,qcd->bnd", _patchify(x, p), w_e) + P["embed"]["b"]
    tok = tok + jnp.asarray(_pos_embed(ls, p, d))[None]
    if mode > 0:
        tok = tok + P["ps_embed"][mode - 1]
        tok = (_layer_norm(tok) * (1.0 + P["ps_ln"]["scale"][mode - 1])
               + P["ps_ln"]["bias"][mode - 1])

    half = T_EMB_DIM // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    arg = t.astype(jnp.float32)[:, None] * freqs[None]
    te = jnp.concatenate([jnp.cos(arg), jnp.sin(arg)], -1)
    te = mm("bk,kd->bd", te, P["t_embed"]["w1"]) + P["t_embed"]["b1"]
    te = mm("bk,kd->bd", jax.nn.silu(te), P["t_embed"]["w2"]) \
        + P["t_embed"]["b2"]
    c = te + P["class_embed"][labels]
    sc = jax.nn.silu(c)

    def block(h, bp):
        ada = mm("bk,kd->bd", sc, bp["ada"]["w"]) + bp["ada"]["b"]
        sh1, s1, g1, sh2, s2, g2 = jnp.split(ada, 6, -1)
        a = _layer_norm(h) * (1 + s1[:, None]) + sh1[:, None]
        B, N, _ = a.shape
        qh = mm("bnd,de->bne", a, bp["attn"]["wq"]).reshape(B, N, H, hd)
        kh = mm("bnd,de->bne", a, bp["attn"]["wk"]).reshape(B, N, H, hd)
        vh = mm("bnd,de->bne", a, bp["attn"]["wv"]).reshape(B, N, H, hd)
        s = mm("bqhd,bkhd->bhqk", qh, kh) / math.sqrt(hd)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vh)
        h = h + g1[:, None] * mm("bnd,de->bne", o.reshape(B, N, d),
                                 bp["attn"]["wo"])
        m = _layer_norm(h) * (1 + s2[:, None]) + sh2[:, None]
        m = mm("bnd,df->bnf", m, bp["mlp"]["w_in"]) + bp["mlp"]["b_in"]
        m = jax.nn.gelu(m, approximate=True)
        m = mm("bnf,fd->bnd", m, bp["mlp"]["w_out"]) + bp["mlp"]["b_out"]
        return h + g2[:, None] * m, None

    tok, _ = jax.lax.scan(block, tok, P["blocks"])
    ada = mm("bk,kd->bd", sc, P["final"]["ada"]["w"]) + P["final"]["ada"]["b"]
    sh, s = jnp.split(ada, 2, -1)
    tok = _layer_norm(tok) * (1 + s[:, None]) + sh[:, None]
    Qd = Qe.T                                                # [npp, np]
    w_d = jnp.einsum("dcp,pq->dcq", P["deembed"]["w_flex"], Qd, precision=HI)
    b_d = jnp.einsum("cp,pq->cq", P["deembed"]["b_flex"], Qd, precision=HI)
    out = mm("bnd,dcq->bnqc", tok, w_d) + b_d.T[None, None]
    out = _unpatchify(out, ls, p)
    return out[..., :ls[-1]]


# ---------------------------------------------------------------------------
# Sampler


def linear_alphas_cumprod(sched: Dict) -> np.ndarray:
    betas = np.linspace(sched["beta_start"], sched["beta_end"],
                        sched["steps"], dtype=np.float64)
    return np.cumprod(1.0 - betas)


def timesteps(n_train: int, T: int) -> np.ndarray:
    """T timesteps spread evenly over the training range, descending."""
    return np.linspace(0, n_train - 1, T).round().astype(np.int64)[::-1]


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode",
                                             "precision"))
def _ddim_step(P, x, labels, t, coefs, guidance, *, cfg_items, mode,
               precision):
    """One guided DDIM step (eta 0) for a batch at one timestep."""
    cfg = dict(cfg_items)
    B = x.shape[0]
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), P)
    null = jnp.full((B,), cfg["num_classes"], jnp.int32)
    eps = forward(f32, jnp.concatenate([x, x]), jnp.full((2 * B,), t),
                  jnp.concatenate([labels, null]), cfg, mode, precision)
    e_c, e_u = jnp.split(eps, 2)
    eps = e_u + guidance * (e_c - e_u)
    sa_t, s1a_t, sa_p, s1a_p = coefs[0], coefs[1], coefs[2], coefs[3]
    x0 = (x - s1a_t * eps) / sa_t
    return sa_p * x0 + s1a_p * eps


def _freeze(cfg: Dict):
    def fz(v):
        if isinstance(v, (list, tuple)):
            return tuple(fz(u) for u in v)
        if isinstance(v, dict):
            return tuple(sorted((k, fz(u)) for k, u in v.items()))
        return v
    return tuple(sorted((k, fz(v)) for k, v in cfg.items()
                        if k in MODEL_KEYS))


MODEL_KEYS = ("num_layers", "d_model", "d_ff", "num_heads", "head_dim",
              "latent_shape", "patch_size", "flex_patch_sizes",
              "underlying_patch_size", "num_classes", "learn_sigma")


def sample(P, cfg: Dict, keys: np.ndarray, conds: Sequence[int],
           modes: Sequence[int], sampler: Dict,
           precision: str = "f32") -> np.ndarray:
    """x0 of a batch of requests that share one step schedule (``modes``,
    one patch mode per step), each conditioned on its class label in
    ``conds``, by the traffic mix's ``sampler`` (``T`` DDIM steps,
    ``guidance_scale``). ``keys``: [B, 2] uint32, each request's PRNG
    key; its prior is ``normal(key, (1, *latent_shape))``."""
    if sampler["solver"] != "ddim":
        raise ValueError(f"the reference samples with DDIM, not "
                         f"{sampler['solver']!r}")
    T, guidance = sampler["T"], sampler["guidance_scale"]
    ls = tuple(cfg["latent_shape"])
    x = jnp.concatenate([jax.random.normal(jnp.asarray(k, jnp.uint32),
                                           (1,) + ls) for k in keys])
    acp = linear_alphas_cumprod(cfg["noise_schedule"])
    ts = timesteps(cfg["noise_schedule"]["steps"], T)
    labels = jnp.asarray(conds, jnp.int32)
    items = _freeze(cfg)
    for i, t in enumerate(ts):
        a_t = acp[t]
        a_p = acp[ts[i + 1]] if i + 1 < len(ts) else 1.0
        coefs = np.asarray([np.sqrt(a_t), np.sqrt(1 - a_t), np.sqrt(a_p),
                            np.sqrt(1 - a_p)], np.float32)
        x = _ddim_step(P, x, labels, jnp.float32(t), jnp.asarray(coefs),
                       jnp.float32(guidance), cfg_items=items,
                       mode=int(modes[i]), precision=precision)
    return np.asarray(x)
