"""Plain reference of the test-only DDPM family: the DiT forward pass and
seeded weights of ``configs/dit_reference.py``, sampled by unguided
ancestral DDPM over a cosine noise schedule (Nichol & Dhariwal 2021,
betas clipped at 0.999), float32 at ``Precision.HIGHEST``.

Each visited timestep t takes the one-step posterior q(x_{t-1} | x_t,
x0) at t, its mean and its variance, whatever timestep comes next, as
the program defines its DDPM step. The noise of each step comes from the
request's key as the program derives it: ``fold_in(key, 1)``, folded in
once more per run of steps of one patch mode (weak first) and split over
that run's steps.
"""
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec

_dit = spec.load_module(spec.HERE / "configs" / "dit_reference.py")

make_params = _dit.make_params


def cosine_schedule(sched: Dict) -> Dict[str, np.ndarray]:
    n = sched["steps"]
    t = np.arange(n + 1) / n
    f = np.cos((t + sched["s"]) / (1 + sched["s"]) * np.pi / 2) ** 2
    betas = np.clip(1 - (f[1:] / f[0]) / (f[:-1] / f[0]), 0, 0.999)
    acp = np.cumprod(1.0 - betas)
    acp_prev = np.concatenate([[1.0], acp[:-1]])
    return {"acp": acp, "c0": betas * np.sqrt(acp_prev) / (1.0 - acp),
            "ct": (1.0 - acp_prev) * np.sqrt(1.0 - betas) / (1.0 - acp),
            "var": betas * (1.0 - acp_prev) / (1.0 - acp)}


def step_keys(key, modes: Sequence[int]) -> List:
    run_key = jax.random.fold_in(jnp.asarray(key, jnp.uint32), 1)
    runs: List[int] = []
    for i, m in enumerate(modes):
        if i == 0 or m != modes[i - 1]:
            runs.append(0)
        runs[-1] += 1
    out = []
    for i, n in enumerate(runs):
        out.extend(jax.random.split(jax.random.fold_in(run_key, i), n))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode",
                                             "precision"))
def _ddpm_step(P, x, labels, t, coefs, noise, *, cfg_items, mode,
               precision):
    cfg = dict(cfg_items)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), P)
    eps = _dit.forward(f32, x, jnp.full((x.shape[0],), t), labels, cfg,
                       mode, precision)
    x0 = (x - coefs[1] * eps) / coefs[0]
    return coefs[2] * x0 + coefs[3] * x + coefs[4] * noise


def sample(P, cfg: Dict, keys: np.ndarray, conds: Sequence[int],
           modes: Sequence[int], sampler: Dict,
           precision: str = "f32") -> np.ndarray:
    """x0 of a batch of requests that share one step schedule."""
    if sampler["solver"] != "ddpm" or sampler["guidance_scale"] != 0.0:
        raise ValueError("the reference samples by unguided DDPM")
    ls = tuple(cfg["latent_shape"])
    x = jnp.concatenate([jax.random.normal(jnp.asarray(k, jnp.uint32),
                                           (1,) + ls) for k in keys])
    per_req = [step_keys(k, modes) for k in keys]
    sc = cosine_schedule(cfg["noise_schedule"])
    n = cfg["noise_schedule"]["steps"]
    ts = np.linspace(0, n - 1, sampler["T"]).round().astype(np.int64)[::-1]
    labels = jnp.asarray(conds, jnp.int32)
    for i, t in enumerate(ts):
        coefs = np.asarray([np.sqrt(sc["acp"][t]), np.sqrt(1 - sc["acp"][t]),
                            sc["c0"][t], sc["ct"][t],
                            np.sqrt(sc["var"][t]) if t > 0 else 0.0],
                           np.float32)
        noise = jnp.stack([jax.random.normal(k[i], ls) for k in per_req])
        x = _ddpm_step(P, x, labels, jnp.float32(t), jnp.asarray(coefs),
                       noise, cfg_items=_dit._freeze(cfg),
                       mode=int(modes[i]), precision=precision)
    return np.asarray(x)
