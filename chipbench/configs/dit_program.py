"""The program under test for the class-conditioned FlexiDiT
configurations: the program's ``ServingEngine`` over a ``FlexiPipeline``
of a DiT with learned sigma, on the configuration's linear noise
schedule, with the traffic mix's sampler at each budget tier; and each
request's condition, a class label drawn uniformly from the seed.

The engine runs on one chip: a deployment over several comes as files
of its own. Everything here that touches the program goes through its
public entry points, imported when a build needs them.
"""
from __future__ import annotations

from typing import Dict, List

from chipbench import spec

_work = spec.load_module(spec.HERE / "configs" / "dit_work.py")


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import AttnConfig, DiTConfig, ModelConfig
    ls = tuple(cfg["latent_shape"])
    return ModelConfig(
        name=cfg["name"], family="dit", num_layers=cfg["num_layers"],
        d_model=cfg["d_model"], d_ff=cfg["d_ff"], vocab_size=0,
        attn=AttnConfig(num_heads=cfg["num_heads"],
                        num_kv_heads=cfg["num_heads"],
                        head_dim=cfg["head_dim"], use_rope=False),
        dit=DiTConfig(latent_shape=ls, patch_size=tuple(cfg["patch_size"]),
                      flex_patch_sizes=tuple(tuple(p) for p in
                                             cfg["flex_patch_sizes"]),
                      underlying_patch_size=tuple(
                          cfg["underlying_patch_size"]),
                      conditioning="class", num_classes=cfg["num_classes"],
                      learn_sigma=cfg["learn_sigma"], lora_rank=0),
        mlp_activation="gelu", norm_type="layernorm",
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"],
        max_seq_len=max(_work.tokens(cfg, m) for m in
                        range(len(_work.patch_sizes(cfg)))))


def check_layout(params, mcfg) -> None:
    """The seeded weights have exactly the tree, shapes and dtype that
    the program's own initialiser would give."""
    import jax
    from repro.models import dit as dit_mod
    want = jax.eval_shape(lambda k: dit_mod.init_dit(mcfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("seeded weights do not match the program's "
                           "parameter layout")


def engine(cfg: Dict, traffic: Dict, params, sched, devices):
    """A frozen ``ServingEngine`` over ``params`` on one device, with
    the mix's sampler at each of its budget tiers on ``sched``."""
    if len(devices) != 1:
        raise ValueError(f"{cfg['name']}: the engine serves from one chip; "
                         f"the cell gives {len(devices)}. A deployment over "
                         f"several chips is a configuration of its own")
    from repro.pipeline import FlexiPipeline, SamplingPlan
    from repro.serving import ServingEngine
    mcfg = model_config(cfg)
    check_layout(params, mcfg)
    pipe = FlexiPipeline(params, mcfg, sched)
    s = traffic["sampler"]
    plans = {float(b): SamplingPlan(T=s["T"], budget=float(b),
                                    solver=s["solver"],
                                    guidance_scale=s["guidance_scale"])
             for b, _w in traffic["tiers"]}
    # frozen serving: after warm-up, only compiled layouts run
    return ServingEngine(pipe, plans, policy=traffic["policy"],
                         allow_cold=False)


def build(cfg: Dict, traffic: Dict, params, devices):
    """The engine on the configuration's linear noise schedule."""
    from repro.diffusion import schedule as sch
    ns = cfg["noise_schedule"]
    if ns["kind"] != "linear":
        raise ValueError(f"{cfg['name']}: noise schedule {ns['kind']!r}; "
                         f"this builder makes the linear one")
    return engine(cfg, traffic, params, sch.linear_schedule(
        ns["steps"], ns["beta_start"], ns["beta_end"]), devices)


def draw_conds(cfg: Dict, rng, n: int) -> List[int]:
    """A class label for each of ``n`` requests, uniform over the
    configuration's classes."""
    return [int(c) for c in rng.integers(0, cfg["num_classes"], n)]
