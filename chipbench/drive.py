"""Drives the program under test: builds its ``ServingEngine`` over a
``FlexiPipeline`` from a configuration file, and makes the calls into it
(``submit``, ``step``) that :mod:`chipbench.loops` strings together.

Everything here that touches the program goes through its public entry
points. Each harness call into the engine sits in a
``jax.profiler.TraceAnnotation`` span (``chipbench.*``), so a traced
run can say what the host was doing while the device idled.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

from chipbench import generator, work

clock = time.monotonic      # the engine's own default clock


def span(name: str):
    return jax.profiler.TraceAnnotation("chipbench." + name)


def sync() -> None:
    """Wait until the device has finished everything dispatched."""
    with span("sync"):
        jax.block_until_ready(jax.live_arrays())


def program_config(cfg: Dict):
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
        max_seq_len=max(work.tokens(cfg, m) for m in
                        range(1 + len(cfg["flex_patch_sizes"]))))


def check_layout(params, mcfg) -> None:
    """The seeded weights have exactly the tree, shapes and dtype that
    the program's own initialiser would give."""
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


def build_engine(cfg: Dict, traffic: Dict, params):
    from repro.diffusion import schedule as sch
    from repro.pipeline import FlexiPipeline, SamplingPlan
    from repro.serving import ServingEngine
    mcfg = program_config(cfg)
    check_layout(params, mcfg)
    ns = cfg["noise_schedule"]
    pipe = FlexiPipeline(params, mcfg, sch.linear_schedule(
        ns["steps"], ns["beta_start"], ns["beta_end"]))
    s = traffic["sampler"]
    plans = {float(b): SamplingPlan(T=s["T"], budget=float(b),
                                    solver=s["solver"],
                                    guidance_scale=s["guidance_scale"])
             for b, _w in traffic["tiers"]}
    # frozen serving: after warm-up, only compiled layouts run
    return ServingEngine(pipe, plans, policy=traffic["policy"],
                         allow_cold=False)


@dataclasses.dataclass
class Served:
    req: generator.Req
    due: float                       # absolute, on ``clock``
    submitted: float
    rid: int
    counted: bool = True             # in the window's population
    finish: Optional[float] = None   # x0 ready, on ``clock``
    admit: Optional[float] = None
    budget_served: Optional[float] = None
    x0: Optional[jax.Array] = None


def submit(engine, r: generator.Req, due: float,
           counted: bool = True) -> Served:
    with span("submit"):
        rid = engine.submit(cond=r.label, budget=r.budget, key=r.key)
    return Served(r, due, clock(), rid, counted)


def step(engine, by_rid: Dict[int, Served]) -> None:
    with span("step"):
        results = engine.step()
    now = clock()
    for res in results:
        s = by_rid.get(res.request.id)
        if s is not None:
            s.finish, s.admit = now, res.record.admit
            s.budget_served, s.x0 = res.budget_served, res.x0


def inflight_steps(engine) -> Dict[int, int]:
    """Denoising steps already dispatched, per in-flight request id."""
    return {f["id"]: f["step"] for f in engine.snapshot_state()["inflight"]}


def window_steps(cfg: Dict, traffic: Dict, served: List[Served],
                 start: Dict[int, int], end: Dict[int, int], t0: float
                 ) -> Dict[str, float]:
    """Request-steps dispatched between two snapshots, the first taken
    at ``t0``, and their needed work: model operations and the attention
    kernel's operations and bytes, by each request's own schedule."""
    T = traffic["sampler"]["T"]
    guided = traffic["sampler"]["guidance_scale"] != 0.0
    n = 0
    fl = afl = aby = 0.0
    for s in served:
        if s.finish is not None and s.finish < t0:
            continue
        a = start.get(s.rid, 0)
        b = T if s.finish is not None else end.get(s.rid, a)
        if b <= a:
            continue
        modes = work.step_modes(cfg, T, s.req.budget)[a:b]
        n += b - a
        fl += work.request_flops(cfg, modes, guided)
        att = work.request_attention(cfg, modes, guided)
        afl += att["flops"]
        aby += att["bytes"]
    return {"request_steps": n, "model_flops": fl, "attn_flops": afl,
            "attn_bytes": aby}
