"""Drives the program under test: makes the calls into the engine that
the configuration's program module built (``submit``, ``step``), which
:mod:`chipbench.loops` strings together, and counts the window's needed
work with the configuration's work module.

Everything here that touches the program goes through the engine's
public entry points. Each harness call into the engine sits in a
``jax.profiler.TraceAnnotation`` span (``chipbench.*``), so a traced
run can say what the host was doing while the device idled.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

from chipbench import generator

clock = time.monotonic      # the engine's own default clock


def span(name: str):
    return jax.profiler.TraceAnnotation("chipbench." + name)


def sync() -> None:
    """Wait until the device has finished everything dispatched."""
    with span("sync"):
        jax.block_until_ready(jax.live_arrays())


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
        rid = engine.submit(cond=r.cond, budget=r.budget, key=r.key)
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


def window_steps(cell, traffic: Dict, served: List[Served],
                 start: Dict[int, int], end: Dict[int, int], t0: float
                 ) -> Dict[str, float]:
    """Request-steps dispatched between two snapshots, the first taken
    at ``t0``, and their needed work by each request's own schedule
    (``cell.work``): model operations, the attention kernel's operations
    and bytes, and the blocks' dense matmul operations."""
    cfg, work = cell.config, cell.work
    T = traffic["sampler"]["T"]
    guided = traffic["sampler"]["guidance_scale"] != 0.0
    n = 0
    fl = afl = aby = bfl = 0.0
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
        bfl += work.request_block_flops(cfg, modes, guided)
    return {"request_steps": n, "model_flops": fl, "attn_flops": afl,
            "attn_bytes": aby, "block_flops": bfl}
