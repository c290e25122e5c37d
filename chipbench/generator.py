"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``traffic/``; this module turns it and a seed into requests.

Every seed gets the same work and the same order: the arrival process
(``arrivals/<arrival>.py``, found by name) gives the due times, budget
tiers come in exact shares by largest remainder, and both are ordered
by the mix's ``order_seed``, so every seed gets one schedule. Each
request's condition (drawn by the configuration's program module, see
:mod:`chipbench.spec`) and its PRNG key come from the seed; they change
values, not work.

Parameters of a mix:

``arrival``          the arrival process, and its own parameters
                     (``rate_per_s``, ``backlog_requests``, ...)
``order_seed``       orders the arrivals and the tiers
``tiers``            ``[[budget, weight], ...]``
``sampler``          ``{"T", "solver", "guidance_scale"}``
``policy``           the engine's admission policy
``drain_limit_s``    how long a window's requests may take past its end
``warmup``           ``{"pass_s", "max_passes", "quiet_passes"}``: passes
                     of ``pass_s`` seconds of the mix, until
                     ``quiet_passes`` in a row compile nothing
``sample_per_tier``  requests per tier compared with the reference
"""
from __future__ import annotations

import dataclasses
import importlib
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Sequence

import numpy as np

ARRIVALS = Path(__file__).resolve().parent / "arrivals"

# stream ids keep the window, the warm-up passes, the load after the
# window and the correctness sample independent draws of one seed
STREAM_WINDOW, STREAM_TAIL, STREAM_SAMPLE, STREAM_WARMUP = 1, 2, 3, 16


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    due_s: float          # offset from the stream's start
    cond: object          # the request's condition, as the program takes it
    budget: float
    key: np.ndarray       # uint32[2], the request's PRNG key


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def tier_counts(tiers, n: int) -> List[int]:
    """Exact per-tier counts summing to n (largest remainder)."""
    w = np.asarray([float(t[1]) for t in tiers])
    raw = n * w / w.sum()
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


def arrival(traffic: Dict) -> ModuleType:
    """The mix's arrival process, ``arrivals/<arrival>.py``."""
    name = traffic["arrival"]
    known = sorted(p.stem for p in ARRIVALS.glob("*.py")
                   if p.stem != "__init__")
    if name not in known:
        raise ValueError(f"unknown arrival {name!r}; known: {known}")
    return importlib.import_module(f"chipbench.arrivals.{name}")


def generate(traffic: Dict, draw_conds: Callable[[np.random.Generator, int],
                                                 Sequence],
             seed: int, stream: int, seconds: float) -> List[Req]:
    """Requests of one stream of ``seconds``, in arrival order;
    ``draw_conds(rng, n)`` gives their conditions."""
    process = arrival(traffic)
    rng = rng_for(seed, stream)
    order = rng_for(traffic["order_seed"], stream)
    n = process.count(traffic, seconds)
    budgets = np.concatenate([np.full(c, float(t[0])) for t, c in
                              zip(traffic["tiers"],
                                  tier_counts(traffic["tiers"], n))])
    order.shuffle(budgets)
    due = process.offsets(traffic, n, order)
    conds = draw_conds(rng, n)
    keys = rng.integers(0, 2 ** 32, size=(n, 2), dtype=np.uint32)
    return [Req(i, float(due[i]), conds[i], float(budgets[i]), keys[i])
            for i in range(n)]


def sample_for_check(traffic: Dict, finished: List[Req], seed: int
                     ) -> List[Req]:
    """The requests compared with the reference: ``sample_per_tier`` of
    each tier, drawn from the seed among the finished ones."""
    rng = rng_for(seed, STREAM_SAMPLE)
    out: List[Req] = []
    for budget, _w in traffic["tiers"]:
        pool = [r for r in finished if r.budget == float(budget)]
        k = min(int(traffic["sample_per_tier"]), len(pool))
        pick = rng.choice(len(pool), size=k, replace=False) if k else []
        out.extend(pool[i] for i in sorted(pick))
    return out
