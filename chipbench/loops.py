"""How a cell's requests reach the engine. Each arrival process
(``arrivals/<name>.py``) names one of these classes as its ``Loop``; a
run builds it over the warmed engine, and it serves the warm-up passes,
the measured window, and says which requests the window answers for.

A loop has ``warm_pass(p)``, ``window(t0, seconds, traffic)`` (the
requests served, as :class:`chipbench.drive.Served`) and
``outcome(served, t0, start, end)``: the window's population and how
many of it failed. ``latency`` says whether the population's latency,
from the due time, is the loop's to report.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from chipbench import drive, generator
from chipbench.drive import Served, clock


def serve_open(engine, reqs: List[generator.Req], t0: float,
               drain_limit: float,
               tail: Optional[List[generator.Req]] = None,
               tail_start: float = 0.0) -> List[Served]:
    """Open loop: each request is submitted once it is due (``t0`` +
    its offset), whatever the engine is doing; serving stops once every
    one of ``reqs`` is done, or ``drain_limit`` seconds after the last
    is due. ``tail`` requests (offsets from ``tail_start``) keep the load
    on meanwhile; they come back with ``counted`` False."""
    sched = [(t0 + r.due_s, r, True) for r in reqs]
    if tail:
        sched += [(tail_start + r.due_s, r, False) for r in tail]
    sched.sort(key=lambda e: e[0])
    deadline = (t0 + reqs[-1].due_s if reqs else t0) + drain_limit
    by_rid: Dict[int, Served] = {}
    n_counted = 0
    i = 0
    while True:
        now = clock()
        while i < len(sched) and sched[i][0] <= now:
            due, r, mine = sched[i]
            s = drive.submit(engine, r, due, mine)
            by_rid[s.rid] = s
            n_counted += mine
            i += 1
        if (n_counted == len(reqs)
                and all(s.finish is not None for s in by_rid.values()
                        if s.counted)) or now >= deadline:
            return list(by_rid.values())
        if engine.idle:
            nxt = sched[i][0] if i < len(sched) else deadline
            with drive.span("wait_arrival"):
                time.sleep(max(0.0, min(nxt, deadline) - now))
            continue
        drive.step(engine, by_rid)


def serve_backlog(engine, by_rid: Dict[int, Served], until: float) -> None:
    """Step the engine over its queue until ``until``."""
    while clock() < until:
        if engine.idle:
            raise RuntimeError("the backlog ran dry; the mix needs more "
                               "backlog_requests")
        drive.step(engine, by_rid)


class OpenLoop:
    """Each request is submitted when it is due, whatever the engine is
    doing. A warm-up pass serves ``pass_s`` seconds of the mix from a
    stream of its own. The window's population is the requests due in
    it; the load goes on past its end from another stream until they
    are done, and one unfinished at the drain limit has failed."""
    latency = True

    def __init__(self, engine, cell, seed: int, seconds: float):
        self.engine, self.cell, self.seed = engine, cell, seed

    def _generate(self, traffic: Dict, stream: int, seconds: float):
        return generator.generate(traffic, self.cell.draw_conds,
                                  self.seed, stream, seconds)

    def warm_pass(self, p: int) -> None:
        t = self.cell.traffic
        reqs = self._generate(t, generator.STREAM_WARMUP + p,
                              t["warmup"]["pass_s"])
        serve_open(self.engine, reqs, clock(), t["drain_limit_s"])

    def window(self, t0: float, seconds: float, traffic: Dict
               ) -> List[Served]:
        reqs = self._generate(traffic, generator.STREAM_WINDOW, seconds)
        tail = self._generate(traffic, generator.STREAM_TAIL,
                              traffic["drain_limit_s"])
        return serve_open(self.engine, reqs, t0, traffic["drain_limit_s"],
                          tail, t0 + seconds)

    def outcome(self, served, t0, start, end):
        population = [s for s in served if s.counted]
        return population, sum(s.finish is None for s in population)


class Backlog:
    """Every request of the window's stream is queued before warm-up, so
    the queue never empties. A warm-up pass serves it for ``pass_s``
    seconds; the window serves it for its length. The population is the
    requests that had work in the window; one still in flight at its end
    has not failed."""
    latency = False

    def __init__(self, engine, cell, seed: int, seconds: float):
        self.engine, self.cell = engine, cell
        self.by_rid: Dict[int, Served] = {}
        for r in generator.generate(cell.traffic, cell.draw_conds, seed,
                                    generator.STREAM_WINDOW, seconds):
            s = drive.submit(engine, r, clock())
            self.by_rid[s.rid] = s

    def warm_pass(self, p: int) -> None:
        serve_backlog(self.engine, self.by_rid,
                      clock() + self.cell.traffic["warmup"]["pass_s"])

    def window(self, t0: float, seconds: float, traffic: Dict
               ) -> List[Served]:
        serve_backlog(self.engine, self.by_rid, t0 + seconds)
        return list(self.by_rid.values())

    def outcome(self, served, t0, start, end):
        population = [s for s in served if (s.finish is not None
                                            and s.finish >= t0)
                      or s.rid in start or s.rid in end]
        return population, 0
