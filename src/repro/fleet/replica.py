"""One fleet replica: a serving engine + its own clock + its price tag.

Two engine kinds sit behind the same pump/submit surface:

* ``packed`` — the continuous-batching :class:`ServingEngine` (the
  normal case; single-replica-equivalent bit-identical sampling);
* ``fixed`` — :class:`FixedSlotEngine`, a per-level fixed-slot batcher
  driving ``FlexiPipeline.sample`` directly. It exists because packed
  engines reject sequence-parallel plans (``plan.parallel`` needs a
  shard_map over the replica's device slice), so a ``--mesh DATAxSEQ
  --replicas N`` fleet runs one fixed-slot engine per seq-wide replica
  mesh.

**Virtual time.** A single-process fleet shares one accelerator, so
replica compute serializes and wall-clock can never show N-replica
throughput. Each replica therefore owns a :class:`ReplicaClock` that
the pump advances by the *modeled* dispatch cost — packed tokens x
calibrated seconds-per-token (x the replica's ``speed_factor``, the
straggler dial). Fleet makespan is the max replica clock; on a real
multi-host deployment every replica has its own chips, the virtual
clock is replaced by ``time.monotonic``, and the same arithmetic holds
with dt measured instead of modeled (``virtual=False``).

**Pricing.** Every replica carries its own
:class:`~repro.serving.controller.BudgetController` and feeds it
wall-per-analytic-FLOP calibration (PR 8's seconds-space pricing) from
its own observed/modeled seconds-per-token, so
``controller.cost_seconds(level)`` is the per-replica price the router
scores placements with — a slow replica literally costs more seconds.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import dit_nfe_flops
from repro.diffusion import schedule as sch
from repro.models import dit as dit_mod
from repro.pipeline.pipeline import FlexiPipeline
from repro.pipeline.plan import SamplingPlan
from repro.serving.controller import BudgetController
from repro.serving.metrics import RequestRecord, ServingMetrics
from repro.serving.queue import Request, RequestQueue
from repro.serving.scheduler import LevelPlan, ServedResult, ServingEngine

ENGINE_KINDS = ("packed", "fixed")

#: pre-measurement seconds-per-token guess (only prices the very first
#: placements in wall mode; the EWMA takes over after one dispatch)
DEFAULT_SECONDS_PER_TOKEN = 1e-4


def replica_pipelines(params: Any, cfg, sched: sch.DiffusionSchedule,
                      n: int) -> List[FlexiPipeline]:
    """One pipeline per one-chip replica: replica ``i``'s params are
    committed to ``jax.devices()[i]`` (round-robin when the process sees
    fewer devices than replicas), so its jitted steps run on that chip
    and all replicas are driven from this one process."""
    devs = jax.devices()
    return [FlexiPipeline(jax.device_put(params, devs[i % len(devs)]),
                          cfg, sched) for i in range(n)]


def pipe_device(pipe: FlexiPipeline) -> jax.Device:
    """The device a one-chip pipeline's params live on."""
    (dev,) = jax.tree.leaves(pipe.params)[0].devices()
    return dev


class ReplicaClock:
    """Per-replica monotonic virtual clock (callable like
    ``time.monotonic``); the pump advances it by modeled dispatch cost."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += float(dt)
        return self.t

    def catch_up(self, t: float) -> None:
        """A replica can't run work that hasn't arrived yet: placement
        at fleet time ``t`` pulls an idle replica's clock forward."""
        if t > self.t:
            self.t = float(t)


def _level_plans(cfg, sched, plans: Dict[float, SamplingPlan]
                 ) -> Dict[float, LevelPlan]:
    """Resolved per-level step ladders (the packed engine builds these
    itself; the fixed-slot engine and the replica price model need the
    same view)."""
    out: Dict[float, LevelPlan] = {}
    for b in sorted(plans):
        plan = plans[b]
        fs = plan.resolve_schedule(cfg)
        ts = sch.respaced_timesteps(sched.num_steps, plan.T)
        step_modes = np.concatenate(
            [np.full(n, m, np.int64) for m, n in fs.phases if n])
        run_len = np.ones(len(step_modes), np.int64)
        for i in range(len(step_modes) - 2, -1, -1):
            if step_modes[i] == step_modes[i + 1]:
                run_len[i] = run_len[i + 1] + 1
        out[b] = LevelPlan(level=b, plan=plan, ts=ts,
                           t_prev=np.concatenate([ts[1:], [-1]]),
                           modes=step_modes, run_len=run_len,
                           flops=plan.flops(cfg))
    return out


class FixedSlotEngine:
    """Legacy fixed-slot batcher with the packed engine's fleet surface
    (submit/step/extract_queued/stop_admissions/metrics).

    Each step serves one same-level batch of up to ``batch_size``
    requests through ``pipe.sample``. With the ``ddim`` solver the batch
    stacks each request's OWN prior draw (``x_T`` rows from the request
    key), so results match a standalone single-request ``sample`` —
    re-admission after a kill reproduces the reference. (``ddpm``
    ancestral noise is batch-keyed by ``sample``; per-request ddpm
    determinism under rebatching is what the packed engine is for.)
    """

    def __init__(self, pipe: FlexiPipeline,
                 plans: Dict[float, SamplingPlan], *,
                 batch_size: int = 4,
                 clock: Optional[Callable[[], float]] = None,
                 base_key: Optional[jax.Array] = None):
        self.pipe = pipe
        self.cfg = pipe.cfg
        self.clock = clock or time.monotonic
        self.batch_size = int(batch_size)
        ref = next(iter(plans.values()))
        self.guided = ref.guidance_active
        self.levels = _level_plans(self.cfg, pipe.sched, plans)
        self.metrics = ServingMetrics()
        self._queue = RequestQueue()
        self._admitting = True
        self._next_id = 0
        self._base_key = (base_key if base_key is not None
                          else jax.random.PRNGKey(0x5e41))

    # -- request lifecycle (packed-engine surface) ---------------------

    def quantize(self, budget: float) -> float:
        for b in sorted(self.levels):
            if b >= budget - 1e-9:
                return b
        return max(self.levels)

    def submit(self, cond: int, budget: float,
               deadline: float = math.inf,
               key: Optional[jax.Array] = None) -> int:
        rid = self._next_id
        self._next_id += 1
        if key is None:
            key = jax.random.fold_in(self._base_key, rid)
        req = Request(id=rid, cond=int(cond), budget=float(budget),
                      deadline=deadline, key=key)
        self._queue.submit(req, self.clock())
        return rid

    def stop_admissions(self) -> None:
        self._admitting = False

    def resume_admissions(self) -> None:
        self._admitting = True

    def extract_queued(self) -> List[Request]:
        out = sorted(self._queue._pending, key=lambda r: r._seq)
        self._queue._pending.clear()
        return out

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_inflight(self) -> int:
        return 0                      # a fixed-slot step runs to finish

    @property
    def idle(self) -> bool:
        return not self._queue

    def cache_stats(self) -> Dict[str, int]:
        return self.pipe.cache_stats()

    # -- the iteration -------------------------------------------------

    def step(self) -> List[ServedResult]:
        """Serve one fixed-slot batch: the level with the oldest pending
        request, filled to ``batch_size`` in arrival order."""
        now = self.clock()
        if not self._queue:
            return []
        pending = sorted(self._queue._pending, key=lambda r: r._seq)
        level = self.quantize(pending[0].budget)
        batch = [r for r in pending
                 if self.quantize(r.budget) == level][:self.batch_size]
        for r in batch:
            self._queue._pending.remove(r)
        lp = self.levels[level]
        n = len(batch)
        cond = jnp.asarray([r.cond for r in batch])
        if lp.plan.solver == "ddim":
            x_T = jnp.concatenate([
                jax.random.normal(r.key, (1,) + self.cfg.dit.latent_shape)
                for r in batch])
        else:
            x_T = None
        res = self.pipe.sample(lp.plan, n, batch[0].key, cond=cond,
                               x_T=x_T)
        jax.block_until_ready(res.x0)
        finish = self.clock()
        mult = 2 if self.guided else 1
        tokens_each = int(mult * sum(
            dit_mod.tokens_for_mode(self.cfg, int(m)) for m in lp.modes))
        self.metrics.record_step(finish, tokens_each * n, tokens_each * n,
                                 n)
        out: List[ServedResult] = []
        for i, r in enumerate(batch):
            rec = RequestRecord(
                id=r.id, arrival=r.arrival, admit=now, finish=finish,
                deadline=r.deadline, budget_requested=r.budget,
                budget_served=level, tokens=tokens_each, flops=lp.flops)
            self.metrics.record_request(rec)
            out.append(ServedResult(request=r, x0=res.x0[i],
                                    budget_served=level, record=rec))
        return out

    def run(self, max_steps: int = 100_000) -> List[ServedResult]:
        out: List[ServedResult] = []
        steps = 0
        while self._queue and steps < max_steps:
            out.extend(self.step())
            steps += 1
        return out


class Replica:
    """Engine + clock + price model, pumped by the fleet driver."""

    def __init__(self, rid: int, pipe: FlexiPipeline,
                 plans: Dict[float, SamplingPlan], *,
                 engine_kind: str = "packed",
                 virtual: bool = True,
                 seconds_per_token: float = DEFAULT_SECONDS_PER_TOKEN,
                 speed_factor: float = 1.0,
                 clock: Optional[Callable[[], float]] = None,
                 controller: Optional[BudgetController] = None,
                 base_key: Optional[jax.Array] = None,
                 batch_size: int = 4,
                 faults: Optional[Any] = None,
                 engine_kwargs: Optional[Dict[str, Any]] = None):
        if engine_kind not in ENGINE_KINDS:
            raise ValueError(f"unknown engine kind {engine_kind!r}; "
                             f"known: {ENGINE_KINDS}")
        self.rid = rid
        self.virtual = virtual
        self.speed_factor = float(speed_factor)
        # per-replica fault facade (resilience/faults.ReplicaFaults);
        # None on every production path — the seams below are no-ops then
        self.faults = faults
        kw = dict(engine_kwargs or {})
        cache = kw.get("cache")
        if virtual:
            t0 = clock() if clock is not None else 0.0
            self.rclock: Callable[[], float] = ReplicaClock(t0)
        else:
            self.rclock = clock or time.monotonic
        self.controller = controller if controller is not None else \
            BudgetController(
                pipe.cfg, plans, cache=cache,
                num_train_steps=pipe.sched.num_steps,
                attn_backend=next(iter(plans.values())).attn_backend)
        if engine_kind == "packed":
            self.engine: Any = ServingEngine(
                pipe, plans, clock=self.rclock,
                controller=self.controller, base_key=base_key, **kw)
            self._levels = self.engine.levels
            guided = self.engine.guided
        else:
            self.engine = FixedSlotEngine(pipe, plans,
                                          batch_size=batch_size,
                                          clock=self.rclock,
                                          base_key=base_key)
            self._levels = self.engine.levels
            guided = self.engine.guided
        cfg = pipe.cfg
        mult = 2 if guided else 1
        self._level_tokens = {
            b: int(mult * sum(dit_mod.tokens_for_mode(cfg, int(m))
                              for m in lp.modes))
            for b, lp in self._levels.items()}
        # wall-per-FLOP feeds: per patch mode, FLOPs carried by one of
        # its (guidance-multiplied) segment tokens — the bridge from the
        # seconds-per-token cost model into the controller's
        # seconds-space pricing
        backend = next(iter(plans.values())).attn_backend
        modes = sorted({int(m) for lp in self._levels.values()
                        for m in lp.modes})
        self._flops_per_token = {
            m: dit_nfe_flops(cfg, m, attn_backend=backend)
            / dit_mod.tokens_for_mode(cfg, m) for m in modes}
        self._spt = float(seconds_per_token)
        self._measured = virtual     # virtual spt is authoritative now
        if virtual:
            self._calibrate()

    # ------------------------------------------------------------------
    # Pricing

    def _calibrate(self) -> None:
        spt = self._spt * (self.speed_factor if self.virtual else 1.0)
        for m, fpt in self._flops_per_token.items():
            self.controller.observe_calibration(m, fpt, spt)

    @property
    def seconds_per_token(self) -> float:
        return self._spt * (self.speed_factor if self.virtual else 1.0)

    def price_seconds(self, level: float) -> float:
        """Calibrated seconds one request at ``level`` costs here."""
        c = self.controller.cost_seconds(level)
        if c is not None:
            return float(c)
        return self._level_tokens[level] * self.seconds_per_token

    def prices(self) -> Dict[float, float]:
        return {b: self.price_seconds(b) for b in self._levels}

    def backlog_seconds(self) -> float:
        """Priced not-yet-done work: queued requests at full price,
        in-flight ones at their remaining-step fraction."""
        total = 0.0
        for r in self.engine._queue._pending:
            total += self.price_seconds(self.engine.quantize(r.budget))
        for f in getattr(self.engine, "_inflight", ()):
            frac = 1.0 - f.step / max(len(f.lp.ts), 1)
            total += self.price_seconds(f.lp.level) * frac
        return total

    # ------------------------------------------------------------------
    # Fleet surface

    def submit(self, cond: int, budget: float, deadline: float,
               key: jax.Array) -> int:
        return self.engine.submit(cond, budget, deadline=deadline, key=key)

    @property
    def has_work(self) -> bool:
        return not self.engine.idle

    def pump(self, now: float) -> Tuple[List[ServedResult], float]:
        """One engine iteration at fleet time ``now``; returns the
        finished results and the dispatch's (modeled or measured)
        seconds. The replica clock never runs behind fleet time."""
        if self.virtual:
            self.rclock.catch_up(now)
        t0 = self.rclock()
        n0 = self.engine.metrics.total_steps
        results = self.engine.step()
        dt = 0.0
        if self.engine.metrics.total_steps > n0:
            srec = self.engine.metrics.steps[-1]
            if self.virtual:
                dt = (srec.packed_tokens * self._spt * self.speed_factor)
                # fault seam: a scripted slowdown window stretches the
                # modeled dispatch cost (the straggler detector and the
                # router's backlog pricing both see it)
                if self.faults is not None:
                    dt *= self.faults.slowdown_factor(t0)
                self.rclock.advance(dt)
            else:
                dt = self.rclock() - t0
                if srec.packed_tokens > 0 and dt > 0:
                    m = dt / srec.packed_tokens
                    self._spt = (m if not self._measured
                                 else 0.7 * self._spt + 0.3 * m)
                    self._measured = True
                    self._calibrate()
        return results, dt

    def estimated_finish(self, engine_id: int, now: float
                         ) -> Optional[float]:
        """Predicted completion time of an in-flight/queued request on
        this replica: remaining tokens x seconds-per-token, behind the
        current backlog. None when unknown here."""
        eng = self.engine
        spt = self.seconds_per_token
        for f in getattr(eng, "_inflight", ()):
            if f.req.id == engine_id:
                mult = 2 if eng.guided else 1
                rem = mult * sum(
                    dit_mod.tokens_for_mode(eng.cfg, int(m))
                    for m in f.lp.modes[f.step:])
                return max(now, self.rclock()) + rem * spt
        for r in eng._queue._pending:
            if r.id == engine_id:
                level = eng.quantize(r.budget)
                return (max(now, self.rclock()) + self.backlog_seconds()
                        + self._level_tokens[level] * spt)
        return None

    def compile_stats(self) -> Dict[str, int]:
        return self.engine.cache_stats()
