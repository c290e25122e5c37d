"""Iteration-level continuous-batching engine (DESIGN.md §serving).

The engine keeps many in-flight requests at *different* denoise steps
and budgets and advances a packed subset of them every iteration:

* **join/leave mid-flight** — new requests enter between any two engine
  steps; finished latents leave without draining anyone else;
* **token packing** — each step's batch is composed token-wise from the
  bucket menu (``serving.batcher``): weak-phase requests contribute
  ``H*W/ratio^2`` tokens, full-mode requests the full grid, packed into
  fixed-capacity rows with segment-id masking (``core.packing``);
* **compile-once** — all executables come from
  ``FlexiPipeline.packed_step``'s runner cache, keyed by the static
  layout only, so steady-state serving never recompiles
  (``cache_stats()`` proves it);
* **SLA awareness** — with ``policy='edf'`` admission and step priority
  follow deadlines; with ``policy='degrade'`` the
  :class:`~repro.serving.controller.BudgetController` demotes queued
  requests to the highest budget level the current arrival rate
  sustains.

Requests are served bit-identically to a standalone
``FlexiPipeline.sample(plan, 1, request.key)`` call: same prior draw,
same per-phase solver-key derivation, same guidance combine.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import ledger as cache_ledger
from repro.cache import policy as cache_policy
from repro.cache.policy import CacheSpec
from repro.cache.store import CacheStore, TransientAllocationError
from repro.core.scheduler import dit_nfe_flops
from repro.diffusion import flow
from repro.diffusion import schedule as sch
from repro.models import dit as dit_mod
from repro.pipeline.packed import PACKED_SOLVERS, PackLayout
from repro.pipeline.pipeline import FlexiPipeline
from repro.pipeline.plan import FLOW_SOLVERS, SamplingPlan
from repro.serving.batcher import BucketMenu
from repro.serving.controller import BudgetController
from repro.serving.metrics import RequestRecord, ServingMetrics
from repro.serving.queue import Request, RequestQueue
from repro.telemetry import TapSample, Telemetry
from repro.telemetry.profile import packed_key as profile_packed_key
from repro.telemetry.trace import REQUEST_PID, span

ENGINE_POLICIES = ("fifo", "edf", "degrade")


@dataclasses.dataclass(frozen=True)
class LevelPlan:
    """One budget level of the menu, fully resolved for step-wise play."""
    level: float
    plan: SamplingPlan
    ts: np.ndarray               # descending timestep (flow: σ) ladder [T]
    t_prev: np.ndarray           # ts shifted, -1 (flow: σ = 0) terminated
    modes: np.ndarray            # per-step patch mode [T]
    run_len: np.ndarray          # same-mode steps remaining (incl. self) [T]
    flops: float                 # analytic per-request denoising FLOPs


@dataclasses.dataclass
class InFlight:
    req: Request
    lp: LevelPlan
    x_src: jax.Array             # [k, F, H, W, C] batch holding the latent
    x_row: int                   # ... at this row (kept unsliced so step
    #                              assembly can reuse whole output batches)
    keys: np.ndarray             # [T, 2] per-step solver keys (host-side)
    admit: float
    seq: int
    # joint conditioning: the request's (context, pooled) text encoder
    # outputs on the device, uploaded once at admission
    text: Optional[Tuple[jax.Array, jax.Array]] = None
    step: int = 0
    # cross-step activation cache (DESIGN.md §cache): this request's OWN
    # staleness clock over its ladder, plus its slot in the engine's
    # CacheStore (slot follows the request across bucket migrations;
    # forced refreshes — join, phase switch, eviction — flip the mask
    # in place so the retire-time histogram reflects reality)
    refresh_mask: Optional[np.ndarray] = None
    cache_slot: int = -1
    cache_mode: int = -1

    @property
    def x(self) -> jax.Array:
        return self.x_src[self.x_row]

    @property
    def mode(self) -> int:
        return int(self.lp.modes[self.step])

    @property
    def done(self) -> bool:
        return self.step >= len(self.lp.ts)


@dataclasses.dataclass
class ServedResult:
    request: Request
    x0: jax.Array
    budget_served: float
    record: RequestRecord
    # measured per-request served cost (telemetry.attribution.ServedCost)
    # when the engine runs with profiling telemetry; None otherwise
    cost: Optional[Any] = None


class ServingEngine:
    """Continuous-batching DiT serving on top of a FlexiPipeline.

    >>> engine = ServingEngine(pipe, plans, max_tokens_per_step=1024)
    >>> engine.submit(cond=3, budget=0.6)
    >>> results = engine.run()          # drain queue + in-flight
    """

    def __init__(self, pipe: FlexiPipeline,
                 plans: Dict[float, SamplingPlan], *,
                 max_tokens_per_step: Optional[int] = None,
                 policy: str = "fifo",
                 clock: Optional[Callable[[], float]] = None,
                 controller: Optional[BudgetController] = None,
                 max_inflight: Optional[int] = None,
                 base_key: Optional[jax.Array] = None,
                 steps_per_dispatch: int = 8,
                 menu: Optional[BucketMenu] = None,
                 allow_cold: bool = True,
                 cache: Optional[CacheSpec] = None,
                 precapture_small: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 faults: Optional[Any] = None,
                 quarantine: Optional[bool] = None,
                 self_heal: bool = True,
                 max_retries: int = 2,
                 expire_queued: bool = False,
                 cache_integrity: bool = False):
        if policy not in ENGINE_POLICIES:
            raise ValueError(f"unknown policy {policy!r}; known: "
                             f"{ENGINE_POLICIES}")
        # resilience (DESIGN.md §resilience): ``faults`` is a per-replica
        # fault-injection facade (resilience.faults.ReplicaFaults); every
        # consultation of it is guarded by ``is not None`` so a disarmed
        # engine runs the exact pre-resilience device-op sequence
        # (lint-enforced: resilience-armed-guard). Quarantine — drop
        # non-finite latents and re-enqueue the request at the most
        # powerful menu level — defaults to armed-only; ``self_heal``
        # re-enqueues locally, the fleet turns it off and escalates
        # through the router instead.
        self._faults = faults
        self._quarantine = (faults is not None) if quarantine is None \
            else quarantine
        self._self_heal = self_heal
        self._max_retries = max_retries
        self._retries: Dict[int, int] = {}
        self.quarantined: List[Request] = []
        self.expired: List[Request] = []
        self._expire_queued = expire_queued
        self.pipe = pipe
        self.cfg = pipe.cfg
        # the one device the params live on: every dispatch input is
        # committed there, so warm-up and served dispatches share one
        # signature per runner and host uploads go straight to that chip
        devs = {d for a in jax.tree.leaves(pipe.params)
                if isinstance(a, jax.Array) for d in a.devices()}
        self._device = devs.pop() if len(devs) == 1 else None
        self.clock = clock or time.monotonic
        # telemetry (DESIGN.md §telemetry): spans stamp the engine's own
        # clock; taps route every dispatch through the tapped step family
        # (bit-identical latents, extra data outputs — never structure)
        self.telemetry = telemetry
        self._taps = telemetry is not None and telemetry.taps_enabled
        self._rec = telemetry.recorder if telemetry is not None else None
        # profiling (DESIGN.md §profiling): compiled-cost registry +
        # per-request attribution + SLO watchdog. Profiling only adds a
        # per-dispatch block_until_ready for honest wall measurement —
        # same runners, same keys, same latents bit-for-bit
        self._profile = telemetry.profile if telemetry is not None else None
        self._attr = telemetry.attribution if telemetry is not None else None
        self._watchdog = telemetry.watchdog if telemetry is not None else None
        self._wd_ticks = 0
        if self._profile is not None:
            pipe.enable_cost_profiling()
        if telemetry is not None:
            telemetry.bind_clock(self.clock)
        self.policy = policy
        self._validate_menu(plans)
        ref = next(iter(plans.values()))
        self.solver = ref.solver
        self.guidance_scale = ref.guidance_scale
        self.clip_x0 = ref.clip_x0
        self.guided = ref.guidance_active
        self._flow = self.solver in FLOW_SOLVERS
        self._text = self.cfg.dit.conditioning == "joint"
        # flow metas carry σ as float32; the final step's next σ is 0
        self._meta_dtype = np.float32 if self._flow else np.int32
        self._final_prev = 0 if self._flow else -1
        self._zero_texts: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        # one engine = one compiled step family = one attention backend
        # (DESIGN.md §attention-backend); 'auto' resolves to the segment-
        # aware Pallas kernel inside packed steps, so FLOPs accounting
        # below prices block-granular attention with cross-segment skips
        self.attn_backend = ref.attn_backend
        self.levels: Dict[float, LevelPlan] = {}
        modes = {0}
        for b in sorted(plans):
            plan = plans[b]
            fs = plan.resolve_schedule(self.cfg)
            ts = (pipe.sched.sigmas(plan.T) if self._flow else
                  sch.respaced_timesteps(pipe.sched.num_steps, plan.T))
            step_modes = np.concatenate(
                [np.full(n, m, np.int64) for m, n in fs.phases if n])
            run_len = np.ones(len(step_modes), np.int64)
            for i in range(len(step_modes) - 2, -1, -1):
                if step_modes[i] == step_modes[i + 1]:
                    run_len[i] = run_len[i + 1] + 1
            self.levels[b] = LevelPlan(
                level=b, plan=plan, ts=ts,
                t_prev=np.concatenate([ts[1:], [self._final_prev]]),
                modes=step_modes, run_len=run_len,
                flops=plan.flops(self.cfg))
            modes.update(int(m) for m in step_modes)
        mult = 2 if self.guided else 1
        self._seg_tokens = {m: dit_mod.segment_tokens(self.cfg, m)
                            for m in sorted(modes)}
        if max_tokens_per_step is None:
            max_tokens_per_step = 4 * mult * self._seg_tokens[0]
        if steps_per_dispatch < 1:
            raise ValueError(f"steps_per_dispatch must be >= 1, got "
                             f"{steps_per_dispatch}")
        self.steps_per_dispatch = steps_per_dispatch
        self.allow_cold = allow_cold
        self.menu = menu if menu is not None else BucketMenu(
            self.cfg, sorted(modes), max_tokens_per_step, guided=self.guided)
        if menu is not None and menu.guided != self.guided:
            raise ValueError("shared menu's guided flag mismatches the plan "
                             "menu's guidance")
        for m in sorted(modes):
            if not self.menu.greedy_fit([m])[0]:
                raise ValueError(
                    f"max_tokens_per_step={self.menu.max_tokens} cannot fit "
                    f"one mode-{m} request's {mult} segment(s); such "
                    f"requests would starve")
        self.max_inflight = max_inflight or 2 * self.menu.max_requests
        self.cache = cache
        self.cache_split = (cache.resolve_split(self.cfg.num_layers)
                            if cache is not None else None)
        self.store: Optional[CacheStore] = None
        self._level_masks: Dict[float, np.ndarray] = {}
        if cache is not None:
            self.store = CacheStore(self.cfg, sorted(modes),
                                    n_slots=self.max_inflight,
                                    guided=self.guided,
                                    integrity=cache_integrity)
            for b, lp in self.levels.items():
                fs = lp.plan.resolve_schedule(self.cfg)
                self._level_masks[b] = cache_policy.ladder_refresh_mask(
                    cache, fs.split_timesteps(lp.ts))
        self.controller = controller
        if policy == "degrade" and controller is None:
            self.controller = BudgetController(
                self.cfg, plans, cache=cache,
                num_train_steps=pipe.sched.num_steps,
                attn_backend=self.attn_backend)
        self.metrics = ServingMetrics()
        self._layout_costs: Dict[Any, Any] = {}
        self._layout_blocks: Dict[Any, Any] = {}
        self._zero_blocks: Dict[int, jax.Array] = {}
        self._queue = RequestQueue()
        self._inflight: List[InFlight] = []
        self._admitting = True
        self._next_id = 0
        self._seq = 0
        self._base_key = (base_key if base_key is not None
                          else jax.random.PRNGKey(0x5e41))
        self._last_step_at: Optional[float] = None
        self._last_sync_at: Optional[float] = self.clock()
        self._flops_since_sync = 0.0
        self.started_at = self.clock()
        if precapture_small > 0:
            self.precapture_warm_set(max_per_mode=precapture_small)

    # ------------------------------------------------------------------
    # Validation / setup

    def _validate_menu(self, plans: Dict[float, SamplingPlan]) -> None:
        if not plans:
            raise ValueError("engine needs a non-empty plan menu")
        if self.cfg.dit is None \
                or self.cfg.dit.conditioning not in ("class", "joint"):
            raise ValueError("the serving engine serves class-conditioned "
                             "and joint-attention DiTs")
        if self.cfg.dit.lora_rank > 0:
            raise ValueError("mixed-mode packing needs mode-independent "
                             "blocks (shared-parameter recipe); per-mode "
                             "LoRA serving is a ROADMAP follow-on")
        ref = next(iter(plans.values()))
        for b, plan in plans.items():
            plan.validate(self.cfg)
            if plan.is_adaptive:
                raise ValueError("adaptive plans are per-sample host loops; "
                                 "the engine packs static schedules only")
            if plan.solver not in PACKED_SOLVERS:
                raise ValueError(f"engine solvers: {PACKED_SOLVERS}, got "
                                 f"{plan.solver!r} at level {b}")
            if (plan.solver in FLOW_SOLVERS) != isinstance(
                    self.pipe.sched, flow.FlowSchedule):
                raise ValueError(f"solver {plan.solver!r} at level {b} "
                                 f"does not run on the pipeline's "
                                 f"{type(self.pipe.sched).__name__}")
            if plan.parallel is not None:
                raise ValueError("sequence-parallel plans can't join the "
                                 "packed engine (single-host); route them "
                                 "through FlexiPipeline.sample")
            if plan.guidance_active and plan.guidance_kind != "uncond":
                raise ValueError("packed steps implement vanilla CFG; "
                                 "weak_cond guidance mixes modes inside "
                                 "one NFE pair")
            if (plan.solver, plan.guidance_scale, plan.clip_x0,
                    plan.attn_backend) != \
                    (ref.solver, ref.guidance_scale, ref.clip_x0,
                     ref.attn_backend):
                raise ValueError("all menu plans must share solver, "
                                 "guidance scale, clip_x0, and "
                                 "attn_backend (one engine = one "
                                 "compiled step family)")

    # ------------------------------------------------------------------
    # Request lifecycle

    def quantize(self, budget: float) -> float:
        """Requested budget → menu level: cheapest level >= requested
        (the served sample is at least as powerful as asked)."""
        for b in sorted(self.levels):
            if b >= budget - 1e-9:
                return b
        return max(self.levels)

    def submit(self, cond: Any, budget: float,
               deadline: float = math.inf,
               key: Optional[jax.Array] = None) -> int:
        """Enqueue one request; returns its id. ``cond``: a class label,
        or under joint conditioning the pair (context [text_len,
        text_dim], pooled [pooled_dim]) of the text encoders' outputs.
        ``key`` seeds the prior draw and solver noise (default: derived
        from the request id)."""
        rid = self._next_id
        self._next_id += 1
        if key is None:
            key = jax.random.fold_in(self._base_key, rid)
        now = self.clock()
        req = Request(id=rid, cond=cond if self._text else int(cond),
                      budget=float(budget),
                      deadline=deadline, key=key)
        self._queue.submit(req, now)
        if self.controller is not None:
            self.controller.observe_arrival(now)
        return rid

    def _solver_keys(self, key: jax.Array, lp: LevelPlan) -> np.ndarray:
        """Per-step solver keys, matching ``sample_phased``'s derivation
        (fold per non-empty phase, split over its timesteps) so DDPM
        ancestral noise is bit-identical to the pipeline's. Pulled to the
        host once at admission: step assembly then stacks them without a
        device round-trip per request per step."""
        run_key = jax.random.fold_in(key, 1)
        parts, i = [], 0
        fs = lp.plan.resolve_schedule(self.cfg)
        for _mode, tsub in fs.split_timesteps(lp.ts):
            if not len(tsub):
                continue
            parts.append(jax.random.split(jax.random.fold_in(run_key, i),
                                          len(tsub)))
            i += 1
        return np.asarray(jnp.concatenate(parts))

    def stop_admissions(self) -> None:
        """Drain mode (DESIGN.md §fleet): keep stepping the in-flight
        cohort to completion, but stop promoting queued requests. The
        queue itself still accepts ``submit`` — the fleet router is
        responsible for not placing onto a draining replica."""
        self._admitting = False

    def resume_admissions(self) -> None:
        self._admitting = True

    def extract_queued(self) -> List[Request]:
        """Remove and return every not-yet-admitted request (submission
        order). Queued requests hold no device or cache state, so a
        draining replica hands them back to the router loss-free; the
        in-flight cohort is NOT touched — it finishes here."""
        out = sorted(self._queue._pending, key=lambda r: r._seq)
        self._queue._pending.clear()
        return out

    def _admit(self, now: float) -> None:
        if self._expire_queued:
            # deadline-expiry path: a queued request whose deadline has
            # passed is a guaranteed SLA miss — reject it terminally
            # instead of burning a dispatch on it (opt-in: latency-SLA
            # deployments; off by default so best-effort queues still
            # serve late requests)
            for req in self._queue.take_expired(now):
                self.expired.append(req)
                self.metrics.total_expired += 1
                if self._rec is not None:
                    self._rec.instant("expired",
                                      args={"id": req.id,
                                            "deadline": req.deadline})
        if not self._admitting:
            return
        policy = "edf" if self.policy == "edf" else "fifo"
        while self._queue and len(self._inflight) < self.max_inflight:
            req = self._queue.pop(policy)
            level = self.quantize(req.budget)
            if self.controller is not None and self.policy == "degrade":
                level = self.controller.assign(level)
            lp = self.levels[level]
            # committed like every step output, so the step assembly's
            # concatenate sees one signature per shape, not one per mix
            # of committed and uncommitted parts
            x_T = self._put(jax.random.normal(
                req.key, (1,) + self.cfg.dit.latent_shape))
            mask = (self._level_masks[level].copy()
                    if self.cache is not None else None)
            text = None
            if self._text:
                with span(self._rec, "upload"):
                    text = tuple(self._put(np.asarray(a)[None])
                                 for a in req.cond)
            self._inflight.append(InFlight(
                req=req, lp=lp, x_src=x_T, x_row=0,
                keys=self._solver_keys(req.key, lp),
                admit=now, seq=self._seq, refresh_mask=mask, text=text))
            self._seq += 1

    def _priority(self, f: InFlight) -> Tuple:
        if self.policy == "edf":
            return (f.req.deadline, f.seq)
        return (f.seq,)

    def _is_warm(self, layout, k: int) -> bool:
        return self.pipe.packed_step_is_warm(
            layout, solver=self.solver,
            guidance_scale=self.guidance_scale, clip_x0=self.clip_x0,
            k_steps=k, cache_split=self.cache_split,
            attn_backend=self.attn_backend, taps=self._taps)

    def _ensure_slot(self, f: InFlight, mode: int) -> bool:
        """Make sure ``f`` owns a live slot in ``mode``'s pool; returns
        True when the request must refresh on this dispatch's first step:
        the slot is fresh (joined / phase-switched / evicted), or the
        allocation failed transiently and the request runs slotless
        (``cache_slot == -1``: deep blocks recomputed exactly, no cache
        reads or writes, re-allocation retried next dispatch)."""
        if f.cache_slot >= 0 and f.cache_mode == mode \
                and self.store.owner_of(mode, f.cache_slot) == f.req.id:
            return False
        if f.cache_slot >= 0 \
                and self.store.owner_of(f.cache_mode,
                                        f.cache_slot) == f.req.id:
            self.store.release(f.cache_mode, f.cache_slot)
        try:
            if self._faults is not None and self._faults.take_alloc_failure():
                raise TransientAllocationError("injected alloc failure")
            f.cache_slot = self.store.alloc(mode, f.req.id)
        except TransientAllocationError:
            f.cache_slot = -1
            self.metrics.total_alloc_failures += 1
        f.cache_mode = mode
        return True

    def _gather_latents(self, sel: List[InFlight], pad: int) -> jax.Array:
        """[cap, F, H, W, C] group input with as few device ops as
        possible: runs of requests holding consecutive rows of the same
        source batch (the common steady state — last step's output array)
        are reused whole; stragglers coalesce into one gather per source;
        dummy tail slots come from a cached zeros block."""
        parts: List[jax.Array] = []
        i = 0
        while i < len(sel):
            src = sel[i].x_src
            idx = [sel[i].x_row]
            i += 1
            while i < len(sel) and sel[i].x_src is src:
                idx.append(sel[i].x_row)
                i += 1
            if idx == list(range(src.shape[0])):
                parts.append(src)                    # whole batch, no op
            else:
                parts.append(src[np.asarray(idx)])   # one gather
        if pad:
            z = self._zero_blocks.get(pad)
            if z is None:
                z = self._zero_blocks[pad] = self._put(jnp.zeros(
                    (pad,) + self.cfg.dit.latent_shape))
            parts.append(z)
        return self._put(parts[0] if len(parts) == 1
                         else jnp.concatenate(parts))

    def _gather_text(self, sel: List[InFlight], pad: int
                     ) -> Tuple[jax.Array, jax.Array]:
        """A group's (context, pooled) text conditions, ``[cap, ...]``:
        the requests' own, uploaded at admission, then cached zeros for
        the dummy tail slots."""
        if pad and pad not in self._zero_texts:
            null = self.pipe.params["null_text"]
            self._zero_texts[pad] = tuple(
                self._put(np.zeros((pad,) + a.shape, a.dtype))
                for a in (null["context"], null["pooled"]))
        parts = [f.text for f in sel] + ([self._zero_texts[pad]]
                                         if pad else [])
        return tuple(self._put(jnp.concatenate([p[i] for p in parts]))
                     for i in range(2))

    def _put(self, a) -> jax.Array:
        """A dispatch input on the engine's device."""
        if self._device is None:
            return jnp.asarray(a)
        return jax.device_put(a, self._device)

    # ------------------------------------------------------------------
    # Warm-set shaping

    def precapture_warm_set(self, max_per_mode: int = 2,
                            k_depths: Optional[Sequence[int]] = None) -> int:
        """Compile (and execute once, with dummy inputs) the SMALL-cohort
        bucket ladder: every menu layout with per-mode counts <=
        ``max_per_mode``, at each micro-step depth in ``k_depths``
        (default: powers of two up to ``steps_per_dispatch``).

        Mid-trace cohorts — a Poisson straggler joining a part-drained
        pack — otherwise fall back to whatever coarse layout happens to
        be warm (bench: packing_eff ~0.6 vs 0.99 at drain). Capturing
        the fine small layouts at startup keeps the frozen planner's
        warm set shaped for them; returns how many executables were
        actually cold (newly compiled)."""
        n_cold = 0
        for layout, k in self.warm_set_ladder(max_per_mode, k_depths):
            n_cold += 1
            self._dummy_dispatch(layout, k)
        return n_cold

    def warm_set_ladder(self, max_per_mode: int = 2,
                        k_depths: Optional[Sequence[int]] = None
                        ) -> List[Tuple[PackLayout, int]]:
        """The still-COLD rungs of the small-cohort bucket ladder, in
        capture order — ``precapture_warm_set``'s work list, exposed so
        a background compile thread (``fleet.warmup``) can walk it one
        ``_dummy_dispatch`` at a time while the engine serves. Already-
        warm rungs are skipped, so the list shrinks to empty as the
        ladder is captured (by either party)."""
        if k_depths is None:
            k_depths, kd = [], 1
            while kd <= self.steps_per_dispatch:
                k_depths.append(kd)
                kd *= 2
        out: List[Tuple[PackLayout, int]] = []
        for layout in self.menu.layouts:
            if any(c > max_per_mode for _m, c in layout.groups):
                continue
            for k in k_depths:
                if not self._is_warm(layout, k):
                    out.append((layout, k))
        return out

    def _dummy_dispatch(self, layout: PackLayout, k: int,
                        record: bool = True) -> None:
        """Run one throwaway dispatch at ``layout`` so the executable is
        compiled AND loaded (a runner that merely exists in the cache
        still stalls its first real step on compilation).

        ``record=False`` keeps the span out of the ring (the background
        compile thread must not interleave writes into the serving
        thread's SpanRecorder ring or stamp a foreign clock); the
        profiler still sees it, on that thread."""
        with span(self._rec if record else None, "compile") as sp:
            if sp.on:
                sp.set(groups=str(layout.groups), k=k, precapture=True)
            runner = self.pipe.packed_step(
                layout, solver=self.solver,
                guidance_scale=self.guidance_scale, clip_x0=self.clip_x0,
                k_steps=k, cache_split=self.cache_split,
                attn_backend=self.attn_backend, taps=self._taps)
            xs, metas, keys, deltas, refreshes = [], [], [], [], []
            texts = []
            for mode, cap in layout.groups:
                xs.append(self._put(np.zeros(
                    (cap,) + self.cfg.dit.latent_shape, np.float32)))
                meta = np.zeros((k, 3, cap), self._meta_dtype)
                meta[:, 1, :] = self._final_prev
                metas.append(self._put(meta))
                if self._text:
                    texts.append(self._gather_text([], cap))
                keys.append(self._put(np.zeros((k, cap, 2), np.uint32)))
                if self.cache is not None:
                    deltas.append(self._put(jnp.zeros(
                        (cap, self.store.mult, self._seg_tokens[mode],
                         self.cfg.d_model), self.store.dtype)))
                    refreshes.append(self._put(np.zeros((k, cap), bool)))
            if self.cache is not None:
                out = runner(self.pipe.params, tuple(xs), tuple(metas),
                             tuple(keys), tuple(deltas), tuple(refreshes))
            else:
                out = runner(self.pipe.params, tuple(xs), tuple(metas),
                             tuple(keys), *self._text_args(texts))
            jax.block_until_ready(out)

    # ------------------------------------------------------------------
    # The engine iteration

    def step(self) -> List[ServedResult]:
        """One engine iteration: admit arrivals, plan (cohort, bucket,
        micro-step depth k), advance the packed cohort k denoise steps in
        one dispatch, and retire finished requests. Requests that don't
        fit the chosen bucket simply wait (no drain, no recompile).

        Each phase is a :class:`~repro.telemetry.trace.span` nested in
        ``engine.step``: ``admit``, ``plan``, ``pack``, ``compile`` (cold
        runner fetch only), ``dispatch``, ``materialize``, ``retire``."""
        with span(self._rec, "step"):
            return self._step()

    def _step(self) -> List[ServedResult]:
        now = self.clock()
        with span(self._rec, "admit") as sp:
            n_before = len(self._inflight)
            self._admit(now)
            if sp.on:
                sp.set(admitted=len(self._inflight) - n_before,
                       queued=len(self._queue))
        if not self._inflight:
            self._last_step_at = now
            return []
        mult = 2 if self.guided else 1

        # co-optimize the cohort, the bucket, and the micro-step depth k:
        # one dispatch advances the cohort k consecutive same-mode denoise
        # steps under lax.scan (joins wait at most k steps), so the
        # planner maximizes request-steps per dispatch — k x cohort size —
        # over the power-of-two depths the highest-priority request can
        # sustain. Cold dispatches pack an EXACT-fit layout (greedy over
        # the priority order, no dummy slots); frozen serving
        # (``allow_cold=False``: every compile stall is an SLA violation)
        # restricts to already-compiled layouts, falling back to a cold
        # one only when nothing warm can serve at all.
        with span(self._rec, "plan") as sp:
            prio = sorted(self._inflight, key=self._priority)
            top = prio[0]
            k_cap = 1
            top_run = min(self.steps_per_dispatch,
                          int(top.lp.run_len[top.step]))
            while k_cap * 2 <= top_run:
                k_cap *= 2
            best = None
            for cold_pass in ((True,) if self.allow_cold else (False, True)):
                if not cold_pass:
                    # frozen pass: only buckets with room for the highest-
                    # priority request's mode — keeps EDF live (top always
                    # advances) and k_cap (derived from top) consistent
                    warm_layouts = {
                        kk: [l for l in ls if l.capacity_for(top.mode)]
                        for kk, ls in self.pipe.warm_packed_layouts(
                            solver=self.solver,
                            guidance_scale=self.guidance_scale,
                            clip_x0=self.clip_x0,
                            cache_split=self.cache_split,
                            attn_backend=self.attn_backend,
                            taps=self._taps).items()}
                kc = k_cap
                while kc >= 1:
                    eligible = [f for f in prio
                                if int(f.lp.run_len[f.step]) >= kc]
                    if not eligible:
                        kc //= 2
                        continue
                    if cold_pass:
                        idx, counts = self.menu.greedy_fit(
                            [f.mode for f in eligible])
                        if not idx:
                            kc //= 2
                            continue
                        cand = PackLayout.for_counts(
                            counts, guided=self.guided,
                            row_capacity=self.menu.row_capacity)
                        sel_by_mode: Dict[int, List[InFlight]] = {}
                        for i in idx:
                            sel_by_mode.setdefault(eligible[i].mode,
                                                   []).append(eligible[i])
                        served = len(idx)
                    else:
                        demand: Dict[int, int] = {}
                        for f in eligible:
                            demand[f.mode] = demand.get(f.mode, 0) + 1
                        cand = self.menu.choose(
                            demand, among=warm_layouts.get(kc, ()))
                        if cand is None:
                            kc //= 2
                            continue
                        sel_by_mode = None
                        served = self.menu.served_by(cand, demand)
                    score = (kc * served,
                             1 if self._is_warm(cand, kc) else 0,
                             -self.menu.packed_tokens(cand))
                    if best is None or score > best[0]:
                        best = (score, kc, cand, sel_by_mode)
                    kc //= 2
                if best is not None:
                    break                 # frozen pass found a warm bucket
            _, k, layout, sel_by_mode = best
            if sel_by_mode is None:       # warm bucket: fill its capacities
                eligible = [f for f in prio
                            if int(f.lp.run_len[f.step]) >= k]
                sel_by_mode = {}
                for f in eligible:
                    sel_by_mode.setdefault(f.mode, []).append(f)
            picked = [sel_by_mode.get(mode, [])[:cap]
                      for mode, cap in layout.groups]
            if sp.on:
                sp.set(k=k, groups=str(layout.groups),
                       inflight=len(self._inflight))

        with span(self._rec, "pack") as sp:
            xs, metas, keys, texts = [], [], [], []
            deltas, refreshes, slot_lists, rf_real = [], [], [], []
            real_tokens = 0
            n_refresh = n_cached_steps = 0
            for (mode, cap), sel in zip(layout.groups, picked):
                pad = cap - len(sel)
                xs.append(self._gather_latents(sel, pad))
                if self._text:
                    texts.append(self._gather_text(sel, pad))
                meta = np.zeros((k, 3, cap), self._meta_dtype)
                meta[:, 1, :] = self._final_prev  # dummy slots: final step
                kk = np.zeros((k, cap, 2), np.uint32)
                rf = np.zeros((k, cap), bool)    # dummies never refresh
                slots: List[int] = []
                for i, f in enumerate(sel):
                    s = f.step
                    meta[:, 0, i] = f.lp.ts[s:s + k]
                    meta[:, 1, i] = f.lp.t_prev[s:s + k]
                    if not self._text:
                        meta[:, 2, i] = f.req.cond
                    kk[:, i] = f.keys[s:s + k]
                    if self.cache is not None:
                        if self._ensure_slot(f, mode):
                            f.refresh_mask[s] = True  # fresh slot: no replay
                        elif self.store.integrity and \
                                not self.store.verify_slot(mode,
                                                           f.cache_slot):
                            # checksum mismatch: the resident delta was
                            # corrupted out of band — force an exact
                            # deep-block recompute; the scatter below
                            # re-records the crc
                            f.refresh_mask[s] = True
                            self.metrics.total_integrity_refreshes += 1
                        if f.cache_slot < 0:
                            # slotless (transient alloc failure): every
                            # micro-step refreshes, so the garbage gathered
                            # in its row is never read and nothing scatters
                            # back
                            f.refresh_mask[s:s + k] = True
                        rf[:, i] = f.refresh_mask[s:s + k]
                        slots.append(f.cache_slot)
                metas.append(self._put(meta))
                keys.append(self._put(kk))
                real_tokens += mult * self._seg_tokens[mode] * len(sel) * k
                if self.cache is not None:
                    refreshes.append(self._put(rf))
                    slot_lists.append(slots)
                    rf_real.append(rf[:, :len(sel)])
                    if slots and min(slots) < 0:
                        # slotless rows gather slot 0's delta; it is
                        # ignored (their refresh flags are all True)
                        gathered = self.store.gather(
                            mode, [max(sl, 0) for sl in slots])
                    else:
                        gathered = (self.store.gather(mode, slots)
                                    if slots else None)
                    if pad:
                        z = jnp.zeros((pad, self.store.mult,
                                       self._seg_tokens[mode],
                                       self.cfg.d_model), self.store.dtype)
                        gathered = (z if gathered is None
                                    else jnp.concatenate([gathered, z]))
                    deltas.append(self._put(gathered))

            step_flops = 0.0
            if self.cache is not None:
                # honest device-cost accounting: the packed executable's
                # lax.cond is DISPATCH-wide — the deep blocks run for the
                # whole pack whenever any cohort member refreshes a
                # micro-step, so only all-skip micro-steps realize the deep
                # saving. The per-request replay counts below feed the
                # quality/staleness ledger (hit rate, histogram); the FLOPs
                # fed to the capacity EWMA charge what the hardware ran.
                any_ref = np.zeros(k, bool)
                for rf in rf_real:
                    if rf.size:
                        any_ref |= rf.any(axis=1)
                deep_skips = k - int(any_ref.sum())
                for (mode, _cap), sel, rf in zip(layout.groups, picked,
                                                 rf_real):
                    n_refresh += int(rf.sum())
                    n_cached_steps += k * len(sel)
                    full = dit_nfe_flops(self.cfg, mode,
                                         attn_backend=self.attn_backend)
                    deep = cache_ledger.deep_block_flops(
                        self.cfg, mode, self.cache_split,
                        attn_backend=self.attn_backend)
                    step_flops += mult * len(sel) * (k * full
                                                     - deep_skips * deep)
            else:
                step_flops = k * sum(
                    mult * len(sel)
                    * dit_nfe_flops(self.cfg, mode,
                                    attn_backend=self.attn_backend)
                    for (mode, _cap), sel in zip(layout.groups, picked))
            if sp.on:
                sp.set(real_tokens=real_tokens)

        was_warm = self._is_warm(layout, k)
        # a cold dispatch: the runner fetch traced + lowered a new
        # executable — the stall every frozen-serving SLA fears
        with (contextlib.nullcontext() if was_warm else
              span(self._rec, "compile", groups=str(layout.groups), k=k)):
            runner = self.pipe.packed_step(
                layout, solver=self.solver,
                guidance_scale=self.guidance_scale, clip_x0=self.clip_x0,
                k_steps=k, cache_split=self.cache_split,
                attn_backend=self.attn_backend, taps=self._taps)
        with span(self._rec, "dispatch") as sp:
            if sp.on:
                # the request ids tie one request's dispatches together
                sp.set(k=k, groups=str(layout.groups),
                       requests=sum(len(s) for s in picked), warm=was_warm,
                       ids=" ".join(str(f.req.id)
                                    for sel in picked for f in sel))
            t_disp = self.clock() if self._profile is not None else 0.0
            tap = None
            if self.cache is not None:
                out = runner(self.pipe.params, tuple(xs),
                             tuple(metas), tuple(keys),
                             tuple(deltas), tuple(refreshes))
                (outs, new_deltas, tap) = out if self._taps else (*out, None)
                if self._faults is not None:
                    outs = self._apply_poison(outs, picked)
                for (mode, _cap), slots, nd in zip(layout.groups, slot_lists,
                                                   new_deltas):
                    if not slots:
                        continue
                    if min(slots) < 0:
                        # skip slotless rows: scattering them would clobber
                        # slot 0's owner
                        keep = [j for j, sl in enumerate(slots) if sl >= 0]
                        if keep:
                            self.store.scatter(
                                mode, [slots[j] for j in keep],
                                nd[np.asarray(keep, np.int32)])
                    else:
                        self.store.scatter(mode, slots, nd[:len(slots)])
                self.metrics.record_cache(n_refresh,
                                          n_cached_steps - n_refresh)
                self.metrics.set_cache_bytes(self.store.bytes_resident)
            else:
                out = runner(self.pipe.params, tuple(xs), tuple(metas),
                             tuple(keys), *self._text_args(texts))
                (outs, tap) = out if self._taps else (out, None)
                if self._faults is not None:
                    outs = self._apply_poison(outs, picked)
            if self._profile is not None:
                self._observe_profile(layout, k, picked, rf_real, step_flops,
                                      now, t_disp, outs)
        if tap is not None:
            # still device arrays — the aggregator syncs at export time
            self.telemetry.taps.add(TapSample(
                time=now, k=k, groups=layout.groups,
                n_real=tuple(len(s) for s in picked),
                eps_norm=tap["eps_norm"], drift=tap.get("drift"),
                attn_blocks=tap.get("attn_blocks"),
                finite=tap.get("finite")))
        self._flops_since_sync += step_flops
        synced = False
        if any(f.step + k >= len(f.lp.ts) for sel in picked for f in sel):
            synced = True
            # someone completes on this dispatch: a result only counts as
            # served once it is materialized, so the finish stamp (and any
            # latency derived from it) waits for the device. This is also
            # the only honest capacity sample — between syncs the clock
            # only sees host-side batch assembly, not device compute
            with span(self._rec, "materialize", k=k):
                jax.block_until_ready(outs)
            now = self.clock()
            if self.controller is not None and self._last_sync_at is not None \
                    and now > self._last_sync_at:
                self.controller.observe_service(self._flops_since_sync,
                                                now - self._last_sync_at)
            self._flops_since_sync = 0.0
            self._last_sync_at = now

        with span(self._rec, "retire") as sp:
            finished: List[ServedResult] = []
            stepped = 0
            # quarantine detection rides existing sync points only: the
            # in-graph finite tap is read on the host after the completion
            # branch's block_until_ready, and the retire-time check reads a
            # latent that same sync already materialized
            bad: set = set()
            if self._quarantine and synced and tap is not None:
                bad = self._scan_finite(tap, picked)
            for g, sel in enumerate(picked):
                for i, f in enumerate(sel):
                    f.x_src, f.x_row = outs[g], i
                    f.step += k
                    stepped += 1
                    if self._quarantine and (
                            f.req.id in bad
                            or (f.done
                                and not np.isfinite(np.asarray(f.x)).all())):
                        self._inflight.remove(f)
                        self._quarantine_request(f, now)
                    elif f.done:
                        self._inflight.remove(f)
                        finished.append(self._retire(f, now))
            cost = self._layout_costs.get(layout)
            if cost is None:
                cost = self._layout_costs[layout] = layout.cost(self.cfg)
            self.metrics.record_step(now, real_tokens,
                                     cost.packed_tokens * k, stepped)
            if self.attn_backend in ("auto", "pallas"):
                # cross-segment block skip ledger (DESIGN.md
                # §attention-backend): what fraction of the pack's score
                # tiles the segment-aware kernel never issued
                blk = self._layout_blocks.get(layout)
                if blk is None:
                    blk = self._layout_blocks[layout] = \
                        layout.attention_block_stats(self.cfg)
                self.metrics.record_attention_blocks(blk[0] * k, blk[1] * k)
            if self._rec is not None:
                self._rec.counter("engine", {"inflight": len(self._inflight),
                                             "queued": len(self._queue)})
            if sp.on:
                sp.set(retired=len(finished))
        if self._watchdog is not None:
            self._wd_ticks += 1
            drift = None
            if self._taps and (self._wd_ticks
                               % self._watchdog.config.taps_every == 0):
                # the one deliberate host sync: tap aggregation, at the
                # watchdog's configured cadence, never per dispatch
                sub = self.telemetry.taps.aggregate().get("drift")
                if sub:
                    drift = float(sub.get("max", 0.0))
            self._watchdog.observe_step(
                now=now, queued=len(self._queue),
                inflight=len(self._inflight),
                compiled=self.pipe.cache_stats()["compiled"],
                latencies=[r.latency for r in self.metrics.requests],
                drift_max=drift,
                nonfinite=self.metrics.total_quarantined)
            if self._watchdog.should_dump():
                self._watchdog.dump(
                    reason="alert", engine_snapshot=self.snapshot_state(),
                    attribution=self._attr, registry=self._profile)
        self._last_step_at = now
        return finished

    def _text_args(self, texts: List) -> Tuple:
        """The packed step's text argument, under joint conditioning."""
        return (tuple(texts),) if self._text else ()

    def _observe_profile(self, layout: PackLayout, k: int,
                         picked: List[List[InFlight]], rf_real: List,
                         step_flops: float, now: float, t_disp: float,
                         outs: Tuple) -> None:
        """Profiling waits on the device once per dispatch: wall is
        meaningless without it. Measurement overhead only — the
        executables and their outputs are untouched."""
        mult = 2 if self.guided else 1
        jax.block_until_ready(outs)
        wall_s = self.clock() - t_disp
        pkey = profile_packed_key(
            layout, solver=self.solver,
            guidance_scale=self.guidance_scale, clip_x0=self.clip_x0,
            k_steps=k, cache_split=self.cache_split,
            attn_backend=self.attn_backend, taps=self._taps)
        self._profile.observe_wall(pkey, wall_s)
        if self._attr is not None:
            rids: List[int] = []
            weights: List[float] = []
            for gi, ((mode, _cap), sel) in enumerate(
                    zip(layout.groups, picked)):
                full = dit_nfe_flops(self.cfg, mode,
                                     attn_backend=self.attn_backend)
                deep = (cache_ledger.deep_block_flops(
                    self.cfg, mode, self.cache_split,
                    attn_backend=self.attn_backend)
                    if self.cache is not None else 0.0)
                for i, f in enumerate(sel):
                    rids.append(f.req.id)
                    if self.cache is not None:
                        # refresh-aware ledger share: skip steps pay
                        # shallow blocks only
                        w = mult * sum(
                            full if r else full - deep
                            for r in rf_real[gi][:, i])
                    else:
                        w = mult * k * full
                    weights.append(float(w))
            if rids:
                self._attr.attribute_dispatch(
                    time=now,
                    label=f"k={k} groups={layout.groups}",
                    request_ids=rids, weights=weights,
                    wall_ns=int(wall_s * 1e9),
                    flops=int(step_flops),
                    bytes_=self._profile.xla_bytes(pkey))
        if self.controller is not None:
            fams = {mode for (mode, _c), sel
                    in zip(layout.groups, picked) if sel}
            self.controller.observe_calibration(
                fams.pop() if len(fams) == 1 else None,
                step_flops, wall_s)

    def _apply_poison(self, outs: Tuple, picked: List[List[InFlight]]
                      ) -> Tuple:
        """Fault seam (post-dispatch host hook): overwrite targeted
        requests' packed-step output rows with NaN — the failure a
        silently degraded weak step would have produced in-graph. Only
        reachable when a FaultPlan is armed."""
        outs = list(outs)
        for g, sel in enumerate(picked):
            for i, f in enumerate(sel):
                if self._faults is not None \
                        and self._faults.take_poison(f.req.id):
                    outs[g] = outs[g].at[i].set(jnp.nan)
                    self.metrics.total_poisoned += 1
        return tuple(outs)

    def _scan_finite(self, tap: Dict[str, Any],
                     picked: List[List[InFlight]]) -> set:
        """Host read of the in-graph finite tap: ids of requests whose
        latent rows went non-finite during this dispatch. Called only
        after the completion branch's existing ``block_until_ready`` —
        never adds a sync point."""
        out: set = set()
        fin = tap.get("finite")
        if fin is None:
            return out
        for g, sel in enumerate(picked):
            if not sel:
                continue
            ok = np.asarray(fin[g])[:, :len(sel)].all(axis=0)
            for i, f in enumerate(sel):
                if not ok[i]:
                    out.add(f.req.id)
        return out

    def _quarantine_request(self, f: InFlight, now: float) -> None:
        """Non-finite latents detected: drop the poisoned trajectory,
        release its cache slot, and re-enqueue the request at the MOST
        POWERFUL menu level, restarting from step 0 with the same key —
        the recovered sample is exactly the clean powerful-path sample.
        With ``self_heal=False`` (fleet mode) the request is parked in
        ``quarantined`` instead, for the router to escalate with
        deadline-aware backoff."""
        if self.store is not None and f.cache_slot >= 0 \
                and self.store.owner_of(f.cache_mode,
                                        f.cache_slot) == f.req.id:
            self.store.release(f.cache_mode, f.cache_slot)
        self.metrics.total_quarantined += 1
        if self._rec is not None:
            self._rec.instant("quarantine",
                              args={"id": f.req.id, "step": f.step,
                                    "level": f.lp.level})
        if not self._self_heal:
            self.quarantined.append(f.req)
            return
        n = self._retries.get(f.req.id, 0)
        if n >= self._max_retries:
            # retry budget exhausted: park the request instead of looping
            # — the caller decides (losing it silently is never an option)
            self.quarantined.append(f.req)
            return
        self._retries[f.req.id] = n + 1
        self._queue.submit(
            Request(id=f.req.id, cond=f.req.cond,
                    budget=max(self.levels), deadline=f.req.deadline,
                    key=f.req.key), now)

    def take_quarantined(self) -> List[Request]:
        """Drain quarantined requests awaiting external escalation (the
        fleet routes them through ``Router.escalate``)."""
        out, self.quarantined = self.quarantined, []
        return out

    def take_expired(self) -> List[Request]:
        """Drain terminally expired requests (deadline passed while
        queued) for the caller's bookkeeping."""
        out, self.expired = self.expired, []
        return out

    def _retire(self, f: InFlight, now: float) -> ServedResult:
        mult = 2 if self.guided else 1
        tokens = int(mult * sum(self._seg_tokens[int(m)] for m in f.lp.modes))
        if self.store is not None and f.cache_slot >= 0 \
                and self.store.owner_of(f.cache_mode,
                                        f.cache_slot) == f.req.id:
            self.store.release(f.cache_mode, f.cache_slot)
        if f.refresh_mask is not None:
            self.metrics.record_refresh_intervals(
                cache_policy.refresh_intervals(f.refresh_mask))
            self.metrics.set_cache_bytes(self.store.bytes_resident)
        rec = RequestRecord(
            id=f.req.id, arrival=f.req.arrival, admit=f.admit, finish=now,
            deadline=f.req.deadline, budget_requested=f.req.budget,
            budget_served=f.lp.level, tokens=tokens, flops=f.lp.flops)
        self.metrics.record_request(rec)
        cost = None
        if self._attr is not None:
            cost = self._attr.finalize(
                f.req.id, queue_wait_s=f.admit - f.req.arrival,
                budget=str(f.lp.level))
        if self._rec is not None:
            # one row per request under the "requests" track (tid = id)
            self._rec.complete(
                f"req{f.req.id}", f.admit, now,
                pid=REQUEST_PID, tid=f.req.id,
                args={"budget_requested": f.req.budget,
                      "budget_served": f.lp.level,
                      "steps": len(f.lp.ts), "flops": f.lp.flops,
                      "queue_wait": f.admit - f.req.arrival})
        return ServedResult(request=f.req, x0=f.x,
                            budget_served=f.lp.level, record=rec,
                            cost=cost)

    # ------------------------------------------------------------------

    def run(self, max_steps: int = 100_000) -> List[ServedResult]:
        """Drain: step until queue and in-flight are empty. An uncaught
        exception first dumps a post-mortem bundle (when a watchdog with
        a postmortem dir is attached), then propagates unchanged."""
        out: List[ServedResult] = []
        steps = 0
        try:
            while (self._queue or self._inflight) and steps < max_steps:
                out.extend(self.step())
                steps += 1
        except Exception:
            if self._watchdog is not None:
                self._watchdog.dump(
                    reason="engine-exception",
                    engine_snapshot=self.snapshot_state(),
                    attribution=self._attr, registry=self._profile)
            raise
        return out

    def snapshot_state(self) -> Dict[str, Any]:
        """Flight-recorder view of engine state: queue, in-flight
        request positions, compile-cache counters, cache residency. All
        host-side — safe to call from the crash path."""
        snap: Dict[str, Any] = {
            "queued": [{"id": r.id, "budget": r.budget,
                        "deadline": r.deadline, "arrival": r.arrival}
                       for r in self._queue._pending],
            "inflight": [{"id": f.req.id, "level": f.lp.level,
                          "step": f.step, "of": len(f.lp.ts),
                          "mode": f.mode, "admit": f.admit,
                          "cache_slot": f.cache_slot}
                         for f in self._inflight],
            "compile": self.pipe.cache_stats(),
            "policy": self.policy,
        }
        if self.store is not None:
            snap["cache_bytes"] = self.store.bytes_resident
        return snap

    @property
    def idle(self) -> bool:
        return not self._queue and not self._inflight

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_inflight(self) -> int:
        return len(self._inflight)

    def cache_stats(self) -> Dict[str, int]:
        """The pipeline's compile-cache counters (packed-step runners are
        cached there; zero growth after warmup = zero recompiles)."""
        return self.pipe.cache_stats()
