"""FlexiPipeline — the single FlexiDiT inference entry point (DESIGN.md
§pipeline).

The pipeline owns ``(params, cfg, diffusion schedule)`` and a cache of
compiled executables so that repeated ``sample`` calls — including budget
or mode switches between calls — never retrace or recompile:

* **static plans** compile one *phase runner* per plan signature
  ``(solver, resolved schedule, timestep ladder, guidance signature,
  LoRA variant, eps_transform)``; batch shape and conditioning are traced
  arguments, so jax's jit cache keys them per runner;
* **adaptive plans** compile one guided NFE per ``(mode, scale, LoRA
  variant)`` — the same two executables the static scheduler uses — and
  drive the probe loop in ``core.adaptive``.

``cache_stats()`` exposes our own hit/miss counters plus the true number
of XLA compilations (summed jit cache sizes), which tests assert stays
flat across repeated calls.

With a device mesh attached (``FlexiPipeline(..., mesh=...)``) plans may
carry a ``parallel=ParallelSpec(...)`` to run sequence-parallel through
``repro.distributed`` (DESIGN.md §distributed); the mesh fingerprint
joins the runner key so budget switches on a fixed mesh stay
compile-free while mesh swaps compile fresh runners.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import adaptive as adaptive_mod
from repro.core.flexify import merge_lora
from repro.core.guidance import GuidanceConfig, make_eps_fn
from repro.core.scheduler import FlexiSchedule
from repro.diffusion import flow, sampler
from repro.diffusion import schedule as sch
from repro.distributed.engine import SeqParallel, mesh_fingerprint
from repro.pipeline.packed import PackLayout, make_packed_step_fn
from repro.pipeline.plan import FLOW_SOLVERS, SamplingPlan
from repro.runtime import sharding as sharding_mod

Params = Dict[str, Any]
# eps_transform(eps, x, t) -> eps — e.g. spectral filtering probes (Fig. 2)
EpsTransform = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


@dataclasses.dataclass
class SampleResult:
    x0: jax.Array
    flops: float                  # actual FLOPs spent for the whole batch
    relative_compute: float       # vs the all-powerful baseline, same T
    trace: Dict[str, Any]         # schedule / switch point / probe gaps / ...


class FlexiPipeline:
    """Compile-once sampling for a flexified DiT.

    >>> pipe = FlexiPipeline(params, cfg, sched)
    >>> plan = SamplingPlan(T=20, budget=0.6)
    >>> res = pipe.sample(plan, n=16, key=jax.random.PRNGKey(0))
    """

    def __init__(self, params: Params, cfg: ModelConfig,
                 sched: sch.DiffusionSchedule,
                 mesh: Optional[Mesh] = None):
        assert cfg.family == "dit" and cfg.dit is not None, cfg.name
        self.params = params
        self.cfg = cfg
        self.sched = sched
        self.mesh = mesh
        self._runners: Dict[Tuple, Callable] = {}
        self._nfes: Dict[Tuple, Callable] = {}
        self._merged: Dict[int, Params] = {}
        self._hits = 0
        self._misses = 0
        # serializes cache miss/insert so a background warm thread
        # (fleet.warmup) racing the serving thread on the same key can't
        # both build: the loser would keep a runner the cache forgot and
        # the next lookup would compile a twin (a phantom recompile)
        self._cache_lock = threading.Lock()
        # (runner key) -> (arg ShapeDtypeStruct tree, analytic FLOPs per
        # call) for sample()-path runners, recorded only when
        # enable_cost_profiling() was called (DESIGN.md §profiling)
        self.profile_specs: Optional[Dict[Tuple, Tuple[Any, float]]] = None

    def set_mesh(self, mesh: Optional[Mesh]) -> None:
        """Attach / swap the device mesh. Compiled runners are keyed by the
        mesh fingerprint, so switching meshes compiles new runners while a
        fixed mesh (any number of budget switches) never recompiles."""
        self.mesh = mesh

    # ------------------------------------------------------------------
    # Cache plumbing

    def cache_stats(self) -> Dict[str, int]:
        with self._cache_lock:
            compiled = sum(f._cache_size() for f in self._runners.values())
            compiled += sum(f._cache_size() for f in self._nfes.values())
            return {"runners": len(self._runners),
                    "nfe_fns": len(self._nfes),
                    "hits": self._hits, "misses": self._misses,
                    "compiled": compiled}

    def update_params(self, params: Params) -> None:
        """Swap weights without dropping compiled executables (params are
        traced arguments, not baked-in constants)."""
        self.params = params
        self._merged.clear()

    def _lora_variant(self, plan: SamplingPlan) -> str:
        return "none" if self.cfg.dit.lora_rank <= 0 else plan.lora

    def _params_for_mode(self, mode: int, variant: str) -> Params:
        if variant != "merged" or mode == 0:
            return self.params
        if mode not in self._merged:
            self._merged[mode] = merge_lora(self.params, self.cfg, mode)
        return self._merged[mode]

    def _lookup(self, cache: Dict, key: Tuple, build: Callable) -> Callable:
        # build() under the lock is cheap (jit wrapping, no compile —
        # XLA compilation happens at first call and jax serializes that
        # internally); what must be atomic is miss-check + insert
        with self._cache_lock:
            if key in cache:
                self._hits += 1
            else:
                self._misses += 1
                cache[key] = build()
            return cache[key]

    def runners(self) -> Dict[Tuple, Callable]:
        """Read-only view of the compiled-runner cache. The compiled-cost
        registry (telemetry/profile.py) harvests AOT cost/memory analysis
        from these; the keys are the zero-recompile cache keys."""
        return dict(self._runners)

    def enable_cost_profiling(self) -> None:
        """Start recording ``(arg spec, analytic FLOPs)`` for
        sample()-path runners so ``CompiledCostRegistry.harvest`` can
        AOT-lower them. Packed runners need no recording — their specs
        derive from the cache key alone. Idempotent; recording is a
        host-side dict insert per ``sample`` call (no device work, no
        effect on jaxprs or latents)."""
        if self.profile_specs is None:
            self.profile_specs = {}

    def _record_spec(self, runner_key: Tuple, args: Tuple,
                     analytic_flops: float) -> None:
        if self.profile_specs is None:
            return
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype), args)
        self.profile_specs[runner_key] = (specs, float(analytic_flops))

    # ------------------------------------------------------------------
    # Conditioning

    def _default_cond(self, n: int, cond: Any) -> Tuple[Any, Any]:
        dit = self.cfg.dit
        if dit.conditioning == "class":
            y = (jnp.arange(n) % dit.num_classes if cond is None
                 else jnp.asarray(cond))
            return y, jnp.full((n,), dit.num_classes)
        if dit.conditioning == "text":
            if cond is None:
                raise ValueError("text-conditioned models need cond "
                                 "embeddings [n, text_len, text_dim]")
            y = jnp.asarray(cond)
            return y, jnp.zeros_like(y)
        return None, None

    # ------------------------------------------------------------------
    # Compiled runners

    def _phase_guidance(self, plan: SamplingPlan, mode: int) -> GuidanceConfig:
        if plan.guidance_active and plan.guidance_kind == "weak_cond" \
                and mode == 0:
            # §3.4: the weak model's *conditional* prediction guides the
            # powerful phase
            return GuidanceConfig(scale=plan.guidance_scale, mode_cond=0,
                                  mode_uncond=plan.weak_mode, kind="weak_cond")
        return GuidanceConfig(scale=plan.guidance_scale, mode_cond=mode,
                              mode_uncond=mode)

    def _param_set_modes(self, plan: SamplingPlan,
                         schedule: FlexiSchedule) -> Tuple[int, ...]:
        """Modes needing their own param tree: with merged LoRA each weak
        mode gets its own merge — including the weak mode serving only as
        the §3.4 guidance NFE — otherwise everything shares the base."""
        if self._lora_variant(plan) != "merged":
            return (0,)
        modes = {m for m, n in schedule.phases if n}
        if plan.guidance_active and plan.guidance_kind == "weak_cond":
            modes.add(plan.weak_mode)
        return tuple(sorted(modes))

    def _static_runner(self, plan: SamplingPlan, schedule: FlexiSchedule,
                       ts: np.ndarray, transform: Optional[EpsTransform],
                       engine: Optional[SeqParallel] = None) -> Callable:
        splits = schedule.split_timesteps(ts)
        set_idx = {m: i for i, m in
                   enumerate(self._param_set_modes(plan, schedule))}
        cfg = self.cfg

        def run(param_sets, x_T, cond, null_cond, key, text_mask,
                null_text_mask):
            phases = []
            for mode, tsub in splits:
                p = param_sets[set_idx.get(mode, 0)]
                g = self._phase_guidance(plan, mode)
                # §3.4 guidance NFE runs at the weak mode: under merged
                # LoRA it must see that mode's merged weights, not the base
                gp = (param_sets[set_idx[g.mode_uncond]]
                      if g.kind == "weak_cond" and g.mode_uncond in set_idx
                      else None)
                base_fn = make_eps_fn(p, cfg, cond, null_cond, g,
                                      text_mask, null_text_mask,
                                      guidance_params=gp, parallel=engine,
                                      attn_backend=plan.attn_backend)
                if transform is None:
                    fn = base_fn
                else:
                    def fn(x, t, _f=base_fn):
                        eps, lv = _f(x, t)
                        return transform(eps, x, t), lv
                phases.append((fn, tsub))
            return sampler.sample_phased(phases, self.sched, x_T, key,
                                         solver=plan.solver,
                                         clip_x0=plan.clip_x0)

        return jax.jit(run)

    def _flow_runner(self, plan: SamplingPlan, schedule: FlexiSchedule,
                     engine: Optional[SeqParallel] = None) -> Callable:
        taus = flow.tau_ladder(plan.T)
        splits = flow.split_tau_ladder(taus, schedule.phases)
        set_idx = {m: i for i, m in
                   enumerate(self._param_set_modes(plan, schedule))}
        solver = "euler" if plan.solver == "flow_euler" else "heun"
        cfg = self.cfg

        def run(param_sets, x_T, cond):
            phases = []
            for mode, tsub in splits:
                p = param_sets[set_idx.get(mode, 0)]
                phases.append((flow.make_flow_v_fn(
                    p, cfg, cond, mode=mode, parallel=engine,
                    attn_backend=plan.attn_backend), tsub))
            return flow.sample_flow_phased(phases, x_T, solver=solver)

        return jax.jit(run)

    def _cached_runner(self, plan: SamplingPlan, schedule: FlexiSchedule,
                       ts: np.ndarray, taps: bool = False) -> Callable:
        """Static runner with the cross-step activation cache (DESIGN.md
        §cache): per-phase refresh masks arrive as TRACED inputs, so one
        compiled runner serves every refresh policy at this (schedule,
        split) signature. ``taps`` (§telemetry) appends per-step
        eps-norm / replay-drift data outputs; latents are bit-identical
        either way and the flag joins the runner key."""
        from repro.cache import apply as cache_apply
        from repro.models import dit as dit_mod
        from repro.models.common import dtype_of
        splits = schedule.split_timesteps(ts)
        set_idx = {m: i for i, m in
                   enumerate(self._param_set_modes(plan, schedule))}
        cfg = self.cfg
        split = plan.cache.resolve_split(cfg.num_layers)

        def run(param_sets, x_T, cond, null_cond, key, text_mask,
                null_text_mask, masks):
            B = x_T.shape[0]
            dtype = dtype_of(cfg.compute_dtype)
            phases = []
            for i, (mode, tsub) in enumerate(splits):
                p = param_sets[set_idx.get(mode, 0)]
                g = self._phase_guidance(plan, mode)
                fn = cache_apply.make_cached_eps_fn(
                    p, cfg, cond, null_cond, g, text_mask,
                    null_text_mask, split,
                    attn_backend=plan.attn_backend)
                guided = g.scale != 0.0 and cond is not None
                delta0 = jnp.zeros(
                    cache_apply.delta_shape(cfg, mode, B, guided), dtype)
                phases.append((fn, tsub, masks[i], delta0))
            return cache_apply.sample_phased_cached(
                phases, self.sched, x_T, key, solver=plan.solver,
                clip_x0=plan.clip_x0, taps=taps)

        return jax.jit(run)

    def packed_step(self, layout: PackLayout, *, solver: str = "ddim",
                    guidance_scale: float = 1.5, clip_x0: float = 0.0,
                    k_steps: int = 1,
                    cache_split: Optional[int] = None,
                    attn_backend: str = "auto",
                    taps: bool = False) -> Callable:
        """Step-granular entry point (DESIGN.md §serving): the compiled
        executable advancing ONE packed engine step (``k_steps``
        micro-steps under lax.scan) at ``layout``. Latents, timesteps,
        conditioning, params, and solver keys are traced, so the serving
        engine replays a layout across arbitrary requests and denoise
        steps without recompiling; runners share this pipeline's cache,
        so ``cache_stats()`` tracks bucket warmup. ``cache_split``
        selects the activation-cached step family (per-request deltas +
        refresh flags are traced too — refresh policies never join the
        key). ``taps`` selects the telemetry step family (DESIGN.md
        §telemetry): same latents bit-for-bit plus on-device tap
        outputs; it is a build-time flag, so it joins the key."""
        key = ("packed", layout, solver, guidance_scale, clip_x0, k_steps,
               cache_split, attn_backend, taps)
        return self._lookup(
            self._runners, key,
            lambda: jax.jit(make_packed_step_fn(
                self.cfg, self.sched, layout, solver=solver,
                guidance_scale=guidance_scale, clip_x0=clip_x0,
                k_steps=k_steps, cache_split=cache_split,
                attn_backend=attn_backend, taps=taps)))

    def packed_step_is_warm(self, layout: PackLayout, *, solver: str = "ddim",
                            guidance_scale: float = 1.5,
                            clip_x0: float = 0.0,
                            k_steps: int = 1,
                            cache_split: Optional[int] = None,
                            attn_backend: str = "auto",
                            taps: bool = False) -> bool:
        """Whether :meth:`packed_step` would be a cache hit — the serving
        planner prefers warm executables so steady-state traffic never
        stalls on a compile."""
        return ("packed", layout, solver, guidance_scale, clip_x0,
                k_steps, cache_split, attn_backend, taps) in self._runners

    def warm_packed_layouts(self, *, solver: str = "ddim",
                            guidance_scale: float = 1.5,
                            clip_x0: float = 0.0,
                            cache_split: Optional[int] = None,
                            attn_backend: str = "auto",
                            taps: bool = False
                            ) -> Dict[int, List[PackLayout]]:
        """Compiled packed-step layouts grouped by micro-step depth k, for
        the given step family. A frozen serving engine
        (``allow_cold=False``) restricts its planner to these."""
        out: Dict[int, List[PackLayout]] = {}
        for key in self._runners:
            if key[0] == "packed" and key[2:5] == (solver, guidance_scale,
                                                   clip_x0) \
                    and key[6:9] == (cache_split, attn_backend, taps):
                out.setdefault(key[5], []).append(key[1])
        return out

    def _nfe_fn(self, mode: int, scale: float,
                attn_backend: str = "auto") -> Callable:
        cfg = self.cfg
        g = GuidanceConfig(scale=scale, mode_cond=mode, mode_uncond=mode)

        def nfe(params, x, t, cond, null_cond, text_mask, null_text_mask):
            return make_eps_fn(params, cfg, cond, null_cond, g,
                               text_mask, null_text_mask,
                               attn_backend=attn_backend)(x, t)

        return jax.jit(nfe)

    # ------------------------------------------------------------------
    # Sampling

    def sample(self, plan: SamplingPlan, n: int, key: jax.Array, *,
               cond: Any = None, x_T: Optional[jax.Array] = None,
               text_mask: Optional[jax.Array] = None,
               null_text_mask: Optional[jax.Array] = None,
               eps_transform: Optional[EpsTransform] = None,
               taps: bool = False) -> SampleResult:
        """Sample ``n`` latents under ``plan``. ``key`` seeds both the prior
        draw and the solver noise (``x_T`` overrides the prior draw).

        ``eps_transform`` is keyed by function *identity*: reuse the same
        callable across calls to reuse its compiled runner — a fresh
        closure per call compiles (and retains) a new runner each time.

        ``taps`` (cached plans only; DESIGN.md §telemetry) returns
        per-step eps-norm and cache replay-drift data outputs in
        ``result.trace["taps"]`` — same ``x0`` bit-for-bit.
        """
        plan.validate(self.cfg)
        if plan.solver in FLOW_SOLVERS and plan.guidance_active:
            raise ValueError("guided flow plans run in the serving engine's "
                             "packed step (ServingEngine)")
        if x_T is None:
            x_T = jax.random.normal(key, (n,) + self.cfg.dit.latent_shape)
        run_key = jax.random.fold_in(key, 1)
        y, null = self._default_cond(n, cond)
        variant = self._lora_variant(plan)

        if eps_transform is not None and (plan.is_adaptive
                                          or plan.solver in FLOW_SOLVERS):
            raise ValueError("eps_transform only applies to static "
                             "diffusion plans")
        if eps_transform is not None and plan.cache is not None:
            raise ValueError("eps_transform does not compose with the "
                             "activation cache")
        if taps and plan.cache is None:
            raise ValueError("taps instrument the cached runner (and the "
                             "serving engine's packed steps); this plan "
                             "has no cache")
        if plan.is_adaptive:
            return self._sample_adaptive(plan, x_T, run_key, y, null,
                                         text_mask, null_text_mask)

        ts = sch.respaced_timesteps(self.sched.num_steps, plan.T)
        schedule = plan.resolve_schedule(self.cfg)
        param_sets = tuple(self._params_for_mode(m, variant)
                           for m in self._param_set_modes(plan, schedule))
        engine = (SeqParallel.create(self.mesh, plan.parallel, self.cfg,
                                     attn_backend=plan.attn_backend)
                  if plan.parallel is not None else None)
        if self.mesh is not None:
            # committed single-device params can't mix with mesh-sharded
            # activations: replicate weights, shard the batch over the data
            # axes (no-ops once placed — jax.device_put short-circuits).
            # Sequence-parallel runners take REPLICATED inputs: the shard_map
            # in_specs re-introduce the (data, seq) split inside the
            # collective, and jax 0.4.x GSPMD miscompiles the mixed
            # batch-sharded + shard_map graph (see distributed.engine).
            repl = NamedSharding(self.mesh, P())
            bspec = (repl if engine is not None else
                     NamedSharding(self.mesh,
                                   sharding_mod.batch_spec(n, self.mesh)))
            param_sets = jax.device_put(param_sets, repl)
            x_T = jax.device_put(x_T, bspec)
            if y is not None:
                y = jax.device_put(y, bspec)
            if null is not None:
                null = jax.device_put(null, bspec)
        # mesh fingerprint joins the key: budget switches on a fixed mesh
        # reuse runners; swapping meshes compiles fresh ones
        sig = (plan.solver, plan.clip_x0, plan.guidance_scale,
               plan.guidance_kind, plan.weak_mode, variant,
               schedule.phases, tuple(int(t) for t in ts), eps_transform,
               plan.parallel, mesh_fingerprint(self.mesh),
               plan.attn_backend)
        if plan.solver in FLOW_SOLVERS:
            runner = self._lookup(
                self._runners, ("flow",) + sig,
                lambda: self._flow_runner(plan, schedule, engine))
            x0 = runner(param_sets, x_T, y)
            self._record_spec(("flow",) + sig, (param_sets, x_T, y),
                              plan.flops(self.cfg, batch=n))
        elif plan.cache is not None:
            from repro.cache import ledger as cache_ledger
            from repro.cache import policy as cache_policy
            # masks are runner INPUTS: interval/band/threshold switches
            # replay the same executable with different flag arrays
            masks = tuple(
                jnp.asarray(cache_policy.refresh_mask(plan.cache, tsub))
                for _m, tsub in schedule.split_timesteps(ts))
            runner = self._lookup(
                self._runners,
                ("cached",) + sig
                + (plan.cache.resolve_split(self.cfg.num_layers), taps),
                lambda: self._cached_runner(plan, schedule, ts, taps=taps))
            out = runner(param_sets, x_T, y, null, run_key, text_mask,
                         null_text_mask, masks)
            x0, tap_phases = out if taps else (out, None)
            fl, n_refresh, n_steps = cache_ledger.schedule_cached_flops(
                self.cfg, schedule, ts, plan.cache,
                cfg_scale_active=plan.guidance_active,
                lora_unmerged=(variant == "unmerged"))
            self._record_spec(
                ("cached",) + sig
                + (plan.cache.resolve_split(self.cfg.num_layers), taps),
                (param_sets, x_T, y, null, run_key, text_mask,
                 null_text_mask, masks), n * fl)
            trace = {"schedule": schedule, "timesteps": ts,
                     "refresh_masks": tuple(np.asarray(m) for m in masks),
                     "cache_refreshes": n_refresh,
                     "cache_steps": n_steps}
            if taps:
                trace["taps"] = tap_phases
            return SampleResult(
                x0=x0, flops=n * fl,
                relative_compute=plan.relative_compute(self.cfg),
                trace=trace)
        else:
            runner = self._lookup(
                self._runners, ("static",) + sig,
                lambda: self._static_runner(plan, schedule, ts, eps_transform,
                                            engine))
            x0 = runner(param_sets, x_T, y, null, run_key, text_mask,
                        null_text_mask)
            self._record_spec(("static",) + sig,
                              (param_sets, x_T, y, null, run_key,
                               text_mask, null_text_mask),
                              plan.flops(self.cfg, batch=n))
        return SampleResult(
            x0=x0, flops=plan.flops(self.cfg, batch=n),
            relative_compute=plan.relative_compute(self.cfg),
            trace={"schedule": schedule, "timesteps": ts})

    def _sample_adaptive(self, plan: SamplingPlan, x_T: jax.Array,
                         run_key: jax.Array, y: Any, null: Any,
                         text_mask, null_text_mask) -> SampleResult:
        ts = sch.respaced_timesteps(self.sched.num_steps, plan.T)
        variant = self._lora_variant(plan)
        n_modes = 1 + len(self.cfg.dit.flex_patch_sizes)
        fns: List[Callable] = []
        for mode in range(n_modes):
            jf = self._lookup(
                self._nfes, ("nfe", mode, plan.guidance_scale, variant,
                             plan.attn_backend),
                lambda m=mode: self._nfe_fn(m, plan.guidance_scale,
                                            plan.attn_backend))
            p = self._params_for_mode(mode, variant)
            fns.append(lambda x, t, _f=jf, _p=p:
                       _f(_p, x, t, y, null, text_mask, null_text_mask))
        res = adaptive_mod.adaptive_sample(
            fns, self.sched, x_T, ts, run_key, self.cfg,
            threshold=plan.budget.threshold,
            probe_every=plan.budget.probe_every,
            weak_mode=plan.weak_mode, solver=plan.solver,
            guided=plan.guidance_active,
            lora_unmerged=(variant == "unmerged"))
        return SampleResult(
            x0=res.x0, flops=res.flops,
            relative_compute=res.flops / res.flops_static_powerful,
            trace={"switch_step": res.switch_step, "gaps": res.gaps,
                   "timesteps": ts,
                   "flops_static_powerful": res.flops_static_powerful})
