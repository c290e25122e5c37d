"""Batched serving drivers.

LM path: prefill + greedy decode over fixed batch slots
(continuous-batching-lite: finished slots are refilled from the request
queue between decode steps).

DiT path: the continuous-batching serving engine (``repro.serving``,
DESIGN.md §serving). Requests carry a class label, a relative-compute
budget quantized onto the ``--budget-levels`` plan menu, and an optional
deadline; the engine keeps many requests in flight at different denoise
steps and packs each iteration token-wise (weak-phase requests
contribute fewer tokens) into compile-once bucket layouts under
``--max-tokens-per-step``. ``--policy`` picks admission/step ordering:
``fifo``, ``edf`` (earliest deadline first), or ``degrade`` (SLA-aware:
queued requests are demoted to the highest budget level the measured
arrival rate sustains).

``--replicas N`` serves through the fleet router (``repro.fleet``,
DESIGN.md §fleet): N in-process replica engines behind one front door,
placement picked by ``--router`` (cheapest priced backlog, cache
affinity, or round-robin), with heartbeat fault tolerance and elastic
drain/join. ``--mesh DATAxSEQ --replicas N`` composes the two layers:
N == DATA sequence-parallel replicas, each a fixed-slot engine over its
own SEQ-wide device mesh, routed by the same fleet policies. A bare
``--mesh`` without ``--replicas`` keeps the legacy single-driver
fixed-slot path (DESIGN.md §distributed).

Telemetry (DESIGN.md §telemetry): ``--trace out.json`` records the
request lifecycle (admit → plan → pack → dispatch → materialize →
finish, plus compile events) and the on-device taps, dumping a
Chrome-trace JSON loadable in https://ui.perfetto.dev;
``--metrics-interval N`` emits one structured ``[metrics]`` line every
N engine steps. Either flag routes dispatches through the tapped step
family — bit-identical latents, zero extra compiles.

  python -m repro.launch.serve --arch deepseek-7b --smoke --requests 8
  python -m repro.launch.serve --arch dit-xl-2 --budget 0.6 --smoke
  python -m repro.launch.serve --arch dit-xl-2 --smoke --policy degrade
  python -m repro.launch.serve --arch dit-xl-2 --mesh 1x8 --budget 0.6 --smoke
  python -m repro.launch.serve --arch dit-xl-2 --smoke --replicas 4 \
      --router affinity
  python -m repro.launch.serve --arch dit-xl-2 --smoke --mesh 2x4 --replicas 2
  python -m repro.launch.serve --arch dit-xl-2 --smoke --attn-backend dense \
      --cache-policy interval --trace trace.json --metrics-interval 25
"""
from __future__ import annotations

import argparse
import os
import time
from collections import defaultdict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import steps as st
from repro.models import lm
from repro.runtime.padding import pad_kv_cache


def parse_budget_levels(arg: Optional[str], base: float) -> List[float]:
    """``--budget-levels`` 'a,b,c' → sorted, deduped, validated floats in
    (0, 1]; default menu derived from ``--budget`` when unset. Validation
    runs on the ROUNDED values (and on the default menu too) so nothing
    outside (0, 1] ever reaches ``SamplingPlan``."""
    if not arg:
        raw = [base, (base + 1.0) / 2, 1.0]
    else:
        raw = []
        for part in arg.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                raw.append(float(part))
            except ValueError:
                raise SystemExit(f"--budget-levels: {part!r} is not a number")
        if not raw:
            raise SystemExit("--budget-levels: no levels given")
    levels = set()
    for b in raw:
        b = round(b, 2)
        if not 0.0 < b <= 1.0:
            raise SystemExit(f"--budget-levels/--budget: level {b} "
                             f"outside (0, 1]")
        levels.add(b)
    return sorted(levels)


def build_plan_menu(cfg, args, parallel=None) -> Dict[float, "object"]:
    """``--budget-levels`` → validated ``{level: SamplingPlan}``, printing
    one ``[plan]`` line per level."""
    from repro.pipeline import SamplingPlan

    levels = parse_budget_levels(getattr(args, "budget_levels", None),
                                 args.budget)
    plans: Dict[float, SamplingPlan] = {}
    for b in levels:
        plan = SamplingPlan(T=args.T, budget=float(b), solver=args.solver,
                            guidance_scale=args.cfg_scale, parallel=parallel,
                            attn_backend=getattr(args, "attn_backend",
                                                 "auto") or "auto")
        plan.validate(cfg)
        plans[b] = plan
        fs = plan.resolve_schedule(cfg)
        print(f"[plan] budget<={b:.2f}: T_weak={fs.phases[0][1]}/{args.T} "
              f"relative_compute={plan.relative_compute(cfg):.3f}")
    return plans


def serve_dit(cfg, args) -> None:
    """Serve DiT sampling requests: continuous-batching engine by default,
    the fleet router under ``--replicas``, the legacy fixed-slot mesh
    driver under a bare ``--mesh``."""
    from repro.diffusion import schedule as sch
    from repro.launch.mesh import make_inference_mesh, parse_mesh_arg
    from repro.models import dit as dit_mod
    from repro.pipeline import FlexiPipeline, ParallelSpec

    if getattr(args, "replicas", 1) > 1:
        _serve_dit_fleet(cfg, args)
        return

    mesh = None
    parallel = None
    s_sz = 1
    if getattr(args, "mesh", None):
        d_sz, s_sz = parse_mesh_arg(args.mesh)
        mesh = make_inference_mesh(d_sz, s_sz)
        if s_sz > 1:
            parallel = ParallelSpec()
        print(f"[mesh] data={d_sz} seq={s_sz} over "
              f"{len(mesh.devices.flat)} devices")

    key = jax.random.PRNGKey(0)
    params = dit_mod.init_dit(cfg, key)          # smoke: untrained weights
    pipe = FlexiPipeline(params, cfg, sch.linear_schedule(args.train_T),
                         mesh=mesh)
    plans = build_plan_menu(cfg, args, parallel)
    if mesh is not None:
        _serve_dit_fixed_slots(cfg, args, pipe, plans, s_sz, parallel, key)
    else:
        _serve_dit_engine(cfg, args, pipe, plans)


def _serve_dit_engine(cfg, args, pipe, plans) -> None:
    """The continuous-batching path (DESIGN.md §serving)."""
    from repro.serving import CacheSpec, ServingEngine
    from repro.telemetry import Telemetry
    from repro.telemetry import export as tel_export

    policy = getattr(args, "policy", None) or "fifo"
    max_tokens = getattr(args, "max_tokens_per_step", None)
    cache = None
    cache_policy = getattr(args, "cache_policy", None) or "off"
    if cache_policy != "off":
        cache = CacheSpec(policy=cache_policy,
                          interval=getattr(args, "cache_interval", 2),
                          threshold=getattr(args, "cache_threshold", 0.05))
        print(f"[cache] activation cache on: policy={cache.policy} "
              f"interval={cache.interval} threshold={cache.threshold} "
              f"split={cache.resolve_split(cfg.num_layers)}/"
              f"{cfg.num_layers} blocks")
    trace_path = getattr(args, "trace", None)
    metrics_interval = getattr(args, "metrics_interval", 0) or 0
    profile = bool(getattr(args, "profile", False))
    pm_dir = getattr(args, "postmortem_dir", None)
    slo_p99 = getattr(args, "slo_p99", None)
    telemetry = None
    if trace_path or metrics_interval or profile or pm_dir or slo_p99:
        # tracing implies taps: the tapped step family is bit-identical
        # and compile-parallel to the untapped one (DESIGN.md §telemetry)
        watchdog = None
        if pm_dir or slo_p99:
            from repro.telemetry.watchdog import Watchdog, WatchdogConfig
            watchdog = Watchdog(WatchdogConfig(p99_slo_s=slo_p99))
        telemetry = Telemetry(taps=True, profile=profile,
                              watchdog=watchdog, postmortem_dir=pm_dir)
        print(f"[telemetry] spans+taps on"
              + (", compiled-cost profiling on" if profile else "")
              + (f", post-mortems -> {pm_dir}" if pm_dir else "")
              + (f", trace -> {trace_path}" if trace_path else ""))
    engine = ServingEngine(pipe, plans, policy=policy,
                           max_tokens_per_step=max_tokens, cache=cache,
                           telemetry=telemetry)
    # warm-set shaping (ROADMAP): compile the small-cohort bucket ladder
    # off the hot path so mid-trace arrivals never meet a coarse layout
    n_pre = engine.precapture_warm_set(max_per_mode=2)
    print(f"[warm-set] precaptured {n_pre} small-cohort executables")
    print(engine.menu.describe())

    levels = sorted(plans)
    rng = np.random.default_rng(0)

    def submit_wave(n: int) -> None:
        now = engine.clock()
        for i in range(n):
            deadline = now + float(rng.uniform(0.5, 5.0))
            engine.submit(cond=int(rng.integers(0, cfg.dit.num_classes)),
                          budget=levels[i % len(levels)], deadline=deadline)

    t0 = time.time()

    def drain():
        """engine.run(), stepwise, emitting the periodic metrics line."""
        out = []
        while not engine.idle:
            out.extend(engine.step())
            if metrics_interval and \
                    engine.metrics.total_steps % metrics_interval == 0:
                print(tel_export.metrics_line(
                    engine.metrics.summary(wall=time.time() - t0),
                    taps=(telemetry.taps.aggregate()
                          if telemetry is not None else None),
                    compile_stats=engine.cache_stats(),
                    spans=(telemetry.recorder.counters()
                           if telemetry is not None else None)))
        return out

    # warmup wave compiles the bucket layouts this workload visits ...
    submit_wave(args.requests)
    results = drain()
    warm = engine.cache_stats()
    # ... after which serving the same workload shape is compile-free
    submit_wave(args.requests)
    results += drain()
    dt = time.time() - t0

    done = len(results)
    stats = engine.cache_stats()
    m = engine.metrics.summary(wall=dt)
    for r in results[:4]:
        print(f"[served] req={r.request.id} budget={r.budget_served:.2f} "
              f"latency={r.record.latency:.2f}s "
              f"x0_std={float(jnp.std(r.x0)):.3f}", flush=True)  # repro: ignore[hot-host-sync] — 4-sample debug print after drain
    print(f"served {done} requests in {int(m['steps'])} engine steps, "
          f"{dt:.1f}s ({done / max(dt, 1e-9):.2f} img/s), "
          f"{m.get('flops', 0.0) / 1e9:.2f} GFLOPs total")
    print(f"[metrics] policy={policy} p50={m.get('p50', 0.0):.2f}s "
          f"p99={m.get('p99', 0.0):.2f}s "
          f"packing_eff={m['packing_efficiency']:.3f} "
          f"deadline_hit={m.get('deadline_hit_rate', 1.0):.2f} "
          f"degraded={int(m['degraded'])}")
    if "attn_block_skip_rate" in m:
        print(f"[attn] backend={engine.attn_backend} "
              f"block_skip_rate={m['attn_block_skip_rate']:.3f} "
              f"(cross-segment score tiles never issued)")
    print(f"[cache] runners={stats['runners']} compiled={stats['compiled']} "
          f"hits={stats['hits']} misses={stats['misses']}")
    if cache is not None:
        cs = engine.metrics.cache_summary()
        print(f"[act-cache] hit_rate={cs['hit_rate']:.3f} "
              f"refreshes={cs['refreshes']} skips={cs['skips']} "
              f"interval_hist={cs['refresh_interval_hist']} "
              f"store_bytes_total={engine.store.bytes_total}")
    if telemetry is not None:
        agg = telemetry.taps.aggregate()
        if "drift" in agg:
            print(f"[taps] drift_mean={agg['drift']['mean']:.4g} "
                  f"drift_max={agg['drift']['max']:.4g} "
                  f"eps_norm_mean={agg['eps_norm']['mean']:.4g} over "
                  f"{agg['request_steps']} request-steps")
        elif "eps_norm" in agg:
            print(f"[taps] eps_norm_mean={agg['eps_norm']['mean']:.4g} "
                  f"over {agg['request_steps']} request-steps")
        print(tel_export.metrics_line(m, taps=agg, compile_stats=stats,
                                      spans=telemetry.recorder.counters(),
                                      tag="metrics-final"))
        if profile:
            # harvest AOT compiled costs for the whole warm set and
            # reconcile: analytic ledger vs XLA vs measured wall
            hv = telemetry.profile.harvest(pipe)
            if hv["errors"]:
                failed = [f"{r.label}: {r.error}"
                          for r in telemetry.profile.records.values()
                          if r.error is not None]
                raise SystemExit(f"[profile] {hv['errors']} runner(s) "
                                 f"failed to compile: {failed}")
            hstats = engine.cache_stats()
            assert hstats["compiled"] == stats["compiled"], \
                "AOT cost harvest must not touch the jit compile cache"
            print(f"[profile] harvest: {hv}")
            for line in telemetry.profile.report_lines():
                print(line)
            cons = telemetry.attribution.conservation()
            print(f"[attrib] conservation deltas {cons} over "
                  f"{len(telemetry.attribution.finalized)} finalized "
                  f"requests (all must be 0)")
            for r in results[:4]:
                if r.cost is not None:
                    c = r.cost
                    print(f"[attrib] req={c.request_id} "
                          f"flops={c.flops / 1e9:.2f}G "
                          f"bytes={c.bytes / 1e6:.1f}MB "
                          f"wall={c.wall_ms:.1f}ms "
                          f"dispatches={c.dispatches} "
                          f"queue_wait={c.queue_wait_s:.3f}s")
            calib = (engine.controller.calibration
                     if engine.controller is not None else None)
            if calib:
                fams = {m: f"{v:.3e}"
                        for m, v in calib["per_family"].items()}
                print(f"[calib] wall_per_analytic_flop "
                      f"global={calib['global']:.3e} per_family={fams}")
        if telemetry.watchdog is not None and telemetry.watchdog.alerts:
            for a in telemetry.watchdog.alerts:
                print(f"[alert] {a.kind} step={a.step} value={a.value:.4g} "
                      f"limit={a.limit:.4g} {a.detail}")
            if telemetry.watchdog.dumps_written:
                print(f"[postmortem] "
                      f"{len(telemetry.watchdog.dumps_written)} bundle(s) "
                      f"-> {telemetry.watchdog.dumps_written}")
        if trace_path:
            # drift/eps counter tracks: the timeline shows WHEN replay
            # error spiked, aligned with the dispatch spans
            for when, vals in telemetry.taps.counter_series():
                telemetry.recorder.counter("taps", vals, ts=when)
            telemetry.recorder.dump(trace_path)
            print(f"[trace] {telemetry.recorder.events_recorded} events "
                  f"({telemetry.recorder.events_dropped} dropped) -> "
                  f"{trace_path} (open in ui.perfetto.dev)")
    # only the fifo drain replays deterministically (edf priorities move
    # with the wall clock, degradation shifts the level mix); frozen-mode
    # zero-compile serving for those is exercised in bench_serving
    if policy == "fifo":
        assert stats["compiled"] == warm["compiled"], \
            "steady-state serving must not recompile after bucket warmup"


def _serve_dit_fleet(cfg, args) -> None:
    """The fleet path (DESIGN.md §fleet): ``--replicas N`` in-process
    replica engines behind the router. Without ``--mesh`` every replica
    is a packed continuous-batching engine with its own pipeline, its
    params placed on its own device (round-robin when there are fewer
    devices than replicas); with
    ``--mesh DATAxSEQ`` (DATA == N) each replica is a fixed-slot engine
    over its own contiguous SEQ-wide device slice, so sequence-parallel
    sharding composes with fleet routing."""
    from repro.diffusion import schedule as sch
    from repro.fleet import Fleet, partition_devices
    from repro.fleet.replica import pipe_device, replica_pipelines
    from repro.launch.mesh import parse_mesh_arg
    from repro.models import dit as dit_mod
    from repro.pipeline import FlexiPipeline, ParallelSpec

    n = args.replicas
    key = jax.random.PRNGKey(0)
    params = dit_mod.init_dit(cfg, key)          # smoke: untrained weights
    sched = sch.linear_schedule(args.train_T)
    s_sz = 1
    parallel = None
    pipes = None
    engine_kind = "packed"
    engine_kwargs = None
    if getattr(args, "mesh", None):
        d_sz, s_sz = parse_mesh_arg(args.mesh)
        if d_sz != n:
            raise SystemExit(f"--mesh {args.mesh}: DATA={d_sz} must equal "
                             f"--replicas {n} on the fleet path (one "
                             f"replica per data-parallel slice)")
        if s_sz > 1:
            # packed engines are single-replica; seq-parallel replicas run
            # fixed-slot engines over per-replica meshes
            parallel = ParallelSpec()
            engine_kind = "fixed"
        devs = jax.devices()
        slices = partition_devices(range(n * s_sz), n, s_sz)
        pipes = []
        for sl in slices:
            mesh = jax.make_mesh((1, s_sz), ("data", "seq"),
                                 devices=[devs[i] for i in sl])
            pipes.append(FlexiPipeline(params, cfg, sched, mesh=mesh))
        print(f"[mesh] {n} replica(s) x seq={s_sz}: slices "
              f"{[list(s) for s in slices]}")
    plans = build_plan_menu(cfg, args, parallel)
    if engine_kind == "packed":
        engine_kwargs = {"policy": getattr(args, "policy", None) or "fifo",
                         "max_tokens_per_step":
                             getattr(args, "max_tokens_per_step", None)}
    if pipes is None:
        # one-chip replicas: each owns a pipeline whose params live on its
        # own device, so the replicas' steps run on distinct chips
        pipes = replica_pipelines(params, cfg, sched, n)
        print(f"[replicas] {n} packed replica(s) on devices "
              f"{[pipe_device(p).id for p in pipes]}")
    # warm each replica's small-cohort ladder off the serving path
    fleet = Fleet(pipes[0], plans, n, router=args.router,
                  pipes=pipes, engine_kind=engine_kind,
                  seq_parallel=s_sz, batch_size=args.batch_slots,
                  engine_kwargs=engine_kwargs,
                  warm_background=True)

    levels = sorted(plans)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        deadline = fleet.now + float(rng.uniform(0.5, 5.0))
        fleet.submit(cond=int(rng.integers(0, cfg.dit.num_classes)),
                     budget=levels[i % len(levels)], deadline=deadline)
    results = fleet.run()
    if engine_kind == "packed":
        fleet.wait_warm(timeout=600.0)
    dt = time.time() - t0
    s = fleet.summary()
    for r in results[:4]:
        print(f"[served] req={r.rid} replica={r.replica} "
              f"budget={r.budget_served:.2f} latency={r.latency:.2f}s "
              f"x0_std={float(jnp.std(r.x0)):.3f}", flush=True)  # repro: ignore[hot-host-sync] — 4-sample debug print after drain
    print(f"[fleet] served {int(s['served'])} requests over "
          f"{s['replicas']} replicas in {dt:.1f}s "
          f"({len(results) / max(dt, 1e-9):.2f} img/s) "
          f"router={args.router}")
    print(f"[fleet] affinity_hit_rate={s['affinity_hit_rate']:.3f} "
          f"placements={int(s['router']['placements'])} "
          f"handbacks={int(s['router']['handbacks'])} "
          f"hedges={int(s['router']['hedges'])}")
    c = s["compile"]
    print(f"[cache] pipes={c['pipes']} runners={c['runners']} "
          f"compiled={c['compiled']} hits={c['hits']} "
          f"misses={c['misses']}")


def _serve_dit_fixed_slots(cfg, args, pipe, plans, s_sz, parallel, key
                           ) -> None:
    """Legacy fixed-batch-slot driver, kept for ``--mesh`` runs (the
    packed engine is single-host)."""
    T, B = args.T, args.batch_slots
    levels = sorted(plans)
    if parallel is not None:
        from repro.distributed import plan_partition
        for b in levels:
            fs = plans[b].resolve_schedule(cfg)
            part = plan_partition(cfg, fs, s_sz, parallel)
            per_phase = " ".join(
                f"m{p.mode}:{p.tokens}+{p.pad}pad/{p.sp}" for p, nn in
                part.phases if nn)
            coll = part.collective_bytes(
                cfg, cfg_scale_active=args.cfg_scale != 0)
            print(f"[shard]   {per_phase} impl="
                  f"{part.phases[0][0].impl} "
                  f"collective={coll / 1e6:.1f}MB/sample "
                  f"eff={part.parallel_efficiency(cfg):.3f}")

    rng = np.random.default_rng(0)
    queue: Dict[float, List[int]] = defaultdict(list)   # budget → labels
    for i in range(args.requests):
        queue[levels[i % len(levels)]].append(
            int(rng.integers(0, cfg.dit.num_classes)))

    done = 0
    batches = 0
    total_flops = 0.0
    t0 = time.time()
    while any(queue.values()):
        # fill the slots from the fullest bucket (continuous-batching-lite)
        b = max(queue, key=lambda k: len(queue[k]))
        labels = [queue[b].pop(0) for _ in range(min(B, len(queue[b])))]
        n_real = len(labels)
        # pad to exactly B slots so every batch hits the same executable
        labels += [labels[-1]] * (B - n_real)
        res = pipe.sample(plans[b], B, jax.random.fold_in(key, 100 + batches),
                          cond=jnp.asarray(labels, jnp.int32))
        jax.block_until_ready(res.x0)
        done += n_real
        batches += 1
        total_flops += res.flops * n_real / B
        print(f"[batch {batches}] budget={b:.2f} served={n_real} "
              f"(pad={B - n_real}) rel_compute={res.relative_compute:.3f} "
              f"x0_std={float(jnp.std(res.x0[:n_real])):.3f}", flush=True)  # repro: ignore[hot-host-sync] — per-batch progress log
    dt = time.time() - t0
    stats = pipe.cache_stats()
    print(f"served {done} requests in {batches} batches, {dt:.1f}s "
          f"({done / max(dt, 1e-9):.2f} img/s), "
          f"{total_flops / 1e9:.2f} GFLOPs total")
    print(f"[cache] runners={stats['runners']} compiled={stats['compiled']} "
          f"hits={stats['hits']} misses={stats['misses']}")
    assert stats["compiled"] <= len(levels), \
        "budget switches must not recompile beyond one runner per plan"


def serve_lm(cfg, args) -> None:
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    B = args.batch_slots
    S_max = args.prompt_len + args.max_new

    prefill = jax.jit(st.make_prefill_step(cfg))
    decode = jax.jit(st.make_decode_step(cfg))

    rng = np.random.default_rng(0)
    pending: List[np.ndarray] = [
        rng.integers(0, cfg.vocab_size, size=(args.prompt_len,),
                     dtype=np.int32)
        for _ in range(args.requests)]
    done = 0
    t0 = time.time()
    tokens_out = 0
    while pending or done < args.requests:
        batch_prompts = [pending.pop(0) for _ in range(min(B, len(pending)))]
        if not batch_prompts:
            break
        prompts = jnp.asarray(np.stack(batch_prompts))
        inputs = {"tokens": prompts}
        if cfg.family == "vlm":
            inputs["vision"] = jnp.zeros((len(batch_prompts),
                                          cfg.vision_tokens, cfg.d_model))
        if cfg.family == "audio":
            inputs["frames"] = jnp.zeros((len(batch_prompts),
                                          cfg.audio_frames, cfg.d_model))
        logits, cache = prefill(params, inputs)
        # pad cache along seq to S_max so decode can write new positions
        cache = pad_kv_cache(cache, args.prompt_len, args.max_new)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        outs = [tok]
        for i in range(args.max_new - 1):
            pos = jnp.full((len(batch_prompts),), args.prompt_len + i,
                           jnp.int32)
            logits, cache = decode(params, cache, tok, pos)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            outs.append(tok)
            tokens_out += len(batch_prompts)
        done += len(batch_prompts)
        gen = jnp.concatenate(outs, axis=1)
        print(f"[batch done] {len(batch_prompts)} reqs, "
              f"first gen: {np.asarray(gen[0])[:8].tolist()}", flush=True)
    dt = time.time() - t0
    print(f"served {done} requests, {tokens_out} tokens in {dt:.1f}s "
          f"({tokens_out/max(dt,1e-9):.1f} tok/s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    # LM path
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    # DiT path
    ap.add_argument("--budget", type=float, default=0.6,
                    help="base relative-compute budget for DiT requests")
    ap.add_argument("--budget-levels", default=None,
                    help="comma-separated relative-compute menu, e.g. "
                         "'0.4,0.6,1.0' (default: derived from --budget)")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "edf", "degrade"],
                    help="serving-engine admission/step policy: arrival "
                         "order, earliest deadline first, or SLA-aware "
                         "budget degradation under load")
    ap.add_argument("--max-tokens-per-step", type=int, default=None,
                    help="token-packing budget of one engine step "
                         "(default: four full-grid CFG requests)")
    ap.add_argument("--cache-policy", default="off",
                    choices=["off", "interval", "banded", "proxy"],
                    help="cross-step activation cache refresh policy "
                         "(DESIGN.md §cache); off disables caching")
    ap.add_argument("--cache-interval", type=int, default=2,
                    help="refresh every k steps (interval policy / band "
                         "fallback); 1 is bit-identical to no cache")
    ap.add_argument("--cache-threshold", type=float, default=0.05,
                    help="proxy policy: analytic conditioning-drift "
                         "threshold triggering a refresh")
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "pallas", "xla-blocked", "dense"],
                    help="attention backend (DESIGN.md §attention-backend): "
                         "auto runs the segment-aware Pallas flash kernel "
                         "on packed/long token streams, dense XLA otherwise. "
                         "On CPU-only hosts the kernel executes in interpret "
                         "mode (semantics-true, wall-clock-slow) — pass "
                         "'dense' there when serving for throughput")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record per-request span tracing + device taps "
                         "and dump a Chrome-trace JSON loadable in "
                         "ui.perfetto.dev (DESIGN.md §telemetry)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="emit one structured [metrics] line every N "
                         "engine steps (0 = off); also enables taps")
    ap.add_argument("--profile", action="store_true",
                    help="compiled-cost profiling (DESIGN.md §profiling): "
                         "harvest XLA cost/memory analysis for every "
                         "compiled runner, measure per-dispatch wall, "
                         "attribute served cost per request, and print "
                         "the analytic/XLA/wall reconciliation report")
    ap.add_argument("--postmortem-dir", default=None, metavar="DIR",
                    help="enable the SLO watchdog + flight recorder: "
                         "alerts and uncaught engine exceptions dump a "
                         "post-mortem bundle (spans, engine/cache/queue "
                         "snapshot, attribution, compiled costs) here")
    ap.add_argument("--slo-p99", type=float, default=None, metavar="SEC",
                    help="p99 latency SLO for the watchdog's rolling "
                         "breach detector (default: off)")
    ap.add_argument("--mesh", default=None,
                    help="DATAxSEQ device mesh for the DiT path, e.g. 2x4. "
                         "With --replicas N (N == DATA) each replica owns "
                         "one contiguous SEQ-wide device slice and the "
                         "fleet router places requests across them; "
                         "without --replicas the legacy single-driver "
                         "fixed-slot path shards each batch over the "
                         "whole mesh")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through the fleet router with N replica "
                         "engines (repro.fleet, DESIGN.md §fleet); 1 = "
                         "single engine, no router")
    ap.add_argument("--router", default="cheapest",
                    choices=["cheapest", "affinity", "rr"],
                    help="fleet placement policy: cheapest priced "
                         "backlog, cache affinity (sticky home replica + "
                         "class sharding), or round-robin")
    ap.add_argument("--T", type=int, default=20,
                    help="DiT denoising steps per request")
    ap.add_argument("--train-T", type=int, default=1000,
                    help="diffusion schedule length the DiT was trained at")
    ap.add_argument("--solver", default="ddim",
                    choices=["ddim", "ddpm", "dpm2"])
    ap.add_argument("--cfg-scale", type=float, default=1.5)
    args = ap.parse_args()

    if args.mesh and os.environ.get("JAX_PLATFORMS") == "cpu":
        # CPU smoke runs: make sure enough host devices exist BEFORE the
        # jax backend initializes. On an accelerator the mesh is built
        # from its real devices.
        from repro.launch.mesh import ensure_host_devices, parse_mesh_arg
        ensure_host_devices(int(np.prod(parse_mesh_arg(args.mesh))))
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = jax.devices()
    print(f"[device] platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.family == "dit":
        serve_dit(cfg, args)
    else:
        serve_lm(cfg, args)


if __name__ == "__main__":
    main()
