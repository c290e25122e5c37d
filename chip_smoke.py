#!/usr/bin/env python3
"""Chip smoke test: serve full-width dit-xl-2 on a TPU through the normal
entry points and check what comes out.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four one-chip replicas, one process

One chip: the compiled flash kernel alone against the plain attention
oracle (``kernels/attention/ref.py``) on a packed segment layout at head
dim 72; then ``FlexiPipeline`` -> ``ServingEngine`` -> packed step ->
Pallas flash kernel at the published dit-xl-2 widths (28 layers, d=1152,
16 heads, patch 2 and 4, CFG), with the warm-set precapture and two
identical waves of requests. It fails unless the platform is a TPU, the
packed step holds the compiled kernel (``tpu_custom_call``), the second
wave compiles nothing, every output is finite, and one served request
agrees with ``FlexiPipeline.sample`` on dense attention under
``jax.default_matmul_precision("highest")``.

Four chips: only the fleet path — four one-chip replicas behind the
router, each with its params on its own device — and what it is compared
with: the same requests, keys and budgets through one single-chip
engine. Two identical waves go through the fleet; it fails unless every
device served requests in the second wave, that wave compiled nothing, no
healthy replica was declared dead, and the outputs agree with the single
engine.

Weights are random, made from ``--seed`` by ``init_dit``; the layers
``init_dit`` zero-initialises (adaLN gates, final adaLN, de-embedding)
get seeded values too, so that the network's output depends on every
block. Timings printed here are smoke timings, not benchmarks. The last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: served vs reference tolerance on the relative RMS error of the final
#: latents (see CHANGES.md): bf16 activations round at 2^-8, and the two
#: paths round in different places in each of 28 blocks x 2 CFG branches
#: x 10 steps, so a few units of 2^-8 are expected; 2^-5 = 8 units.
REL_RMS_TOL = 2.0 ** -5
#: the compiled kernel vs the f32 oracle on bf16 inputs: the repo's bf16
#: attention tolerance (tests/test_kernels.py)
KERNEL_TOL = 2e-2

T_STEPS = 10
LEVELS = (0.6, 1.0)
N_REQUESTS = 8


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileCounter:
    """Counts XLA compiles (and their seconds) process-wide, through
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.count = 0
        self.seconds = 0.0
        self.persistent_hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_kw):
            if name == event:
                self.count += 1
                self.seconds += secs

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.persistent_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def seeded_params(cfg, seed: int):
    """``init_dit`` weights, with its zero-initialised output layers
    seeded too (otherwise every output would be exactly zero)."""
    import jax
    import jax.numpy as jnp
    from repro.models import dit as dit_mod

    key = jax.random.PRNGKey(seed)
    params = dit_mod.init_dit(cfg, key)
    d = cfg.d_model

    def seeded(a, i, scale):
        return (jax.random.normal(jax.random.fold_in(key, i), a.shape,
                                  jnp.float32) * scale).astype(a.dtype)

    params["blocks"]["ada"]["w"] = seeded(params["blocks"]["ada"]["w"], 1,
                                          0.5 / d ** 0.5)
    params["final"]["ada"]["w"] = seeded(params["final"]["ada"]["w"], 2,
                                         0.5 / d ** 0.5)
    params["deembed"]["w_flex"] = seeded(params["deembed"]["w_flex"], 3,
                                         1.0 / d ** 0.5)
    return params


def make_plans():
    from repro.pipeline import SamplingPlan
    return {b: SamplingPlan(T=T_STEPS, budget=b, solver="ddim",
                            guidance_scale=1.5) for b in LEVELS}


def request_set(cfg, seed: int):
    """``N_REQUESTS`` (label, budget, key) triples from the seed,
    alternating the two budget levels so packs mix 64- and 256-token
    segments."""
    import jax
    import numpy as np
    rng = np.random.default_rng(seed)
    base = jax.random.PRNGKey(seed + 1)
    return [(int(rng.integers(0, cfg.dit.num_classes)),
             LEVELS[i % len(LEVELS)], jax.random.fold_in(base, i))
            for i in range(N_REQUESTS)]


def serve_wave(engine, requests):
    """Submit the requests and drain the engine; returns (x0 per request
    in submission order, wall seconds)."""
    import jax
    t0 = time.perf_counter()
    ids = [engine.submit(cond=label, budget=b, key=key)
           for label, b, key in requests]
    results = engine.run()
    x0 = {r.request.id: r.x0 for r in results}
    out = [x0[i] for i in ids]
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def rel_rms(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def check_kernel(seed: int) -> None:
    """The compiled flash kernel vs the f32 oracle on a packed segment
    layout at dit-xl-2's head dim, including a row shorter than one
    128-token tile."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.attention import ops as attn_ops
    from repro.kernels.attention import ref as attn_ref

    H, hd = 16, 72
    for S, rows in ((256, ([64, 64, 128], [64, 64, 64], [256], [100, 60])),
                    (64, ([64], [32, 32]))):
        B = len(rows)
        seg = np.full((B, S), -1, np.int32)
        for r, lengths in enumerate(rows):
            off = 0
            for i, n in enumerate(lengths):
                seg[r, off:off + n] = i
                off += n
        ks = jax.random.split(jax.random.PRNGKey(seed + S), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.bfloat16)
                   for kk in ks)
        fn = jax.jit(lambda q, k, v, s: attn_ops.flash_attention(
            q, k, v, causal=False, segment_ids=s))
        seg = jnp.asarray(seg)
        hlo = fn.lower(q, k, v, seg).compile().as_text()
        _check("tpu_custom_call" in hlo,
               f"flash kernel at S={S} did not compile to a Mosaic kernel")
        got = np.asarray(fn(q, k, v, seg), np.float32)
        want = np.asarray(attn_ref.attention_ref(
            q, k, v, causal=False, segment_ids=seg), np.float32)
        err = float(np.abs(got - want).max())
        _log(f"kernel S={S} hd={hd} segments={list(rows)}: "
             f"max|kernel-ref|={err:.3e} (tol {KERNEL_TOL})")
        _check(np.isfinite(got).all(), "flash kernel output not finite")
        _check(err <= KERNEL_TOL, f"flash kernel vs ref {err} > "
               f"{KERNEL_TOL}")


def run_one_chip(cfg, seed: int, counter: CompileCounter) -> None:
    import jax
    import numpy as np
    from repro.diffusion import schedule as sch
    from repro.pipeline import FlexiPipeline
    from repro.serving import ServingEngine
    from repro.telemetry.profile import packed_arg_specs

    check_kernel(seed)

    c0, s0 = counter.count, counter.seconds
    pipe = FlexiPipeline(seeded_params(cfg, seed), cfg,
                         sch.linear_schedule(1000))
    plans = make_plans()
    engine = ServingEngine(pipe, plans, policy="fifo")
    t0 = time.perf_counter()
    n_pre = engine.precapture_warm_set(max_per_mode=2)
    _log(f"warm-set precapture: {n_pre} executables in "
         f"{time.perf_counter() - t0:.1f}s")
    requests = request_set(cfg, seed)
    wave1, wall1 = serve_wave(engine, requests)
    warm_compiles = counter.count
    wave2, wall2 = serve_wave(engine, requests)
    wave2_compiles = counter.count - warm_compiles
    stats = engine.cache_stats()
    _log(f"compiles before serving steady state: {warm_compiles - c0} "
         f"({counter.seconds - s0:.1f}s compiling, "
         f"{counter.persistent_hits} persistent-cache hits); "
         f"second wave: {wave2_compiles} compiles; engine runners "
         f"{stats['runners']}, compiled {stats['compiled']}")
    _check(wave2_compiles == 0,
           f"second wave compiled {wave2_compiles} executable(s)")
    outs = [np.asarray(x) for x in wave1 + wave2]
    _check(all(np.isfinite(x).all() for x in outs),
           "non-finite served output")
    _check(all(x.shape == cfg.dit.latent_shape for x in outs),
           f"served output shape {outs[0].shape} != "
           f"{cfg.dit.latent_shape}")
    diff = max(float(np.abs(a - b).max()) for a, b in zip(outs[:N_REQUESTS],
                                                          outs[N_REQUESTS:]))
    _log(f"wave1 vs wave2 max|diff|={diff:.3e} (same requests and keys)")
    _log(f"smoke timing (not a benchmark): wave 2 served {N_REQUESTS} "
         f"images in {wall2:.3f}s = {N_REQUESTS / wall2:.2f} img/s "
         f"(wave 1: {wall1:.3f}s)")

    # the compiled packed step holds the Mosaic flash kernel
    packed = [(k, fn) for k, fn in pipe.runners().items()
              if k[0] == "packed"]
    key, fn = max(packed, key=lambda kv: len(kv[0][1].groups))
    hlo = fn.lower(*packed_arg_specs(cfg, key, pipe.params)).compile() \
        .as_text()
    _check("tpu_custom_call" in hlo,
           f"packed step {key[1].groups} holds no compiled Pallas kernel")
    _log(f"packed step groups={key[1].groups} k={key[5]}: tpu_custom_call "
         f"present (compiled flash kernel)")

    # one served request, recomputed by the plain pipeline on dense
    # attention at the highest matmul precision
    idx = 0                                  # a budget-0.6 request
    label, b, rkey = requests[idx]
    ref_plan = dataclasses.replace(plans[b], attn_backend="dense")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(pipe.sample(ref_plan, 1, rkey,
                                     cond=np.asarray([label], np.int32)).x0[0])
    err = rel_rms(outs[idx], ref)
    max_abs = float(np.abs(outs[idx] - ref).max())
    _log(f"served vs dense reference (budget {b}, label {label}): "
         f"rel_rms={err:.3e} (tol {REL_RMS_TOL:.3e}) max|diff|={max_abs:.3e} "
         f"max|ref|={float(np.abs(ref).max()):.3e}")
    _check(np.isfinite(ref).all(), "non-finite reference output")
    _check(err <= REL_RMS_TOL, f"served vs reference rel_rms {err} > "
           f"{REL_RMS_TOL}")
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    _log(f"peak_bytes_in_use={peak}")


def run_fleet(cfg, seed: int, counter: CompileCounter,
              n_replicas: int) -> None:
    import jax
    import numpy as np
    from repro.diffusion import schedule as sch
    from repro.fleet import Fleet
    from repro.fleet.replica import pipe_device, replica_pipelines
    from repro.serving import ServingEngine

    devs = jax.devices()
    _check(len(devs) >= n_replicas,
           f"--chips {n_replicas} needs {n_replicas} devices, found "
           f"{len(devs)}")
    plans = make_plans()
    pipes = replica_pipelines(seeded_params(cfg, seed), cfg,
                              sch.linear_schedule(1000), n_replicas)
    # round-robin placement: every replica gets the same share of both
    # waves, so "every device served" and "wave 2 compiles nothing" are
    # checks of the path, not of the router's wall-clock price estimates
    # (which wave 1's compiles would skew)
    fleet = Fleet(pipes[0], plans, n_replicas, router="rr", pipes=pipes,
                  engine_kind="packed", engine_kwargs={"policy": "fifo"},
                  warm_background=True)
    t0 = time.perf_counter()
    fleet.wait_warm(timeout=900.0)
    _log(f"fleet warm-set capture on {n_replicas} devices: "
         f"{time.perf_counter() - t0:.1f}s, {counter.count} compiles "
         f"({counter.seconds:.1f}s compiling)")

    # two identical waves: the first also compiles the eager ops around
    # the steps (per device), the second must compile nothing
    requests = request_set(cfg, seed)
    waves = []
    for wave in (1, 2):
        c0 = counter.count
        steps0 = {rid: rep.engine.metrics.total_steps
                  for rid, rep in fleet.replicas.items()}
        t0 = time.perf_counter()
        fids = [fleet.submit(cond=label, budget=b, key=key)
                for label, b, key in requests]
        results = {r.rid: r for r in fleet.run()}
        out = [np.asarray(results[f].x0) for f in fids]
        wall = time.perf_counter() - t0
        served = {rid: sum(results[f].replica == rid for f in fids)
                  for rid in fleet.replicas}
        steps = {rid: rep.engine.metrics.total_steps - steps0[rid]
                 for rid, rep in fleet.replicas.items()}
        _log(f"fleet wave {wave}: {counter.count - c0} compiles, "
             f"{len(fleet.readmit_latencies)} re-admissions so far, "
             f"{wall:.3f}s")
        for rid, rep in sorted(fleet.replicas.items()):
            _log(f"  device {pipe_device(rep.engine.pipe).id}: replica {rid} "
                 f"dispatches={steps[rid]} requests served={served[rid]}")
        waves.append((out, counter.count - c0, served, wall))
    fleet_out, wave2_compiles, served, wall = waves[1]
    _log(f"smoke timing (not a benchmark): fleet wave 2 served "
         f"{N_REQUESTS} images in {wall:.3f}s = {N_REQUESTS / wall:.2f} "
         f"img/s")
    used = {pipe_device(rep.engine.pipe).id for rep in fleet.replicas.values()}
    _check(len(used) == n_replicas, f"replicas share devices: {sorted(used)}")
    _check(not fleet.readmit_latencies,
           f"{len(fleet.readmit_latencies)} request(s) re-admitted: a "
           f"healthy replica was declared dead")
    _check(all(n > 0 for n in served.values()),
           f"a device served no request in wave 2: {served}")
    _check(wave2_compiles == 0,
           f"fleet wave 2 compiled {wave2_compiles} executable(s)")
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(waves[0][0], fleet_out))
    _log(f"fleet wave1 vs wave2 max|diff|={diff:.3e} (same requests and "
         f"keys)")

    # the same requests, keys and budgets through one single-chip engine
    single = ServingEngine(pipes[0], plans, policy="fifo")
    single.precapture_warm_set(max_per_mode=2)
    single_out, _ = serve_wave(single, requests)
    single_out = [np.asarray(x) for x in single_out]
    errs = [rel_rms(a, b) for a, b in zip(fleet_out, single_out)]
    max_abs = max(float(np.abs(a - b).max())
                  for a, b in zip(fleet_out, single_out))
    _check(all(np.isfinite(x).all() for out, *_ in waves for x in out),
           "non-finite fleet output")
    _log(f"fleet vs single-chip engine: max rel_rms={max(errs):.3e} "
         f"(tol {REL_RMS_TOL:.3e}), max|diff|={max_abs:.3e}")
    _check(max(errs) <= REL_RMS_TOL,
           f"fleet vs single engine rel_rms {max(errs)} > {REL_RMS_TOL}")
    for d in devs[:n_replicas]:
        _log(f"device {d.id} peak_bytes_in_use="
             f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernel + engine on one chip; 4: only the "
                         "four-replica fleet path and its single-chip "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
              f"this script from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from repro.configs import get_config
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    _log(f"platform={dev.platform} device_kind={dev.device_kind} "
         f"count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{dev.platform!r}); this smoke test runs only on the chip",
              file=sys.stderr)
        return 1

    cfg = get_config("dit-xl-2")
    _log(f"config {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
         f"{cfg.attn.num_heads} heads x {cfg.attn.head_dim}, "
         f"{cfg.param_dtype}, T={T_STEPS}, budgets {LEVELS}, "
         f"{N_REQUESTS} requests")
    counter = CompileCounter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_fleet(cfg, args.seed, counter, n_replicas=4)
        else:
            run_one_chip(cfg, args.seed, counter)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _log(f"total compiles {counter.count} ({counter.seconds:.1f}s), "
         f"wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
