"""Telemetry correctness gates (DESIGN.md §telemetry).

Two claims, both gated via ``baselines.json`` (what tracing costs is
measured on the chip, traced against untraced runs of the benchmark;
see PERF.md):

* **zero added recompiles** — after one warm drain per family, replaying
  the workload (a budget-mix switch each wave) compiles nothing, taps on
  or off. The tapped family is cached under its own key; turning
  telemetry on costs exactly the one-time warmup of that family.
* **drift tap ≡ eager** — the on-device replay-drift tap
  (``‖new_delta − old_delta‖`` inside the scan) matches an eager
  step-by-step host recomputation of the same quantity to ≤1e-5, on
  trained-like weights where drift is nonzero.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

T = 12
TRAIN_T = 100
N_REQ = 16
MAX_TOKENS = 4096
DRIFT_ATOL = 1e-5


def _bench_cfg():
    from repro.configs import get_config
    base = get_config("dit-xl-2").reduced()
    return dataclasses.replace(
        base, num_layers=6, d_model=256, d_ff=1024,
        attn=dataclasses.replace(base.attn, num_heads=8, num_kv_heads=8,
                                 head_dim=32))


def _trained_like(params, key):
    """Non-degenerate de-embed / adaLN gates so cached-replay drift is a
    real signal, not structurally zero (zero-init heads make every block
    an identity at init)."""
    import jax
    params["deembed"]["w_flex"] = jax.random.normal(
        jax.random.fold_in(key, 1),
        params["deembed"]["w_flex"].shape) * 0.1
    params["final"]["ada"]["w"] = jax.random.normal(
        jax.random.fold_in(key, 2), params["final"]["ada"]["w"].shape) * 0.05
    params["blocks"]["ada"]["w"] = jax.random.normal(
        jax.random.fold_in(key, 3), params["blocks"]["ada"]["w"].shape) * 0.05
    return params


def bench_telemetry() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common as C
    from benchmarks.baseline import check_baseline
    from repro.cache import apply as cache_apply
    from repro.core.guidance import GuidanceConfig
    from repro.diffusion import schedule as sch
    from repro.models import dit as dit_mod
    from repro.pipeline import FlexiPipeline, SamplingPlan
    from repro.serving import BucketMenu, CacheSpec, ServingEngine
    from repro.telemetry import Telemetry

    cfg = _bench_cfg()
    params = _trained_like(dit_mod.init_dit(cfg, jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(0))
    sched = sch.linear_schedule(TRAIN_T)
    pipe = FlexiPipeline(params, cfg, sched)
    cache = CacheSpec(policy="interval", interval=2)
    split = cache.resolve_split(cfg.num_layers)

    # ------------------------------------------------------------------
    # Gate 3 first (cheap, device-independent): drift tap ≡ eager replay

    B = 2
    g = GuidanceConfig(scale=1.5, mode_cond=0, mode_uncond=0)
    cond = jnp.asarray([1, 2], jnp.int32)
    null = jnp.full((B,), cfg.dit.num_classes, jnp.int32)
    eps_fn_c = cache_apply.make_cached_eps_fn(
        params, cfg, cond, null, g, None, None, split,
        attn_backend="dense")
    ts = sch.respaced_timesteps(TRAIN_T, 8)
    refresh = jnp.asarray([i % 2 == 0 for i in range(len(ts))])
    x0 = jax.random.normal(jax.random.PRNGKey(3),
                           (B,) + cfg.dit.latent_shape)
    delta0 = jnp.zeros(cache_apply.delta_shape(cfg, 0, B, True))
    key = jax.random.PRNGKey(4)
    _x, tap = cache_apply.cached_ddim_phase(
        eps_fn_c, sched, x0, ts, refresh, key, delta0, taps=True)
    tap_drift = np.asarray(tap["drift"])            # [T, 2B]

    # eager recomputation: same loop, step by step on the host
    ts_prev = np.concatenate([ts[1:], [-1]])
    x, delta = x0, delta0
    eager = []
    for i, (t, tp) in enumerate(zip(ts, ts_prev)):
        tb = jnp.full((B,), int(t), jnp.int32)
        tpb = jnp.full((B,), int(tp), jnp.int32)
        eps, _lv, nd = eps_fn_c(x, tb, delta, refresh[i])
        d = np.asarray(nd - delta)
        eager.append(np.sqrt(np.mean(np.square(d),
                                     axis=tuple(range(1, d.ndim)))))
        x = sch.ddim_step(sched, x, eps, tb, tpb, 0.0, key)
        delta = nd
    eager = np.stack(eager)
    drift_err = float(np.max(np.abs(tap_drift - eager)))
    drift_refresh_mean = float(eager[np.asarray(refresh)].mean())
    skip_max = float(np.max(np.abs(tap_drift[~np.asarray(refresh)])))
    assert drift_refresh_mean > 0, \
        "trained-like weights should produce nonzero refresh drift"
    C.csv_row("telemetry_drift", 0.0,
              f"tap_vs_eager_max_err={drift_err:.2e};"
              f"refresh_drift_mean={drift_refresh_mean:.4f};"
              f"skip_drift_max={skip_max:.2e}")

    # ------------------------------------------------------------------
    # Gate 1: zero added recompiles, latents independent of telemetry

    plans = {}
    for b in (0.4, 0.7, 1.0):
        plan = SamplingPlan(T=T, budget=b, guidance_scale=1.5,
                            attn_backend="dense")
        plan.validate(cfg)
        plans[b] = plan
    levels = sorted(plans)
    rng = np.random.default_rng(0)
    reqs = [(int(rng.integers(0, cfg.dit.num_classes)),
             levels[int(rng.integers(0, len(levels)))])
            for _ in range(N_REQ)]
    menu = BucketMenu(cfg, (0, 1), MAX_TOKENS, guided=True)

    def drain(telemetry=None):
        engine = ServingEngine(pipe, plans, max_tokens_per_step=MAX_TOKENS,
                               menu=menu, cache=cache, telemetry=telemetry)
        for i, (label, lvl) in enumerate(reqs):
            engine.submit(cond=label, budget=lvl,
                          key=jax.random.fold_in(jax.random.PRNGKey(7), i))
        results = engine.run()
        jax.block_until_ready(results[-1].x0)
        return results

    drain()                                        # warm the untapped family
    warm_off = pipe.cache_stats()["compiled"]
    drain(Telemetry(taps=True))                    # warm the tapped family
    warm_on = pipe.cache_stats()["compiled"]
    tapped_family_compiles = warm_on - warm_off

    tel = Telemetry(taps=True)
    res_off, res_on = drain(), drain(tel)          # replay both families
    recompiles = pipe.cache_stats()["compiled"] - warm_on
    assert recompiles == 0, \
        f"{recompiles} recompiles during telemetry on/off replay"
    # latents must not depend on whether anyone was watching
    a = {r.request.id: np.asarray(r.x0) for r in res_off}
    b = {r.request.id: np.asarray(r.x0) for r in res_on}
    assert all(np.array_equal(a[i], b[i]) for i in a), \
        "telemetry changed the served latents"

    agg = tel.taps.aggregate()
    n_spans = tel.recorder.events_recorded
    C.csv_row("telemetry_replay", 0.0,
              f"recompiles_after_warmup={recompiles};"
              f"tapped_family_compiles={tapped_family_compiles};"
              f"span_events={n_spans};"
              f"tap_request_steps={agg['request_steps']}")

    bench = {
        "name": "telemetry", "arch": "dit-xl-2:reduced+4L128d",
        "T": T, "requests": N_REQ, "levels": levels,
        "drift": {"tap_vs_eager_max_err": drift_err,
                  "refresh_drift_mean": drift_refresh_mean,
                  "skip_drift_max": skip_max},
        "recompiles_after_warmup": recompiles,
        "tapped_family_compiles": tapped_family_compiles,
        "spans": {"events_recorded": n_spans,
                  "events_dropped": tel.recorder.events_dropped},
        "taps": agg,
    }
    print("BENCH " + json.dumps(bench))
    check_baseline("telemetry", bench)


if __name__ == "__main__":
    bench_telemetry()
