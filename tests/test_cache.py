"""Cross-step activation cache (DESIGN.md §cache).

The load-bearing asserts: interval=1 (refresh every step) is
BIT-IDENTICAL to uncached sampling for ddim AND ddpm on both the plain
pipeline and the packed engine path (each against its own uncached run); interval k>1 drifts boundedly;
policy switches on a warm runner never recompile; and engine cache
slots are released on retire and reused across join/leave.
"""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import (CacheSpec, CacheStore, cache_savings,
                         cached_nfe_flops, conditioning_drift, delta_bytes,
                         ladder_refresh_mask, refresh_intervals, refresh_mask)
from repro.cache.ledger import deep_block_flops
from repro.core import flexify
from repro.core.scheduler import (FlexiSchedule, dit_block_flops,
                                  dit_nfe_flops)
from repro.diffusion import schedule as sch
from repro.models import dit as dit_mod
from repro.pipeline import FlexiPipeline, SamplingPlan
from repro.serving import BudgetController, ServingEngine, request_cost_flops

pytestmark = pytest.mark.tier1

T = 6


@pytest.fixture(scope="module")
def flexi(tiny_dit_cfg, trained_like_dit):
    fparams, fcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)])
    return fparams, fcfg, sch.linear_schedule(100)


@pytest.fixture(scope="module")
def pipe(flexi):
    fparams, fcfg, sched = flexi
    return FlexiPipeline(fparams, fcfg, sched)


def make_plans(solver="ddim", cache=None):
    return {0.6: SamplingPlan(T=T, budget=FlexiSchedule.weak_first(T, 3),
                              solver=solver, guidance_scale=1.5, cache=cache),
            1.0: SamplingPlan(T=T, budget=1.0, solver=solver,
                              guidance_scale=1.5, cache=cache)}


# ---------------------------------------------------------------------------
# Policies (host-only)


def test_cache_spec_validation():
    with pytest.raises(ValueError, match="policy"):
        CacheSpec(policy="lru")
    with pytest.raises(ValueError, match="interval"):
        CacheSpec(interval=0)
    with pytest.raises(ValueError, match="threshold"):
        CacheSpec(threshold=0.0)
    with pytest.raises(ValueError, match="bands"):
        CacheSpec(policy="banded", bands=((5, 0),))
    assert CacheSpec(policy="interval", interval=1).exact
    assert not CacheSpec(policy="interval", interval=2).exact
    assert not CacheSpec(policy="proxy").exact
    # split=0 resolves to L//4 (min 1) and must leave a deep block
    assert CacheSpec().resolve_split(8) == 2
    assert CacheSpec().resolve_split(2) == 1
    with pytest.raises(ValueError, match="deep block"):
        CacheSpec(split=4).resolve_split(4)


def test_refresh_mask_interval_and_banded():
    ts = np.linspace(99, 0, 8).round().astype(np.int64)
    m1 = refresh_mask(CacheSpec(policy="interval", interval=1), ts)
    assert m1.all()
    m3 = refresh_mask(CacheSpec(policy="interval", interval=3), ts)
    np.testing.assert_array_equal(
        m3, [True, False, False, True, False, False, True, False])
    # banded: refresh every step while t >= 50, every 4 below
    mb = refresh_mask(CacheSpec(policy="banded", bands=((50, 1),),
                                interval=4), ts)
    assert mb[:4].all()                      # ts 99..57 band at interval 1
    assert list(mb[4:]) == [False, False, False, True]
    assert refresh_intervals(m3) == [3, 3]
    assert refresh_mask(CacheSpec(), np.zeros(0, np.int64)).shape == (0,)


def test_refresh_mask_proxy_monotone_in_threshold():
    ts = np.linspace(999, 0, 20).round().astype(np.int64)
    loose = refresh_mask(CacheSpec(policy="proxy", threshold=0.5), ts)
    tight = refresh_mask(CacheSpec(policy="proxy", threshold=0.01), ts)
    assert loose[0] and tight[0]
    assert tight.sum() >= loose.sum()        # tighter drift → more refreshes
    assert 0 < loose.sum() < len(ts)         # neither degenerate
    # drift is 0 at zero gap and grows with the gap
    assert conditioning_drift([50], [50])[0] == pytest.approx(0.0, abs=1e-12)
    assert conditioning_drift([80], [50])[0] > \
        conditioning_drift([55], [50])[0] > 0


def test_ladder_mask_resets_per_phase():
    fs = FlexiSchedule.weak_first(T, 3)
    ts = sch.respaced_timesteps(100, T)
    mask = ladder_refresh_mask(CacheSpec(policy="interval", interval=4),
                               fs.split_timesteps(ts))
    # phase boundaries force a refresh: step 0 AND step 3 (mode switch)
    np.testing.assert_array_equal(mask, [True, False, False,
                                         True, False, False])


# ---------------------------------------------------------------------------
# Ledger


def test_cached_flops_ledger(flexi):
    _, fcfg, _ = flexi
    L = fcfg.num_layers
    full = dit_nfe_flops(fcfg, 0)
    skip = cached_nfe_flops(fcfg, 0, split=1, refresh=False)
    assert cached_nfe_flops(fcfg, 0, split=1, refresh=True) == full
    # the skipped deep share is exactly (L - split)/L of the block FLOPs
    N0 = dit_mod.tokens_for_mode(fcfg, 0)
    assert full - skip == pytest.approx(
        dit_block_flops(fcfg, N0) * (L - 1) / L)
    assert deep_block_flops(fcfg, 0, 1) == pytest.approx(full - skip)
    # a full-T exact run saves nothing; interval 2 saves something
    fs = FlexiSchedule(((0, T),))
    ts = sch.respaced_timesteps(100, T)
    exact = cache_savings(fcfg, fs, ts, CacheSpec(policy="interval",
                                                  interval=1, split=1))
    assert exact["flops_saved_frac"] == 0.0
    k2 = cache_savings(fcfg, fs, ts, CacheSpec(policy="interval",
                                               interval=2, split=1))
    assert 0.0 < k2["flops_saved_frac"] < 1.0
    assert k2["refresh_rate"] == pytest.approx(0.5)
    assert delta_bytes(fcfg, 0, guided=True) == \
        2 * dit_mod.tokens_for_mode(fcfg, 0) * fcfg.d_model * 4


def test_plan_cached_flops_and_controller_pricing(flexi):
    _, fcfg, _ = flexi
    spec = CacheSpec(policy="interval", interval=2, split=1)
    plans = make_plans()
    plan = plans[1.0]
    assert plan.cached_flops(fcfg) == plan.flops(fcfg)     # no cache: same
    cost_plain = request_cost_flops(fcfg, plan)
    cost_cached = request_cost_flops(fcfg, plan, cache=spec)
    assert cost_cached < cost_plain
    # the controller prices cache-adjusted costs into the budget solve:
    # capacity that only sustains 0.6 uncached sustains 1.0 with caching
    lam, cap = 4.0, 4.0 * request_cost_flops(fcfg, plans[0.6])
    for cache in (None, spec):
        ctl = BudgetController(fcfg, plans, target_util=1.0, alpha=1.0,
                               cache=cache)
        ctl.observe_service(flops=cap, dt=1.0)
        for i in range(5):
            ctl.observe_arrival(i / lam)
        if cache is None:
            assert ctl.solve() == 0.6
        else:
            assert ctl.costs[1.0] < ctl.costs[0.6] * 1.7   # savings priced
    plan_c = SamplingPlan(T=T, budget=1.0, cache=spec)
    assert plan_c.cached_flops(fcfg) < plan_c.flops(fcfg)


def test_cache_plan_validation():
    with pytest.raises(ValueError, match="solvers"):
        SamplingPlan(T=T, budget=1.0, solver="dpm2", cache=CacheSpec())
    with pytest.raises(ValueError, match="weak_cond|vanilla"):
        SamplingPlan(T=T, budget=1.0, guidance_kind="weak_cond",
                     cache=CacheSpec())
    from repro.pipeline import AdaptiveBudget
    with pytest.raises(ValueError, match="static"):
        SamplingPlan(T=T, budget=AdaptiveBudget(), cache=CacheSpec())


# ---------------------------------------------------------------------------
# Store


def test_cache_store_slots_and_eviction(flexi):
    _, fcfg, _ = flexi
    store = CacheStore(fcfg, (0, 1), n_slots=2, guided=True)
    s0 = store.alloc(0, owner=10)
    s1 = store.alloc(0, owner=11)
    assert {s0, s1} == {0, 1} and store.n_active == 2
    assert store.bytes_resident == 2 * delta_bytes(fcfg, 0)
    # pool exhausted → LRU eviction: oldest owner loses its slot
    store.touch(0, s1)
    s2 = store.alloc(0, owner=12)
    assert s2 == s0 and store.owner_of(0, s0) == 12
    assert store.evictions == 1
    # release → freed slot is reused (join/leave recycling)
    store.release(0, s1)
    assert store.owner_of(0, s1) is None
    assert store.alloc(1, owner=13) in (0, 1)   # per-mode pools independent
    assert store.n_active == 2                  # mode-0 s2 + the mode-1 slot
    # gather/scatter round-trip
    vals = jnp.ones((1, 2, dit_mod.tokens_for_mode(fcfg, 0),
                     fcfg.d_model))
    store.scatter(0, [s2], vals)
    np.testing.assert_array_equal(np.asarray(store.gather(0, [s2])),
                                  np.asarray(vals))
    assert store.bytes_total == 2 * (delta_bytes(fcfg, 0)
                                     + delta_bytes(fcfg, 1))


# ---------------------------------------------------------------------------
# Plain pipeline path: exactness, drift, zero-recompile policy switches


@pytest.mark.parametrize("solver", ["ddim", "ddpm"])
def test_interval1_bit_identical_plain(pipe, solver):
    key = jax.random.PRNGKey(7)
    cond = jnp.asarray([3, 8], jnp.int32)
    plan = SamplingPlan(T=T, budget=FlexiSchedule.weak_first(T, 3),
                        solver=solver, guidance_scale=1.5)
    exact = CacheSpec(policy="interval", interval=1, split=1)
    ref = pipe.sample(plan, 2, key, cond=cond).x0
    got = pipe.sample(SamplingPlan(T=T,
                                   budget=FlexiSchedule.weak_first(T, 3),
                                   solver=solver, guidance_scale=1.5,
                                   cache=exact), 2, key, cond=cond).x0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("solver", ["ddim", "ddpm"])
def test_interval_k_bounded_drift(pipe, flexi, solver):
    _, fcfg, _ = flexi
    key = jax.random.PRNGKey(11)
    cond = jnp.asarray([1, 4], jnp.int32)
    plan = SamplingPlan(T=T, budget=1.0, solver=solver, guidance_scale=1.5)
    ref = pipe.sample(plan, 2, key, cond=cond).x0
    spec = CacheSpec(policy="interval", interval=2, split=1)
    res = pipe.sample(SamplingPlan(T=T, budget=1.0, solver=solver,
                                   guidance_scale=1.5, cache=spec),
                      2, key, cond=cond)
    rel = float(jnp.mean((res.x0 - ref) ** 2) / jnp.mean(ref ** 2))
    assert 0.0 < rel < 0.25, rel            # stale but bounded
    # the ledger prices the skipped deep blocks into the result
    assert res.flops < plan.flops(fcfg, batch=2)
    assert res.trace["cache_refreshes"] < res.trace["cache_steps"]


def test_policy_switch_never_recompiles(pipe):
    key = jax.random.PRNGKey(3)
    cond = jnp.asarray([2], jnp.int32)

    def run(spec):
        return pipe.sample(SamplingPlan(T=T, budget=1.0, solver="ddim",
                                        guidance_scale=1.5, cache=spec),
                           1, key, cond=cond).x0
    run(CacheSpec(policy="interval", interval=2, split=1))
    warm = pipe.cache_stats()
    # interval change, banded, proxy threshold sweep: all the same runner
    for spec in (CacheSpec(policy="interval", interval=3, split=1),
                 CacheSpec(policy="banded", bands=((50, 1),), interval=4,
                           split=1),
                 CacheSpec(policy="proxy", threshold=0.02, split=1),
                 CacheSpec(policy="proxy", threshold=0.3, split=1)):
        run(spec)
    after = pipe.cache_stats()
    assert after["compiled"] == warm["compiled"]
    assert after["misses"] == warm["misses"]


# ---------------------------------------------------------------------------
# Packed engine path: parity, slot lifecycle, metrics


def _reference(pipe, plans, level, label, key):
    # the engine's packed steps run the segment-aware Pallas kernel
    # ('auto' resolves to it on packed token streams); bit-exactness is a
    # within-backend guarantee, so the per-request reference samples at
    # the same backend (cross-backend ≤1e-4 parity lives in test_serving
    # / test_attention_backend)
    plan = dataclasses.replace(plans[level], attn_backend="pallas")
    return np.asarray(pipe.sample(plan, 1, key,
                                  cond=jnp.asarray([label], jnp.int32)).x0[0])


def _serve_join_leave(pipe, solver, cache):
    """Three requests, a fourth joining two steps in; returns
    ``{id: (x0, level, label, key)}`` and the engine."""
    plans = make_plans(solver)
    eng = ServingEngine(pipe, plans, max_tokens_per_step=256, cache=cache)
    spec = [(0, 0.6, 3), (1, 1.0, 7), (2, 0.6, 5)]
    keys = {rid: jax.random.PRNGKey(60 + rid) for rid, _, _ in spec}
    for rid, lvl, label in spec:
        eng.submit(cond=label, budget=lvl, key=keys[rid])
    results = []
    for _ in range(2):
        results += eng.step()
    late = eng.submit(cond=9, budget=1.0, key=jax.random.PRNGKey(99))
    spec.append((late, 1.0, 9))
    keys[late] = jax.random.PRNGKey(99)
    results += eng.run()
    assert len(results) == 4
    out = {}
    for r in results:
        _, lvl, label = next(s for s in spec if s[0] == r.request.id)
        out[r.request.id] = (np.asarray(r.x0), lvl, label,
                             keys[r.request.id])
    return out, eng


@pytest.mark.parametrize("solver", ["ddim", "ddpm"])
def test_engine_interval1_bit_identical_packed(pipe, solver):
    """Packed cached dispatches at interval=1 reproduce the UNCACHED
    packed engine bit-for-bit — requests join and leave mid-flight, so
    slots churn while exactness holds — and match the per-request
    pipeline to the cross-packing tolerance. (Packing changes the batch
    of some matmuls, and XLA's CPU backend rounds a row of a batch-1
    product differently from the same row in a larger batch, so the
    packed engine and the per-request pipeline agree to ~1e-6, not
    bitwise; the cache adds no difference of its own.)"""
    plans = make_plans(solver)
    cached, eng = _serve_join_leave(
        pipe, solver, CacheSpec(policy="interval", interval=1, split=1))
    uncached, _ = _serve_join_leave(pipe, solver, None)
    assert cached.keys() == uncached.keys()
    for rid, (x0, lvl, label, key) in cached.items():
        np.testing.assert_array_equal(x0, uncached[rid][0])
        ref = _reference(pipe, plans, lvl, label, key)
        np.testing.assert_allclose(x0, ref, atol=1e-5, rtol=1e-5)
    assert eng.store.n_active == 0          # every slot released on retire


def test_engine_cache_drift_and_slot_reuse(pipe, flexi):
    _, fcfg, _ = flexi
    plans = make_plans("ddim")
    eng = ServingEngine(pipe, plans, max_tokens_per_step=256,
                        cache=CacheSpec(policy="interval", interval=2,
                                        split=1))
    key = jax.random.PRNGKey(5)
    eng.submit(cond=4, budget=1.0, key=key)
    (r1,) = eng.run()
    used = [(m, s) for m in eng.store.modes
            for s in range(eng.store.n_slots)
            if eng.store.owner_of(m, s) is not None]
    assert not used                          # released at retire
    ref = _reference(pipe, plans, 1.0, 4, key)
    rel = float(np.mean((np.asarray(r1.x0) - ref) ** 2) / np.mean(ref ** 2))
    assert 0.0 < rel < 0.25
    # join/leave slot recycling: the next request claims the same slot id
    eng.submit(cond=2, budget=1.0, key=jax.random.PRNGKey(6))
    eng.step()
    active = [(m, s) for m in eng.store.modes
              for s in range(eng.store.n_slots)
              if eng.store.owner_of(m, s) is not None]
    assert len(active) == 1 and active[0][1] == 0   # slot 0 reused
    eng.run()
    assert eng.store.n_active == 0
    # ledger: hits recorded, histogram populated, bytes gauge settled at 0
    cs = eng.metrics.cache_summary()
    assert cs["enabled"] and 0.0 < cs["hit_rate"] < 1.0
    assert cs["refresh_interval_hist"]
    assert eng.metrics.cache_bytes_resident == 0
    assert eng.metrics.summary()["cache_hit_rate"] == cs["hit_rate"]
    assert eng.store.bytes_total > 0


def test_precapture_warm_set(pipe):
    plans = make_plans("ddim")
    eng = ServingEngine(pipe, plans, max_tokens_per_step=256,
                        steps_per_dispatch=4)
    n = eng.precapture_warm_set(max_per_mode=1)
    # every small layout is now warm at every power-of-two depth
    for layout in eng.menu.layouts:
        if all(c <= 1 for _m, c in layout.groups):
            for k in (1, 2, 4):
                assert eng._is_warm(layout, k)
    assert eng.precapture_warm_set(max_per_mode=1) == 0   # idempotent
    assert n >= 0
