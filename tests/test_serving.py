"""Continuous-batching serving engine (DESIGN.md §serving).

Deterministic simulated-clock tests: no wall time anywhere — the engine,
queue, controller, and metrics all read the injected clock. The heavy
asserts: a packed mixed-budget engine step is bit-compatible (≤1e-4;
observed exactly 0) with per-request ``FlexiPipeline.sample``, join/leave
happen mid-flight without draining, EDF reorders under contention, and
the SLA controller degrades budgets under load.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flexify
from repro.core.packing import (assign_rows, mixed_pack_cost, pack_ratio,
                                packed_row_flops)
from repro.core.scheduler import FlexiSchedule, dit_nfe_flops
from repro.diffusion import schedule as sch
from repro.models import dit as dit_mod
from repro.pipeline import FlexiPipeline, PackLayout, SamplingPlan
from repro.serving import (BucketMenu, BudgetController, Request,
                           RequestQueue, ServingEngine, count_chain,
                           request_cost_flops)

pytestmark = pytest.mark.tier1

T = 6


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


@pytest.fixture(scope="module")
def flexi(tiny_dit_cfg, trained_like_dit):
    fparams, fcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)])
    return fparams, fcfg, sch.linear_schedule(100)


@pytest.fixture(scope="module")
def pipe(flexi):
    fparams, fcfg, sched = flexi
    return FlexiPipeline(fparams, fcfg, sched)


def make_plans(solver="ddim"):
    return {0.6: SamplingPlan(T=T, budget=FlexiSchedule.weak_first(T, 3),
                              solver=solver, guidance_scale=1.5),
            1.0: SamplingPlan(T=T, budget=1.0, solver=solver,
                              guidance_scale=1.5)}


# ---------------------------------------------------------------------------
# Host-only: row assembly, bucket menu, queue, controller


def test_assign_rows_first_fit():
    # full segments own a row; weak ones pack r-per-row; no row overflows
    rows = assign_rows([64, 16, 16, 16, 16, 64], capacity=64)
    assert sorted(len(r) for r in rows) == [1, 1, 4]
    for row in rows:
        assert sum([64, 16, 16, 16, 16, 64][i] for i in row) <= 64
    # a leftover weak segment opens a fresh (padded) row
    assert len(assign_rows([16] * 5, capacity=64)) == 2
    with pytest.raises(ValueError, match="capacity"):
        assign_rows([65], capacity=64)


def test_count_chain():
    assert count_chain(0) == ()
    assert count_chain(1) == (1,)
    assert count_chain(6) == (1, 2, 3, 4, 6)
    assert count_chain(16) == (1, 2, 3, 4, 6, 9, 13, 16)


def test_bucket_menu_choose(flexi):
    _, fcfg, _ = flexi
    menu = BucketMenu(fcfg, (0, 1), max_tokens_per_step=256, guided=True)
    # every layout respects the token budget
    for layout in menu.layouts:
        assert layout.cost(fcfg).packed_tokens <= 256
    # pure-full demand → the biggest full bucket (2 requests = 4 CFG rows)
    l = menu.choose({0: 5})
    assert l.capacity_for(0) == 2 and l.capacity_for(1) == 0
    # mixed demand is served mixed
    l = menu.choose({0: 1, 1: 2})
    assert l.capacity_for(0) >= 1 and l.capacity_for(1) >= 2
    # tiny demand picks a tight bucket, not the biggest one
    l = menu.choose({1: 1})
    assert l.capacity_for(1) == 1 and l.n_requests == 1
    assert menu.choose({}) is None
    with pytest.raises(ValueError, match="not in the bucket menu"):
        menu.choose({3: 1})
    with pytest.raises(ValueError, match="below one row"):
        BucketMenu(fcfg, (0, 1), max_tokens_per_step=32, guided=True)


def test_request_queue_policies():
    q = RequestQueue()
    q.submit(Request(id=0, cond=0, budget=1.0, deadline=5.0), now=0.0)
    q.submit(Request(id=1, cond=0, budget=1.0, deadline=1.0), now=0.1)
    q.submit(Request(id=2, cond=0, budget=1.0, deadline=3.0), now=0.2)
    assert q.pop("fifo").id == 0
    assert q.pop("edf").id == 1          # earliest deadline, not arrival
    assert q.pop("edf").id == 2
    with pytest.raises(IndexError):
        q.pop("fifo")
    q.submit(Request(id=3, cond=0, budget=1.0), now=0.3)
    with pytest.raises(ValueError, match="policy"):
        q.pop("sjf")


def test_controller_solves_highest_sustainable_budget(flexi):
    _, fcfg, _ = flexi
    plans = make_plans()
    ctl = BudgetController(fcfg, plans, target_util=1.0, alpha=1.0)
    f_hi = request_cost_flops(fcfg, plans[1.0])
    f_lo = request_cost_flops(fcfg, plans[0.6])
    assert f_lo < f_hi
    # no estimates yet → no evidence of pressure → highest level
    assert ctl.solve() == 1.0
    # capacity for exactly 2 full-budget requests/s, arrivals at 1/s
    ctl.observe_service(flops=2 * f_hi, dt=1.0)
    ctl.observe_arrival(0.0)
    ctl.observe_arrival(1.0)
    assert ctl.solve() == 1.0
    # arrivals speed up to 4/s: only the weak level fits 2*f_hi/4 per req
    for t in (1.25, 1.5, 1.75):
        ctl.observe_arrival(t)
    assert ctl.arrival_rate == pytest.approx(4.0)
    assert ctl.solve() == 0.6
    assert ctl.assign(1.0) == 0.6        # demoted
    assert ctl.assign(0.6) == 0.6        # never promoted
    # load drops again → back to full quality
    ctl.observe_arrival(101.75)
    assert ctl.solve() == 1.0
    assert ctl.assign(1.0) == 1.0


def test_request_cost_flops_counts_parallel_padding(flexi):
    """The ledger charges sequence-parallel pad-to-divisible waste
    (distributed.partition) on top of the plan's analytic FLOPs."""
    _, fcfg, _ = flexi
    plan = SamplingPlan(T=T, budget=FlexiSchedule.weak_first(T, 3),
                        guidance_scale=1.5)
    base = request_cost_flops(fcfg, plan, sp=1)
    # priced at the plan's own backend: the flash kernel pads a row
    # shorter than one 128-token tile up to the tile, so it is not the
    # dense-N² convention on this small model
    assert base == pytest.approx(plan.flops(fcfg,
                                            attn_backend=plan.attn_backend))
    padded = request_cost_flops(fcfg, plan, sp=3)   # 64 % 3 != 0 → padding
    assert padded > base


# ---------------------------------------------------------------------------
# Packed-cost accounting (satellite: conditioning-token overhead)


def test_packed_row_flops_conditioning_overhead(flexi):
    _, fcfg, _ = flexi
    N0 = dit_mod.tokens_for_mode(fcfg, 0)
    d, L = fcfg.d_model, fcfg.num_layers
    r = pack_ratio(fcfg, 1)
    row = packed_row_flops(fcfg, [1] * r, capacity=N0)
    # every packed segment carries its own adaLN conditioning where the
    # plain NFE pays for one sample: that exact delta is in the ledger
    ada_overhead = (r - 1) * (L * 2 * d * 6 * d + 2 * d * 2 * d)
    seg_embed = sum(2 * dit_mod.tokens_for_mode(fcfg, 1) * 16
                    * (4 * d + d * dit_mod.c_out_dim(fcfg))
                    for _ in range(r))       # npix=16 for the (1,4,4) mode
    plain_embed = (2 * N0 * 4 * 4 * d
                   + 2 * N0 * d * 4 * dit_mod.c_out_dim(fcfg))
    assert row == pytest.approx(dit_nfe_flops(fcfg, 0) + ada_overhead
                                + seg_embed - plain_embed)
    with pytest.raises(ValueError, match="exceed"):
        packed_row_flops(fcfg, [1] * (r + 1), capacity=N0)


def test_mixed_pack_cost(flexi):
    _, fcfg, _ = flexi
    # one full + four weak segments fill exactly two rows, zero waste
    c = mixed_pack_cost(fcfg, [0, 1, 1, 1, 1])
    assert c.rows == 2 and c.efficiency == 1.0
    # one full + one weak: the weak row is 3/4 padding
    c2 = mixed_pack_cost(fcfg, [0, 1])
    assert c2.rows == 2
    assert c2.efficiency == pytest.approx((64 + 16) / 128)
    assert c2.flops < c.flops


# ---------------------------------------------------------------------------
# The engine: bit-exactness, join/leave, EDF, degradation


def _reference(pipe, plans, level, label, key):
    return np.asarray(pipe.sample(plans[level], 1, key,
                                  cond=jnp.asarray([label], jnp.int32)).x0[0])


@pytest.mark.parametrize("solver", ["ddim", "ddpm"])
def test_engine_matches_per_request_sampling(pipe, flexi, solver):
    """A packed mixed-budget engine step — requests at different denoise
    steps, budgets, and modes in ONE forward — reproduces each request's
    standalone FlexiPipeline.sample output (acceptance: ≤1e-4), with
    requests joining and leaving mid-flight and zero recompiles when the
    same workload shape replays."""
    plans = make_plans(solver)
    clk = FakeClock()
    eng = ServingEngine(pipe, plans, max_tokens_per_step=256,
                        policy="fifo", clock=clk)
    spec = [(0, 0.6, 3), (1, 1.0, 7), (2, 0.6, 5)]
    keys = {rid: jax.random.PRNGKey(40 + rid) for rid, _, _ in spec}
    for rid, lvl, label in spec:
        eng.submit(cond=label, budget=lvl, key=keys[rid])
        clk.advance(0.01)
    # two steps in, a late request JOINS while the others are mid-flight
    results = []
    for _ in range(2):
        results += eng.step()
        clk.advance(0.01)
    late = eng.submit(cond=9, budget=1.0, key=jax.random.PRNGKey(99))
    spec.append((late, 1.0, 9))
    keys[late] = jax.random.PRNGKey(99)
    results += eng.run()
    assert len(results) == 4
    # the early requests LEFT before the late one finished (no drain)
    order = [r.request.id for r in results]
    assert order.index(late) == len(order) - 1
    assert set(order) == {0, 1, 2, late}
    for r in results:
        _, lvl, label = next(s for s in spec if s[0] == r.request.id)
        ref = _reference(pipe, plans, lvl, label, keys[r.request.id])
        np.testing.assert_allclose(np.asarray(r.x0), ref, atol=1e-4,
                                   rtol=1e-4)
    # replaying the same workload shape is compile-free (bucket warmup)
    warm = eng.cache_stats()
    for rid, lvl, label in spec[:3]:
        eng.submit(cond=label, budget=lvl, key=keys[rid])
        clk.advance(0.01)
    for _ in range(2):
        eng.step()
        clk.advance(0.01)
    eng.submit(cond=9, budget=1.0, key=keys[late])
    eng.run()
    after = eng.cache_stats()
    assert after["compiled"] == warm["compiled"]
    assert after["misses"] == warm["misses"]
    # simulated clock → deterministic latency metrics
    assert eng.metrics.summary()["served"] == 8.0
    assert math.isfinite(eng.metrics.latency_percentiles()["p99"])


def test_edf_orders_by_deadline_under_contention(pipe):
    """With capacity for one full request per step, EDF serves the later
    arrival with the earlier deadline first; FIFO does not."""
    plans = {1.0: SamplingPlan(T=T, budget=1.0, guidance_scale=1.5)}
    finish_order = {}
    for policy in ("fifo", "edf"):
        clk = FakeClock()
        eng = ServingEngine(pipe, plans, max_tokens_per_step=128,
                            policy=policy, clock=clk)
        eng.submit(cond=1, budget=1.0, deadline=100.0)   # early arrival
        clk.advance(0.01)
        eng.submit(cond=2, budget=1.0, deadline=1.0)     # urgent latecomer
        results = []
        while not eng.idle:
            results += eng.step()
            clk.advance(0.01)
        finish_order[policy] = [r.request.id for r in results]
    assert finish_order["fifo"] == [0, 1]
    assert finish_order["edf"] == [1, 0]


def test_degrade_demotes_under_load_and_recovers(pipe, flexi):
    _, fcfg, _ = flexi
    plans = make_plans()
    ctl = BudgetController(fcfg, plans, target_util=1.0, alpha=1.0)
    clk = FakeClock()
    eng = ServingEngine(pipe, plans, max_tokens_per_step=256,
                        policy="degrade", clock=clk, controller=ctl)
    # teach the controller: capacity = 2 full requests/s, arrivals 8/s
    ctl.observe_service(flops=2 * request_cost_flops(fcfg, plans[1.0]),
                        dt=1.0)
    for i in range(8):
        eng.submit(cond=i % 10, budget=1.0)
        clk.advance(0.125)
    overloaded = eng.run()
    assert all(r.budget_served == 0.6 for r in overloaded)
    assert all(r.record.degraded for r in overloaded)
    assert eng.metrics.summary()["degraded"] == 8.0
    # load drops: next request arrives after a long gap → full quality
    clk.advance(50.0)
    eng.submit(cond=3, budget=1.0)
    relaxed = eng.run()
    assert [r.budget_served for r in relaxed] == [1.0]
    # degraded requests still sample correctly — at the weaker plan
    plans_ref = make_plans()
    r0 = overloaded[0]
    ref = _reference(pipe, plans_ref, 0.6, r0.request.cond, r0.request.key)
    np.testing.assert_allclose(np.asarray(r0.x0), ref, atol=1e-4, rtol=1e-4)


def test_engine_menu_validation(pipe, flexi):
    _, fcfg, _ = flexi
    with pytest.raises(ValueError, match="non-empty"):
        ServingEngine(pipe, {})
    with pytest.raises(ValueError, match="adaptive"):
        from repro.pipeline import AdaptiveBudget
        ServingEngine(pipe, {1.0: SamplingPlan(T=T, budget=AdaptiveBudget())})
    with pytest.raises(ValueError, match="share solver"):
        ServingEngine(pipe, {0.6: SamplingPlan(T=T, budget=0.6,
                                               solver="ddim"),
                             1.0: SamplingPlan(T=T, budget=1.0,
                                               solver="ddpm")})
    with pytest.raises(ValueError, match="weak_cond"):
        ServingEngine(pipe, {0.6: SamplingPlan(
            T=T, budget=0.6, guidance_kind="weak_cond")})
    # requested budgets quantize UP to the menu (at least as powerful)
    eng = ServingEngine(pipe, make_plans(), max_tokens_per_step=256)
    assert eng.quantize(0.3) == 0.6
    assert eng.quantize(0.6) == 0.6
    assert eng.quantize(0.7) == 1.0
    assert eng.quantize(1.0) == 1.0


def test_packlayout_validation():
    with pytest.raises(ValueError, match="at least one"):
        PackLayout(groups=())
    with pytest.raises(ValueError, match="mode-sorted"):
        PackLayout(groups=((1, 2), (0, 1)))
    with pytest.raises(ValueError, match="counts"):
        PackLayout(groups=((0, 0),))
    layout = PackLayout.for_counts({1: 2, 0: 1})
    assert layout.groups == ((0, 1), (1, 2))
    assert layout.n_requests == 3
    assert layout.segment_modes() == (0, 0, 1, 1, 1, 1)
