"""Compiled-cost profiling, attribution, watchdog (DESIGN.md §profiling).

The load-bearing asserts: per-request attributed wall/FLOPs/bytes sum
EXACTLY (integer equality) to every dispatch's totals across mixed
budgets, cache refresh patterns, and join/leave mid-flight; the packed
cache-key mirror in telemetry/profile.py matches FlexiPipeline's real
runner cache; harvesting XLA cost analysis adds zero jit compiles and
profiling leaves latents and jaxpr fingerprints bit-identical; the
BudgetController reprices from measured calibration; the watchdog's
detectors fire (and cool down) on the right signals and the flight
recorder writes a complete bundle.
"""
import ast
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flexify
from repro.diffusion import schedule as sch
from repro.pipeline import FlexiPipeline, PackLayout, SamplingPlan
from repro.pipeline.plan import CacheSpec
from repro.serving import ServingEngine
from repro.serving.controller import (BudgetController, plan_mode_flops,
                                      request_cost_flops)
from repro.telemetry import Telemetry
from repro.telemetry import export as tel_export
from repro.telemetry.attribution import (AttributionLedger, ServedCost,
                                         exact_shares)
from repro.telemetry.profile import (CompiledCostRegistry, packed_arg_specs,
                                     packed_key)
from repro.telemetry.trace import SpanRecorder
from repro.telemetry.watchdog import (ALERT_DRIFT, ALERT_P99, ALERT_QUEUE,
                                      ALERT_RECOMPILE, Watchdog,
                                      WatchdogConfig)

pytestmark = pytest.mark.tier1

T = 6


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        self.t += 0.001
        return self.t


@pytest.fixture(scope="module")
def flexi(tiny_dit_cfg, trained_like_dit):
    fparams, fcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)])
    return fparams, fcfg, sch.linear_schedule(100)


@pytest.fixture(scope="module")
def pipe(flexi):
    fparams, fcfg, sched = flexi
    return FlexiPipeline(fparams, fcfg, sched)


def _plans():
    return {0.6: SamplingPlan(T=T, budget=0.5, guidance_scale=1.5),
            1.0: SamplingPlan(T=T, budget=1.0, guidance_scale=1.5)}


def _make_engine(pipe, telemetry=None, controller=None, policy="fifo"):
    return ServingEngine(pipe, _plans(), policy=policy,
                         steps_per_dispatch=2,
                         cache=CacheSpec(policy="interval", interval=2,
                                         split=1),
                         clock=FakeClock(), telemetry=telemetry,
                         controller=controller)


def _serve(engine, n=4):
    for i in range(n):
        engine.submit(cond=i % 10, budget=0.6 if i % 2 else 1.0)
    return {r.request.id: r for r in engine.run()}


# ---------------------------------------------------------------------------
# exact_shares: the conservation primitive


def test_exact_shares_sum_is_exact():
    rng = np.random.default_rng(0)
    for _ in range(50):
        n = int(rng.integers(1, 9))
        total = int(rng.integers(0, 10**12))
        weights = rng.random(n) * rng.choice([1e-6, 1.0, 1e9])
        shares = exact_shares(total, list(weights))
        assert sum(shares) == total
        assert all(s >= 0 for s in shares)


def test_exact_shares_degenerate_weights_split_equally():
    assert exact_shares(10, [0.0, 0.0]) == [5, 5]
    assert exact_shares(7, [0.0, 0.0, 0.0]) == [3, 2, 2]
    # negative weights clamp to zero, never to negative shares
    assert exact_shares(9, [-5.0, 3.0]) == [0, 9]
    assert exact_shares(0, [1.0, 2.0]) == [0, 0]
    assert exact_shares(5, []) == []


def test_exact_shares_proportional_when_divisible():
    assert exact_shares(4, [1.0, 3.0]) == [1, 3]
    assert exact_shares(100, [1.0, 1.0, 2.0]) == [25, 25, 50]


# ---------------------------------------------------------------------------
# AttributionLedger


def test_ledger_conservation_and_finalize():
    led = AttributionLedger()
    led.attribute_dispatch(time=0.0, label="d0", request_ids=[1, 2],
                           weights=[1.0, 2.0], wall_ns=1_000_001,
                           flops=999_999_999_999, bytes_=7)
    led.attribute_dispatch(time=1.0, label="d1", request_ids=[2, 3],
                           weights=[5.0, 1e-9], wall_ns=13, flops=17)
    assert all(v == 0 for v in led.conservation().values())
    assert all(d.conserved for d in led.dispatches)
    c2 = led.finalize(2, queue_wait_s=0.5, budget="0.6")
    assert c2.dispatches == 2 and c2.budget == "0.6"
    assert c2.queue_wait_s == 0.5
    # conservation holds across the open/finalized split
    assert all(v == 0 for v in led.conservation().values())
    led.finalize(1)
    led.finalize(3)
    total = sum(c.wall_ns for c in led.finalized.values())
    assert total == led.total_wall_ns == 1_000_001 + 13


def test_ledger_finalize_without_dispatch_is_zeros():
    led = AttributionLedger()
    c = led.finalize(42, queue_wait_s=1.0, budget="1.0")
    assert isinstance(c, ServedCost)
    assert (c.flops, c.bytes, c.wall_ns, c.dispatches) == (0, 0, 0, 0)
    # idempotent: a second finalize returns the same record
    assert led.finalize(42) is led.finalized[42]


# ---------------------------------------------------------------------------
# packed-key mirror + spec derivation + harvest


def test_packed_key_mirrors_runner_cache(pipe):
    layout = PackLayout(groups=((0, 1), (1, 1)), guided=True)
    kw = dict(solver="ddim", guidance_scale=1.5, clip_x0=0.0, k_steps=2,
              cache_split=1, attn_backend="auto", taps=False)
    pipe.packed_step(layout, **kw)
    mirror = packed_key(layout, **kw)
    assert mirror in pipe.runners(), \
        "telemetry/profile.py's packed_key drifted from " \
        "FlexiPipeline.packed_step's cache key"


def test_packed_arg_specs_lower_without_jit_compiles(pipe):
    engine = _make_engine(pipe)
    _serve(engine, n=2)
    before = pipe.cache_stats()["compiled"]
    n_packed = 0
    for key, fn in pipe.runners().items():
        if key[0] != "packed":
            continue
        n_packed += 1
        specs = packed_arg_specs(pipe.cfg, key, pipe.params)
        fn.lower(*specs)         # spec tree must match the real signature
    assert n_packed > 0
    assert pipe.cache_stats()["compiled"] == before


def test_registry_harvest_is_invisible_and_idempotent(pipe):
    tel = Telemetry(profile=True)
    engine = _make_engine(pipe, telemetry=tel)
    _serve(engine, n=3)
    before = pipe.cache_stats()["compiled"]
    hv = tel.profile.harvest(pipe)
    assert pipe.cache_stats()["compiled"] == before, \
        "AOT cost harvest touched the jit dispatch cache"
    assert hv["errors"] == 0 and hv["harvested"] > 0
    hv2 = tel.profile.harvest(pipe)          # already harvested: all noops
    assert hv2["harvested"] == 0 and hv2["errors"] == 0
    rep = tel.profile.reconcile()
    assert rep["n_errors"] == 0
    assert rep["n_records"] == hv["total"]
    assert 0 < rep["min_xla_over_analytic"]
    # engine fed per-dispatch walls under the same keys the harvest used
    packed_walls = [k for k in tel.profile.walls if k[0] == "packed"]
    assert packed_walls and all(k in tel.profile.records
                                for k in packed_walls)
    wall_rows = [r for r in rep["rows"] if "wall_ms_ewma" in r]
    assert wall_rows and all(r["wall_ms_ewma"] > 0 for r in wall_rows)


# ---------------------------------------------------------------------------
# Engine attribution: exact conservation across join/leave


def test_engine_attribution_conserves_with_join_leave(pipe):
    tel = Telemetry(profile=True)
    engine = _make_engine(pipe, telemetry=tel)
    for i in range(3):                       # first cohort, mixed budgets
        engine.submit(cond=i, budget=0.6 if i % 2 else 1.0)
    for _ in range(2):                       # advance partway...
        engine.step()
    engine.submit(cond=7, budget=1.0)        # ...then join mid-flight
    engine.submit(cond=8, budget=0.6)
    results = {r.request.id: r for r in engine.run()}
    assert len(results) == 5
    led = tel.attribution
    assert all(v == 0 for v in led.conservation().values()), \
        "attribution broke conservation"
    assert all(d.conserved for d in led.dispatches)
    assert len(led.finalized) == 5 and not led._open
    agg_wall = sum(c.wall_ns for c in led.finalized.values())
    agg_flops = sum(c.flops for c in led.finalized.values())
    assert agg_wall == led.total_wall_ns
    assert agg_flops == led.total_flops
    for rid, res in results.items():
        assert res.cost is not None
        assert res.cost.request_id == rid
        assert res.cost.dispatches > 0 and res.cost.flops > 0
        assert res.cost.budget == str(res.budget_served)
        assert res.cost.queue_wait_s >= 0
    # a full-budget request rides more denoise steps than a weak one at
    # the same ladder, so its attributed FLOPs must dominate
    full = [r.cost.flops for r in results.values() if r.budget_served == 1.0]
    weak = [r.cost.flops for r in results.values() if r.budget_served == 0.6]
    assert min(full) > max(weak)


def test_profiling_bit_identity_and_fingerprint(pipe):
    served_off = {i: np.asarray(r.x0)
                  for i, r in _serve(_make_engine(pipe)).items()}
    warm = pipe.cache_stats()["compiled"]
    tel = Telemetry(profile=True)
    tel.profile.harvest(pipe)                # harvest-then-serve ordering
    served_on = {i: np.asarray(r.x0)
                 for i, r in _serve(_make_engine(pipe, telemetry=tel)).items()}
    assert pipe.cache_stats()["compiled"] == warm, \
        "profiling replay recompiled a warm engine"
    for rid, x in served_off.items():
        assert np.array_equal(x, served_on[rid]), \
            "profiling changed the served latents"
    # jaxpr fingerprints: tracing a packed runner from its derived specs
    # yields the same jaxpr before and after a harvest
    from repro.analysis.jaxpr_audit import fingerprint
    key = next(k for k in pipe.runners() if k[0] == "packed")
    fn = pipe.runners()[key]
    specs = packed_arg_specs(pipe.cfg, key, pipe.params)
    fp1 = fingerprint(jax.make_jaxpr(fn)(*specs))
    tel2 = Telemetry(profile=True)
    tel2.profile.harvest(pipe)
    fp2 = fingerprint(jax.make_jaxpr(fn)(*specs))
    assert fp1 == fp2


# ---------------------------------------------------------------------------
# Controller: mode split + measured repricing


def test_plan_mode_flops_sums_to_request_cost(flexi):
    _p, fcfg, _s = flexi
    cache = CacheSpec(policy="interval", interval=2, split=1)
    for budget in (0.5, 1.0):
        for cs in (None, cache):
            plan = SamplingPlan(T=T, budget=budget, guidance_scale=1.5)
            split = plan_mode_flops(fcfg, plan, cache=cs,
                                    num_train_steps=100)
            total = request_cost_flops(fcfg, plan, cache=cs,
                                       num_train_steps=100)
            assert sum(split.values()) == pytest.approx(total)
    # the weak plan spends most steps in the cheap mode
    weak = plan_mode_flops(fcfg, SamplingPlan(T=T, budget=0.5,
                                              guidance_scale=1.5))
    assert len(weak) == 2 and min(weak) == 0


def test_controller_reprices_from_measured_calibration(flexi):
    _p, fcfg, _s = flexi
    ctrl = BudgetController(fcfg, _plans(), num_train_steps=100)
    assert ctrl.calibration is None
    assert ctrl.solve() == ctrl.solve_analytic()    # uncalibrated: legacy
    wpf = 1e-10                                     # measured wall/FLOP
    ctrl.observe_calibration(None, 1.0, wpf)
    cs = {b: ctrl.cost_seconds(b) for b in ctrl.levels}
    assert cs[1.0] > cs[0.6] > 0
    # seconds budget between the two measured costs; analytic capacity
    # believes a 4x faster device than measured
    mid = 0.5 * (cs[0.6] + cs[1.0])
    ctrl.observe_arrival(0.0)
    ctrl.observe_arrival(mid / ctrl.target_util)
    ctrl.observe_service(4.0 / wpf, 1.0)
    assert ctrl.solve_analytic() == 1.0             # analytic: sustain full
    assert ctrl.solve() == 0.6                      # measured: demote
    assert ctrl.assign(1.0) == 0.6


def test_controller_per_family_calibration_ewma(flexi):
    _p, fcfg, _s = flexi
    ctrl = BudgetController(fcfg, _plans(), alpha=0.5, num_train_steps=100)
    ctrl.observe_calibration(0, 1e9, 1.0)           # family 0: 1e-9 s/FLOP
    ctrl.observe_calibration(0, 1e9, 3.0)           # EWMA -> 2e-9
    ctrl.observe_calibration(None, 1e9, 10.0)       # mixed: global only
    cal = ctrl.calibration
    assert cal["per_family"] == {0: pytest.approx(2e-9)}
    assert cal["global"] == pytest.approx(0.5 * 2e-9 + 0.5 * 10e-9)
    # families never seen alone price at the global factor
    seen = {m for b in ctrl.levels for m in ctrl.mode_costs[b]}
    assert 1 in seen
    expect = sum(fl * (cal["per_family"][0] if m == 0 else cal["global"])
                 for m, fl in ctrl.mode_costs[1.0].items())
    assert ctrl.cost_seconds(1.0) == pytest.approx(expect)
    # bad observations are ignored, not poisonous
    ctrl.observe_calibration(0, 0.0, 1.0)
    ctrl.observe_calibration(0, 1e9, -1.0)
    assert ctrl.calibration == cal


# ---------------------------------------------------------------------------
# Watchdog detectors + flight recorder


def test_watchdog_recompile_detector_and_cooldown():
    wd = Watchdog(WatchdogConfig(warmup_steps=2, cooldown_steps=3))
    base = dict(queued=0, inflight=1, compiled=5)
    assert wd.observe_step(now=0.0, **base) == []
    assert wd.observe_step(now=1.0, **base) == []
    # a compile during warmup re-baselines silently
    fired = wd.observe_step(now=2.0, queued=0, inflight=1, compiled=6)
    assert [a.kind for a in fired] == [ALERT_RECOMPILE]
    # cooldown suppresses an immediate re-fire, baseline still advances
    assert wd.observe_step(now=3.0, queued=0, inflight=1, compiled=7) == []
    wd.observe_step(now=4.0, queued=0, inflight=1, compiled=7)
    wd.observe_step(now=5.0, queued=0, inflight=1, compiled=7)
    fired = wd.observe_step(now=6.0, queued=0, inflight=1, compiled=8)
    assert [a.kind for a in fired] == [ALERT_RECOMPILE]
    assert len(wd.alerts) == 2


def test_watchdog_queue_p99_drift_detectors():
    wd = Watchdog(WatchdogConfig(queue_limit=4, p99_slo_s=1.0,
                                 min_latencies=3, drift_limit=0.1,
                                 warmup_steps=1))
    fired = wd.observe_step(now=0.0, queued=9, inflight=2, compiled=1,
                            latencies=[2.0, 2.5, 3.0], drift_max=0.5)
    kinds = sorted(a.kind for a in fired)
    assert kinds == sorted([ALERT_QUEUE, ALERT_P99, ALERT_DRIFT])
    p99 = next(a for a in fired if a.kind == ALERT_P99)
    assert p99.value == pytest.approx(3.0) and p99.limit == 1.0
    # below every limit: silence
    wd2 = Watchdog(WatchdogConfig(queue_limit=4, p99_slo_s=10.0,
                                  min_latencies=3, drift_limit=0.1))
    assert wd2.observe_step(now=0.0, queued=1, inflight=1, compiled=1,
                            latencies=[0.1, 0.2, 0.3],
                            drift_max=0.01) == []
    # too few latencies: the p99 detector stays quiet
    wd3 = Watchdog(WatchdogConfig(p99_slo_s=0.01, min_latencies=8))
    assert wd3.observe_step(now=0.0, queued=0, inflight=0, compiled=0,
                            latencies=[5.0] * 3) == []


def test_watchdog_alerts_land_in_span_recorder():
    rec = SpanRecorder(clock=FakeClock())
    wd = Watchdog(WatchdogConfig(queue_limit=1), recorder=rec)
    wd.observe_step(now=0.5, queued=5, inflight=0, compiled=0)
    evs = rec.by_name(f"alert.{ALERT_QUEUE}")
    assert len(evs) == 1 and evs[0].ph == "i"
    assert evs[0].args["value"] == 5.0 and evs[0].args["limit"] == 1.0


def test_watchdog_dump_bundle_and_cap(tmp_path):
    rec = SpanRecorder(clock=FakeClock())
    rec.instant("mark")
    led = AttributionLedger()
    led.attribute_dispatch(time=0.0, label="d", request_ids=[0],
                           weights=[1.0], wall_ns=10, flops=20)
    reg = CompiledCostRegistry()
    wd = Watchdog(WatchdogConfig(queue_limit=1, max_dumps=2),
                  recorder=rec, postmortem_dir=str(tmp_path))
    assert not wd.should_dump()              # nothing fired yet
    wd.observe_step(now=0.0, queued=9, inflight=1, compiled=3)
    assert wd.should_dump()
    path = wd.dump(reason="alert", engine_snapshot={"queued": []},
                   attribution=led, registry=reg)
    assert path and Path(path).exists()
    assert not wd.should_dump()              # pending flag consumed
    bundle = json.loads(Path(path).read_text())
    assert bundle["reason"] == "alert"
    assert bundle["alerts"][0]["kind"] == ALERT_QUEUE
    assert bundle["engine"] == {"queued": []}
    assert any(e["name"] == "mark" for e in bundle["spans"])
    assert bundle["span_counters"]["events_recorded"] >= 1
    assert bundle["attribution"]["totals"]["wall_ns"] == 10
    assert "compiled_costs" in bundle
    # the cap: max_dumps bundles, then the recorder goes quiet
    assert wd.dump(reason="crash") is not None
    assert wd.dump(reason="crash") is None
    assert len(wd.dumps_written) == 2


def test_watchdog_dump_never_raises(tmp_path):
    class Broken:
        def snapshot(self):
            raise RuntimeError("boom")
    wd = Watchdog(postmortem_dir=str(tmp_path))
    assert wd.dump(reason="crash", attribution=Broken()) is None
    wd2 = Watchdog()                          # no dir configured: no-op
    assert wd2.dump(reason="crash") is None


def test_engine_watchdog_fires_and_dumps_on_queue_breach(pipe, tmp_path):
    wd = Watchdog(WatchdogConfig(queue_limit=0, warmup_steps=0))
    tel = Telemetry(profile=True, watchdog=wd,
                    postmortem_dir=str(tmp_path))
    engine = _make_engine(pipe, telemetry=tel)
    engine.max_inflight = 1                  # force a standing queue
    _serve(engine, n=3)
    kinds = {a.kind for a in wd.alerts}
    assert ALERT_QUEUE in kinds
    dumps = sorted(tmp_path.glob("postmortem_*.json"))
    assert dumps
    bundle = json.loads(dumps[0].read_text())
    assert bundle["reason"] == "alert"
    assert "inflight" in bundle["engine"]
    assert bundle["attribution"]["conservation"]["flops_delta"] == 0
    assert tel.snapshot()["alerts"]


# ---------------------------------------------------------------------------
# Telemetry bundle + exporters


def test_telemetry_bundle_wires_profile_and_watchdog(tmp_path):
    tel = Telemetry(profile=True, postmortem_dir=str(tmp_path))
    assert tel.profiling
    assert isinstance(tel.profile, CompiledCostRegistry)
    assert isinstance(tel.attribution, AttributionLedger)
    assert tel.watchdog is not None          # default-built from the dir
    assert tel.watchdog.recorder is tel.recorder
    assert tel.watchdog.postmortem_dir == str(tmp_path)
    snap = tel.snapshot()
    assert snap["attribution"]["conservation"]["wall_ns_delta"] == 0
    assert snap["alerts"] == []
    plain = Telemetry()
    assert not plain.profiling and plain.watchdog is None
    assert "attribution" not in plain.snapshot()


def test_export_surfaces_span_counters():
    rec = SpanRecorder(clock=FakeClock(), max_events=4)
    for i in range(6):
        rec.instant(f"e{i}")
    spans = rec.counters()
    assert spans == {"events_recorded": 6, "events_dropped": 2,
                     "occupancy": 1.0, "capacity": 4}
    line = tel_export.metrics_line({"served": 2}, spans=spans)
    assert "span_dropped=2" in line and "span_occupancy=1" in line


# ---------------------------------------------------------------------------
# Bench harness: the committed perf trajectory


def test_update_trajectory_replaces_one_suite_and_is_stable(tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.run import _headline, update_trajectory
    path = tmp_path / "BENCH.json"
    update_trajectory("serving", {"serving_engine": {"speedup": 1.5,
                                                     "note": "str-dropped"}},
                      "sha1", path=path)
    update_trajectory("profile", {"profile": {"bit_identical": True,
                                              "reconcile": {"n_errors": 0}}},
                      "sha1", path=path)
    doc = json.loads(path.read_text())
    assert set(doc["suites"]) == {"serving", "profile"}
    prof = doc["suites"]["profile"]["benches"]["profile"]
    assert prof == {"bit_identical": 1, "reconcile.n_errors": 0}
    assert "note" not in doc["suites"]["serving"]["benches"]["serving_engine"]
    # re-running the same suite at the same sha is byte-stable and
    # preserves the other suite's entry
    before = path.read_bytes()
    update_trajectory("profile", {"profile": {"bit_identical": True,
                                              "reconcile": {"n_errors": 0}}},
                      "sha1", path=path)
    assert path.read_bytes() == before
    assert json.loads(path.read_text())["suites"]["serving"]["git_sha"] \
        == "sha1"
    assert _headline({"a": {"b": 2.5}, "c": [1, 2], "d": "x"}) \
        == {"a.b": 2.5}


# ---------------------------------------------------------------------------
# Lint: attribution must stay host-pure


def _lint_attr(src: str):
    from repro.analysis.rules_telemetry import TelemetryRule
    return TelemetryRule().check("src/repro/telemetry/attribution.py",
                                 ast.parse(src), src)


def test_rules_attribution_bans_device_imports():
    assert [f.rule for f in _lint_attr("import numpy as np\n")] \
        == ["telemetry-attribution-device"]
    assert [f.rule for f in _lint_attr("from jax import numpy as jnp\n")] \
        == ["telemetry-attribution-device"]
    assert [f.rule for f in _lint_attr("import jaxlib\n")] \
        == ["telemetry-attribution-device"]


def test_rules_attribution_bans_device_calls_and_syncs():
    bad = ("def f(x):\n"
           "    return np.sum(x)\n")
    assert [f.rule for f in _lint_attr(bad)] \
        == ["telemetry-attribution-device"]
    bad = ("def f(x):\n"
           "    return x.block_until_ready()\n")
    assert [f.rule for f in _lint_attr(bad)] \
        == ["telemetry-attribution-device"]
    bad = ("def f(x):\n"
           "    return x.item()\n")
    assert [f.rule for f in _lint_attr(bad)] \
        == ["telemetry-attribution-device"]


def test_rules_attribution_allows_host_arithmetic():
    ok = ("import dataclasses\n"
          "def exact_shares(total, weights):\n"
          "    s = float(sum(weights))\n"
          "    return [int(total * w / s) for w in weights]\n")
    assert _lint_attr(ok) == []
    # the shipped module is clean under its own rule
    src = Path(__file__).resolve().parents[1] \
        / "src/repro/telemetry/attribution.py"
    text = src.read_text()
    assert _lint_attr(text) == []


def test_roofline_peaks_keyed_by_device_kind():
    """Roofline terms price a program with the named chip's published
    peaks; a device the table does not hold is an error, not a v5e
    default."""
    from repro.launch import roofline as rl
    v5e = rl.device_peaks("TPU v5 lite")
    assert v5e.flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    assert v5e.source
    terms = rl.roofline_terms({"flops": 197e12, "bytes accessed": 819e9 / 2},
                              {}, 1, device_kind="TPU v5 lite")
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(0.5)
    assert terms["dominant"] == "compute"
    with pytest.raises(ValueError, match="no published peaks"):
        rl.device_peaks("cpu")
    with pytest.raises(ValueError):
        rl.roofline_terms({"flops": 1.0}, {}, 1, device_kind="TPU v9")
