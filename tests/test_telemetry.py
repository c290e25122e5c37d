"""End-to-end inference telemetry (DESIGN.md §telemetry).

The load-bearing asserts: tapped steps produce BIT-IDENTICAL latents to
untapped ones (taps are data, not structure), the on-device drift tap
matches an eager host recomputation, turning telemetry on adds zero
recompiles to a warm engine, and the exported trace is valid Chrome
trace-event JSON.
"""
import ast
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import apply as cache_apply
from repro.core import flexify
from repro.core.guidance import GuidanceConfig
from repro.diffusion import schedule as sch
from repro.pipeline import FlexiPipeline, PackLayout, SamplingPlan
from repro.pipeline.packed import make_packed_step_fn
from repro.pipeline.plan import CacheSpec
from repro.serving import ServingEngine
from repro.telemetry import TapAggregator, TapSample, Telemetry
from repro.telemetry import export as tel_export
from repro.telemetry.trace import (ENGINE_PID, REQUEST_PID, SpanRecorder,
                                  span)

pytestmark = pytest.mark.tier1

T = 6


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        self.t += 0.001          # every read advances: spans get nonzero dur
        return self.t


@pytest.fixture(scope="module")
def flexi(tiny_dit_cfg, trained_like_dit):
    fparams, fcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)])
    return fparams, fcfg, sch.linear_schedule(100)


@pytest.fixture(scope="module")
def pipe(flexi):
    fparams, fcfg, sched = flexi
    return FlexiPipeline(fparams, fcfg, sched)


# ---------------------------------------------------------------------------
# SpanRecorder / trace export


def test_span_recorder_ring_buffer_counts_drops():
    rec = SpanRecorder(clock=FakeClock(), max_events=4)
    for i in range(7):
        rec.instant(f"e{i}")
    assert len(rec.events) == 4
    assert rec.events_recorded == 7
    assert rec.events_dropped == 3
    assert [e.name for e in rec.events] == ["e3", "e4", "e5", "e6"]


def test_span_recorder_event_kinds():
    rec = SpanRecorder(clock=FakeClock())
    with rec.span("work", args={"k": 2}):
        pass
    rec.complete("req0", 1.0, 3.5, pid=REQUEST_PID, tid=7,
                 args={"budget": 0.6})
    rec.counter("engine", {"inflight": 3.0})
    spans = rec.by_name("work")
    assert len(spans) == 1 and spans[0].ph == "X" and spans[0].dur > 0
    req = rec.by_name("req0")[0]
    assert (req.pid, req.tid, req.dur) == (REQUEST_PID, 7, 2.5)
    assert rec.by_name("engine")[0].ph == "C"


def test_chrome_trace_export_roundtrip(tmp_path):
    rec = SpanRecorder(clock=FakeClock())
    with rec.span("dispatch"):
        pass
    rec.instant("mark")
    path = tmp_path / "trace.json"
    rec.dump(str(path))
    t = json.loads(path.read_text())           # must be plain-JSON loadable
    evs = t["traceEvents"]
    # process metadata names both tracks; ts/dur are exported in µs
    meta = [e for e in evs if e["ph"] == "M"]
    assert {e["pid"] for e in meta} == {ENGINE_PID, REQUEST_PID}
    x = next(e for e in evs if e["ph"] == "X")
    src = rec.by_name("dispatch")[0]
    assert x["ts"] == pytest.approx(src.ts * 1e6)
    assert x["dur"] == pytest.approx(src.dur * 1e6)
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t"


# ---------------------------------------------------------------------------
# TapAggregator


def _sample(k=2, n_real=(1, 2), caps=(2, 3), drift=True, t=0.0):
    groups = tuple((m, c) for m, c in zip((0, 1), caps))
    eps = tuple(np.full((k, c), 1.0 + g) for g, c in enumerate(caps))
    dr = tuple(np.full((k, c), 0.5 * (g + 1)) for g, c in enumerate(caps)) \
        if drift else None
    return TapSample(time=t, k=k, groups=groups, n_real=n_real,
                     eps_norm=eps, drift=dr,
                     attn_blocks=np.asarray([3, 4], np.int32))


def test_tap_aggregator_masks_dummy_slots():
    agg = TapAggregator()
    agg.add(_sample(n_real=(1, 2)))
    out = agg.aggregate()
    # 2 steps x (1 + 2) live requests = 6 request-steps, dummies excluded
    assert out["request_steps"] == 6
    assert out["eps_norm"]["mean"] == pytest.approx((1.0 * 2 + 2.0 * 4) / 6)
    assert out["drift"]["max"] == pytest.approx(1.0)
    assert out["drift_per_mode"] == {"0": pytest.approx(0.5),
                                     "1": pytest.approx(1.0)}
    assert out["attn_blocks"] == {"active": 6, "total": 8,
                                  "skip_rate": pytest.approx(0.25)}


def test_tap_counter_series_backdated_into_trace():
    agg = TapAggregator()
    agg.add(_sample(t=1.5))
    agg.add(_sample(n_real=(0, 0), t=2.5))     # all-dummy: no point
    series = agg.counter_series()
    assert len(series) == 1
    when, vals = series[0]
    assert when == 1.5
    assert vals["drift_max"] == pytest.approx(1.0)
    assert set(vals) == {"eps_norm_mean", "drift_mean", "drift_max"}
    rec = SpanRecorder(clock=FakeClock(10.0))
    rec.counter("taps", vals, ts=when)
    assert rec.by_name("taps")[0].ts == 1.5    # dispatch time, not now


def test_tap_aggregator_empty_groups_and_window():
    agg = TapAggregator(max_samples=2)
    for i in range(5):
        agg.add(_sample(n_real=(0, 0), t=float(i)))
    out = agg.aggregate()
    assert len(agg) == 2
    assert out["samples_recorded"] == 5
    assert out["request_steps"] == 0
    assert "eps_norm" not in out and "drift" not in out


# ---------------------------------------------------------------------------
# Exporters


def test_flatten_drops_nan_and_sanitizes():
    line = tel_export.metrics_line(
        {"a": {"p50": 1.5, "bad": float("nan")}, "ok": True, "s": "str",
         "b-c": 2})
    assert line == "[metrics] a_p50=1.5 b_c=2 ok=1"


def test_metrics_line_order_and_content():
    line = tel_export.metrics_line(
        {"served": 5, "p99": 2.0, "p50": 1.0, "zzz": 9.0},
        taps={"drift": {"mean": 0.5, "max": 1.5}},
        compile_stats={"compiled": 4})
    assert line.startswith("[metrics] served=5 p50=1 p99=2")
    assert "drift_mean=0.5" in line and "compiled=4" in line
    assert line.rstrip().endswith("zzz=9")      # unknown keys trail


# ---------------------------------------------------------------------------
# Taps are data, not structure: bit-identity + drift ≡ eager


@pytest.mark.parametrize("cache_split", [None, 1])
def test_tapped_step_bit_identical(flexi, cache_split):
    fparams, fcfg, sched = flexi
    layout = PackLayout(groups=((0, 1), (1, 2)), guided=True)
    kw = dict(k_steps=2, cache_split=cache_split)
    off = make_packed_step_fn(fcfg, sched, layout, **kw)
    on = make_packed_step_fn(fcfg, sched, layout, taps=True, **kw)
    xs, metas, keys, deltas, refreshes = [], [], [], [], []
    key = jax.random.PRNGKey(0)
    for gi, (mode, n) in enumerate(layout.groups):
        xs.append(jax.random.normal(jax.random.fold_in(key, gi),
                                    (n,) + fcfg.dit.latent_shape))
        meta = np.zeros((2, 3, n), np.int32)
        meta[0, 0], meta[1, 0] = 90, 80
        meta[0, 1], meta[1, 1] = 80, 70
        metas.append(jnp.asarray(meta))
        keys.append(jnp.zeros((2, n, 2), jnp.uint32))
        if cache_split is not None:
            _eb, N, d = cache_apply.delta_shape(fcfg, mode, n, True)
            deltas.append(jnp.zeros((n, 2, N, d)))
            refreshes.append(jnp.asarray([[True] * n, [False] * n]))
    args = [fparams, tuple(xs), tuple(metas), tuple(keys)]
    if cache_split is not None:
        args += [tuple(deltas), tuple(refreshes)]
    out_off = off(*args)
    out_on = on(*args)
    if cache_split is None:
        xs_off, (xs_on, tap) = out_off, out_on
    else:
        (xs_off, nd_off), (xs_on, nd_on, tap) = out_off, out_on
        for a, b in zip(nd_off, nd_on):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert len(tap["drift"]) == len(layout.groups)
    for a, b in zip(xs_off, xs_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # tap contract: [k, n_g] per group + the layout's block ledger
    for g, (_m, n) in enumerate(layout.groups):
        assert tap["eps_norm"][g].shape == (2, n)
    active, total = (int(v) for v in np.asarray(tap["attn_blocks"]))
    assert 0 < active <= total


def test_drift_tap_matches_eager_recomputation(flexi):
    fparams, fcfg, sched = flexi
    B = 2
    g = GuidanceConfig(scale=1.5, mode_cond=0, mode_uncond=0)
    cond = jnp.asarray([1, 2], jnp.int32)
    null = jnp.full((B,), fcfg.dit.num_classes, jnp.int32)
    eps_fn_c = cache_apply.make_cached_eps_fn(
        fparams, fcfg, cond, null, g, None, None, 1, attn_backend="dense")
    ts = sch.respaced_timesteps(100, T)
    refresh = jnp.asarray([i % 2 == 0 for i in range(len(ts))])
    x0 = jax.random.normal(jax.random.PRNGKey(3),
                           (B,) + fcfg.dit.latent_shape)
    delta0 = jnp.zeros(cache_apply.delta_shape(fcfg, 0, B, True))
    key = jax.random.PRNGKey(4)
    _x, tap = cache_apply.cached_ddim_phase(
        eps_fn_c, sched, x0, ts, refresh, key, delta0, taps=True)
    tap_drift = np.asarray(tap["drift"])                     # [T, 2B]

    ts_prev = np.concatenate([ts[1:], [-1]])
    x, delta, eager = x0, delta0, []
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
    mask = np.asarray(refresh)
    assert float(eager[mask].mean()) > 0        # drift is a real signal
    np.testing.assert_allclose(tap_drift, eager, atol=1e-5)
    # skip steps replay exactly: the tap is exactly zero there
    assert np.max(np.abs(tap_drift[~mask])) == 0.0


def test_pipeline_sample_taps(pipe, flexi):
    _f, fcfg, _s = flexi
    plan = SamplingPlan(T=T, guidance_scale=1.5,
                        cache=CacheSpec(policy="interval", interval=2,
                                        split=1))
    key = jax.random.PRNGKey(5)
    res_off = pipe.sample(plan, 2, key)
    res_on = pipe.sample(plan, 2, key, taps=True)
    assert np.array_equal(np.asarray(res_off.x0), np.asarray(res_on.x0))
    phases = res_on.trace["taps"]
    assert len(phases) >= 1
    total = sum(p["drift"].shape[0] for p in phases)
    assert total == T
    with pytest.raises(ValueError, match="no cache"):
        pipe.sample(SamplingPlan(T=T, guidance_scale=1.5), 2, key,
                    taps=True)


# ---------------------------------------------------------------------------
# Engine integration


def _make_engine(pipe, telemetry=None, clock=None):
    plans = {0.6: SamplingPlan(T=T, budget=0.5, guidance_scale=1.5),
             1.0: SamplingPlan(T=T, budget=1.0, guidance_scale=1.5)}
    return ServingEngine(pipe, plans, policy="fifo", steps_per_dispatch=2,
                         cache=CacheSpec(policy="interval", interval=2,
                                         split=1),
                         clock=clock, telemetry=telemetry)


def _serve(engine, n=4):
    for i in range(n):
        engine.submit(cond=i % 10, budget=0.6 if i % 2 else 1.0)
    return {r.request.id: np.asarray(r.x0) for r in engine.run()}


def test_engine_telemetry_zero_recompiles_and_bit_identity(pipe):
    tel = Telemetry(taps=True)
    eng_on = _make_engine(pipe, telemetry=tel, clock=FakeClock())
    served_on = _serve(eng_on)
    warm = eng_on.cache_stats()["compiled"]
    # replay the same budget mix: everything warm, taps included
    again = _serve(eng_on)
    assert eng_on.cache_stats()["compiled"] == warm
    assert set(again) != set(served_on)          # fresh request ids

    eng_off = _make_engine(pipe, clock=FakeClock())
    served_off = _serve(eng_off)
    for rid, x_on in served_on.items():
        assert np.array_equal(x_on, served_off[rid])

    agg = tel.taps.aggregate()
    assert agg["request_steps"] > 0
    assert agg["drift"]["mean"] >= 0 and "eps_norm" in agg
    assert agg["attn_blocks"]["total"] > 0


def test_engine_spans_cover_lifecycle(pipe, tmp_path):
    tel = Telemetry(taps=True)
    eng = _make_engine(pipe, telemetry=tel, clock=FakeClock())
    _serve(eng, n=3)
    names = {e.name for e in tel.recorder.events}
    for expected in ("admit", "plan", "pack", "dispatch", "materialize"):
        assert expected in names, f"missing span {expected!r}"
    # one lifecycle row per request on the requests track
    rows = [e for e in tel.recorder.events if e.pid == REQUEST_PID]
    assert len(rows) == 3
    assert {e.tid for e in rows} == {0, 1, 2}
    assert all(e.args["budget_served"] >= 0.6 for e in rows)
    # cold dispatches surfaced as compile events (fresh pipe had to build)
    assert any(e.name == "compile" for e in tel.recorder.events)
    path = tmp_path / "engine_trace.json"
    tel.recorder.dump(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_engine_without_telemetry_records_nothing(pipe):
    eng = _make_engine(pipe, clock=FakeClock())
    _serve(eng, n=2)
    assert eng.telemetry is None


def test_span_pushes_the_ring_under_its_phase_name():
    rec = SpanRecorder(clock=FakeClock())
    with span(rec, "plan", k=2) as sp:
        assert sp.on
        sp.set(groups="((0, 1),)")
    ev = rec.by_name("plan")[0]
    assert ev.ph == "X" and ev.dur > 0
    assert ev.args == {"k": 2, "groups": "((0, 1),)"}
    # no recorder, no profiler: nobody records, so no args are built
    with span(None, "plan") as sp:
        assert not sp.on


def test_engine_phases_nest_in_step_on_the_profiler_clock(pipe, tmp_path):
    """The engine's phases reach the profiler's own trace with no
    Telemetry attached: on the host plane, every phase lies inside an
    ``engine.step`` of the same thread, and ``engine.dispatch`` names
    the requests it advances."""
    import glob

    from jax.profiler import ProfileData
    eng = _make_engine(pipe)
    _serve(eng, n=2)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, n=3)
    finally:
        jax.profiler.stop_trace()
    xplane, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats)) for e in line.events
              if e.name.startswith("engine.")]
             for plane in ProfileData.from_file(xplane).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    evs = max(lines, key=len)
    steps = [(a, b) for n, a, b, _ in evs if n == "engine.step"]
    assert steps
    seen = set()
    for name, a, b, stats in evs:
        if name == "engine.step":
            continue
        seen.add(name)
        assert any(s0 <= a and b <= s1 for s0, s1 in steps), name
    assert {"engine.admit", "engine.plan", "engine.pack",
            "engine.dispatch", "engine.materialize",
            "engine.retire"} <= seen
    ids = set()
    for name, _a, _b, stats in evs:
        if name == "engine.dispatch":
            ids |= {int(i) for i in str(stats["ids"]).split()}
    assert ids == {2, 3, 4}                # the traced requests' ids


# ---------------------------------------------------------------------------
# Analysis: lint rules + jaxpr audit unit


def _lint(src: str, path="src/repro/telemetry/taps.py"):
    from repro.analysis.rules_telemetry import TelemetryRule
    return TelemetryRule().check(path, ast.parse(src), src)


def test_rules_telemetry_flags_host_callback():
    bad = "import jax\ndef tap(x):\n    jax.debug.print('{}', x)\n"
    fs = _lint(bad)
    assert [f.rule for f in fs] == ["telemetry-host-callback"]
    fs = _lint("from jax import pure_callback\n"
               "def t(x):\n    return pure_callback(f, s, x)\n")
    assert [f.rule for f in fs] == ["telemetry-host-callback"]


def test_rules_telemetry_flags_host_sync_outside_sink():
    bad = ("import numpy as np\n"
           "class TapAggregator:\n"
           "    def add(self, s):\n"
           "        self.v = np.asarray(s.eps)\n")
    fs = _lint(bad)
    assert [f.rule for f in fs] == ["telemetry-tap-host-sync"]


def test_rules_telemetry_allows_sink_and_other_files():
    ok = ("import numpy as np\n"
          "class TapAggregator:\n"
          "    def aggregate(self):\n"
          "        return float(np.asarray(self.v).mean())\n")
    assert _lint(ok) == []
    # outside telemetry/ the rule is silent
    assert _lint("import jax\njax.debug.print('x')\n",
                 path="src/repro/pipeline/packed.py") == []


def test_repo_telemetry_source_is_clean():
    from pathlib import Path

    from repro import telemetry
    from repro.analysis.rules_telemetry import TelemetryRule
    rule = TelemetryRule()
    pkg = Path(telemetry.__file__).parent
    for py in sorted(pkg.glob("*.py")):
        rel = f"src/repro/telemetry/{py.name}"
        text = py.read_text()
        assert rule.check(rel, ast.parse(text), text) == [], rel


def test_jaxpr_audit_tapped_step_passes():
    from repro.analysis.jaxpr_audit import audit_tapped_step
    rep = audit_tapped_step()
    assert rep.findings == []
    assert set(rep.fingerprints) == {"packed_step_tapped",
                                     "packed_cached_step_tapped"}
