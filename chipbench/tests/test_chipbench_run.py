"""A whole run at a test size on the CPU, past the harness's look for a
chip: a sound window is correct and prints the result line's schema;
the same window with the timed path broken underneath is not correct;
and the control (the reference in float8) put in the program's place
is not correct either."""
import json
import sys
from pathlib import Path

import jax
import pytest

from chipbench import check, control, devtrace, enginetrace, peaks, run, spec

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 33 + 5
E2E = {"latency_p50_s": "s", "latency_p90_s": "s", "img_per_s": "img/s",
       "setup_s": "s"}


def tiny_cell():
    return spec.make_cell(
        "tiny.open", 1, config_path=DATA / "tiny-dit.json",
        traffic_path=DATA / "tiny-open.json",
        limits_path=DATA / "tiny-limits.json", end_to_end=E2E,
        per_layer={"queue_wait_p90_s": "s", "packing_eff.open": "%"})


def warmed(cell):
    """Set-up as a run makes it, on whatever device JAX has, with no
    peak table and no persistent cache."""
    src = str(run.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return run.warm(cell, SEED, jax.devices(), 1.5, peak=None)


@pytest.fixture(scope="module")
def prepared():
    return warmed(tiny_cell())


@pytest.fixture(scope="module")
def backlog():
    cell = spec.make_cell(
        "tiny.backlog", 1, config_path=DATA / "tiny-dit.json",
        traffic_path=DATA / "tiny-backlog.json",
        limits_path=DATA / "tiny-limits.json",
        end_to_end={"img_per_s": "img/s", "setup_s": "s"},
        per_layer={"packing_eff.backlog": "%"})
    return warmed(cell)


def window(pp):
    m = run.measure(pp, 1.5, False)
    checks = run.check(pp.cell, pp.seed, pp.params, m["finished"])
    return checks, check.correct(m["failed"], checks)


def test_sound_window_is_correct(prepared):
    checks, ok = window(prepared)
    assert ok, checks
    assert checks["compared"]["value"] == 3


def test_step_that_returns_its_state_unchanged_is_caught(prepared,
                                                         monkeypatch):
    def packed_step(*a, **kw):
        return lambda params, xs, *rest: tuple(xs)
    monkeypatch.setattr(prepared.engine.pipe, "packed_step", packed_step)
    checks, ok = window(prepared)
    assert not ok
    assert checks["rel_rms_max"]["value"] > checks["rel_rms_max"]["limit"]


def test_answer_altered_where_it_is_produced_is_caught(prepared,
                                                       monkeypatch):
    orig = prepared.engine.pipe.packed_step

    def packed_step(*a, **kw):
        runner = orig(*a, **kw)
        return lambda *args: tuple(o * 1.25 for o in runner(*args))
    monkeypatch.setattr(prepared.engine.pipe, "packed_step", packed_step)
    checks, ok = window(prepared)
    assert not ok
    assert checks["rel_rms_max"]["value"] > checks["rel_rms_max"]["limit"]


def test_control_reads_above_the_limit(prepared):
    """The control's x0, handed to the run's own comparison in place of
    the served x0, comes out not correct."""
    checks = control.control_checks(prepared.cell, prepared.seed,
                                    prepared.params)
    assert check.correct(0, checks) is False
    assert checks["rel_rms_max"]["value"] > checks["rel_rms_max"]["limit"]
    assert checks["compared"]["value"] >= 1


def test_traced_window_reads_the_engine_split(prepared, monkeypatch):
    """A traced window's records hold the engine reduction and the block
    work that the engine readers read. The CPU's trace has no device op
    line, so the recorded chip excerpt stands in for it."""
    ex = json.loads((DATA / "trace_excerpt_engine.json").read_text())
    trace = {k: ex[k] for k in ("device_ops", "host_spans", "engine_spans")}
    trace["op_names"] = [ex["op_name_table"][i] for i in ex["op_name_ids"]]
    monkeypatch.setattr(devtrace, "find_xplane", lambda d: d)
    monkeypatch.setattr(enginetrace, "normalise",
                        lambda path, device_id=0: trace)
    r = run.measure(prepared, 1.5, True)["run"]
    assert r["trace"]["engine"]["engine_spans"] == 42
    assert r["trace"]["engine"]["idle_in_engine_s"] > 0
    assert r["work"]["block_flops"] > 0
    r["peak"] = peaks.peak_for("TPU v5 lite")
    for name in ("engine_idle_share.open", "sync_wait_share",
                 "block_matmul_util"):
        assert spec.metric_reader(name)(r) > 0, name


def test_result_line_schema(prepared, capsys):
    """Runs last: it hands the engine over, as a run does."""
    result = run.finish(prepared, 1.5, False)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == set(E2E)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == E2E[name]
        assert m["value"] > 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    err = capsys.readouterr().err
    assert "check rel_rms_max" in err.splitlines()[-1]


def test_backlog_run_is_correct(backlog):
    """A backlog run: its window keeps the queue busy, counts the steps
    it dispatched, and what it served passes the check."""
    result = run.finish(backlog, 1.5, False)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"img_per_s", "setup_s"}
    assert result["metrics"]["img_per_s"]["value"] > 0
    assert result["checks"]["compared"]["value"] >= 1
