"""The reading of the program's own spans and scopes from a trace: the
idle time by engine phase, the device time by scope, the readers built
on them, and the dense block work they divide by."""
import hashlib
import json
import types
from pathlib import Path

import pytest

from chipbench import devtrace, drive, enginetrace, generator, peaks, spec

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
work = spec.load_module(CONFIGS / "dit_work.py")
MS = 1_000_000
PEAK = peaks.peak_for("TPU v5 lite")
MLP = "jit(step)/jit(main)/while/body/dit_block/mlp/dot_general"
ATTN = "jit(step)/jit(main)/while/body/dit_block/attn/pallas_call"
NEW_METRICS = ("engine_idle_share.open", "engine_idle_share.backlog",
               "sync_wait_share", "block_matmul_util")


def synthetic():
    """100 ms: a block's ops, the embed, an unscoped op; one engine step
    with its phases, then a wait for arrivals."""
    ops = [
        ["while.4", 0, 21 * MS, "jit(step)/jit(main)/while"],
        ["fusion.1", 0, 10 * MS, MLP],
        ["flash_attention.2", 10 * MS, 10 * MS, ATTN],
        ["fusion.3", 21 * MS, 4 * MS, "jit(step)/jit(main)/embed/conv"],
        ["fusion.5", 60 * MS, 10 * MS, ""],
    ]
    return {
        "device_ops": [o[:3] for o in ops],
        "op_names": [o[3] for o in ops],
        "host_spans": [["chipbench.window", 0, 100 * MS],
                       ["chipbench.step", 21 * MS, 55 * MS],
                       ["chipbench.wait_arrival", 76 * MS, 24 * MS]],
        "engine_spans": [["engine.step", 22 * MS, 53 * MS],
                         ["engine.admit", 23 * MS, 3 * MS],
                         ["engine.plan", 26 * MS, 4 * MS],
                         ["engine.pack", 30 * MS, 20 * MS],
                         ["engine.dispatch", 50 * MS, 8 * MS],
                         ["engine.materialize", 60 * MS, 12 * MS],
                         ["engine.retire", 72 * MS, 2 * MS]],
    }


def test_idle_goes_to_the_innermost_engine_span():
    r = enginetrace.reduce(synthetic())
    idle = {k: pytest.approx(v) for k, v in r["idle_s"].items()}
    # idle [25, 60) and [70, 100) ms; engine.step's own 3 ms (22-23 is
    # busy, 58-60 and 74-75 are not) keep its own label
    assert idle == {"engine.pack": 0.020, "engine.dispatch": 0.008,
                    "engine.plan": 0.004, "engine.step": 0.003,
                    "engine.admit": 0.001, "engine.materialize": 0.002,
                    "engine.retire": 0.002,
                    enginetrace.OUTSIDE: 0.025}
    assert r["idle_in_engine_s"] == pytest.approx(0.040)
    assert r["sync_wait_s"] == pytest.approx(0.012)
    assert r["engine_spans"] == 7 and r["scoped_ops"] == 3
    # own device time by scope: the loop keeps 1 ms of its 21
    assert r["device_s_by_scope"] == {
        "dit_block/mlp": pytest.approx(0.010),
        "dit_block/attn": pytest.approx(0.010),
        enginetrace.UNSCOPED: pytest.approx(0.011),
        "embed": pytest.approx(0.004)}
    assert enginetrace.by_top_scope(r["device_s_by_scope"])["dit_block"] \
        == pytest.approx(0.020)
    # the block's ops less the flash kernel's
    assert r["block_s"] == pytest.approx(0.010)


def test_innermost_pieces():
    spans = [("a", 0, 10), ("b", 2, 4), ("c", 3, 8), ("d", 12, 14)]
    assert enginetrace.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 8, "c"), (8, 10, "a"),
        (12, 14, "d")]


def test_scope_path():
    assert enginetrace.scope_path(MLP) == "dit_block/mlp"
    assert enginetrace.scope_path("jit(step)/guidance_solver/mul") == \
        "guidance_solver"
    assert enginetrace.scope_path("jit(step)/while/body/add") == ""
    assert enginetrace.scope_path("") == ""


def run_record(trace, block_flops=0.985e12):
    tr = devtrace.reduce(trace)
    tr["engine"] = enginetrace.reduce(trace)
    return {"cell": "c", "chips": 1, "peak": PEAK, "window_s": 0.1,
            "steps": [], "requests": [], "trace": tr,
            "work": {"request_steps": 1, "model_flops": 1.0,
                     "attn_flops": 1.0, "attn_bytes": 1.0,
                     "block_flops": block_flops}}


def test_readers_on_a_synthetic_trace():
    r = run_record(synthetic())
    read = lambda n: spec.metric_reader(n)(r)  # noqa: E731
    assert read("engine_idle_share.open") == pytest.approx(40.0)
    assert read("engine_idle_share.backlog") == pytest.approx(40.0)
    assert read("sync_wait_share") == pytest.approx(12.0)
    # 0.985 TFLOP at 197 TFLOP/s is 5 ms, of 10 ms of block ops
    assert read("block_matmul_util") == pytest.approx(50.0)


def test_readers_return_nothing_without_the_programs_spans():
    """The earlier recorded excerpt, from a program that stamped no
    engine span and named no scope: every new reader returns nothing."""
    trace = json.loads((DATA / "trace_excerpt.json").read_text())
    r = run_record(trace)
    assert r["trace"]["engine"]["engine_spans"] == 0
    assert r["trace"]["engine"]["scoped_ops"] == 0
    for name in NEW_METRICS:
        assert spec.metric_reader(name)(r) is None
    # spans but no scopes: the scope metric alone falls silent
    trace = synthetic()
    trace["op_names"] = [""] * len(trace["device_ops"])
    r = run_record(trace)
    assert spec.metric_reader("block_matmul_util")(r) is None
    assert spec.metric_reader("sync_wait_share")(r) == pytest.approx(12.0)
    r = run_record(synthetic())
    r["trace"] = None
    for name in NEW_METRICS:
        assert spec.metric_reader(name)(r) is None


def test_devtrace_reduction_is_unchanged():
    """``devtrace.reduce`` gives byte for byte what it gave before the
    engine's spans existed, and takes no notice of the extra lists."""
    trace = json.loads((DATA / "trace_excerpt.json").read_text())
    out = json.dumps(devtrace.reduce(trace), sort_keys=True)
    assert hashlib.sha256(out.encode()).hexdigest() == (
        "172b1218aeeb0ea517be1aac7a342e10b723d085aef34f7859b48077b216f673")
    extra = dict(trace, engine_spans=[["engine.step", 0, 1]],
                 op_names=[MLP] * len(trace["device_ops"]))
    assert json.dumps(devtrace.reduce(extra), sort_keys=True) == out


XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000
             stats { metadata_id: 2 int64_value: 2 } }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%%fusion.3 = bf16[8]{0} fusion(x)"
    stats { metadata_id: 1 str_value: "%(mlp)s" } } }
  event_metadata { key: 2 value { id: 2 name: "flash_attention.1"
    stats { metadata_id: 2 int64_value: 7 }
    stats { metadata_id: 1 ref_value: 3 } } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
  stat_metadata { key: 3 value { id: 3 name: "%(attn)s" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 7
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 11000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "engine.dispatch" } }
}
""" % {"attn": ATTN, "mlp": MLP}


def test_normalise_reads_op_names_from_the_event_metadata(tmp_path):
    """The op's ``tf_op`` stat, which a TPU's device plane keeps on the
    event's metadata, as a string or as a reference to a stat name."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = enginetrace.normalise(str(path))
    assert tr["device_ops"] == [["fusion.3", 1000, 5000],
                                ["flash_attention.1", 7000, 2000],
                                ["copy.2", 10000, 1000]]
    assert tr["op_names"] == [MLP, ATTN, ""]
    assert tr["host_spans"] == [["chipbench.window", 1000, 12000]]
    assert tr["engine_spans"] == [["engine.step", 1000, 11000],
                                  ["engine.dispatch", 2000, 4000]]


def test_block_work_matches_hand_arithmetic():
    """24*N*d^2 per token and 12*d^2 of adaLN per segment, per layer."""
    c = json.loads((CONFIGS / "dit-xl-2.json").read_text())
    d, L = 1152, 28
    for mode, n in ((0, 256), (1, 64)):
        assert work.block_flops(c, mode) == pytest.approx(
            L * (24 * n * d * d + 12 * d * d))
        # with attention, the blocks' whole work
        assert work.block_flops(c, mode) + L * work.attention_flops(
            n, d) == pytest.approx(L * work.layer_flops(c, n))
    tiny = json.loads((DATA / "tiny-dit.json").read_text())
    d, f, L = 64, 256, 2
    n = work.tokens(tiny, 0)
    assert n == 16
    hand = L * (n * (2 * d * 3 * d + 2 * d * d + 2 * 2 * d * f)
                + 2 * d * 6 * d)
    assert work.block_flops(tiny, 0) == hand
    assert work.request_block_flops(tiny, [0, 0, 1], guided=True) \
        == 2 * (2 * hand + work.block_flops(tiny, 1))


def test_window_block_work_counts_the_window_steps():
    """The block work of ``drive.window_steps``'s request-steps: a
    request finished before the window counts nothing, one finished in
    it its steps from the window's start, one in flight up to the
    window's end."""
    tiny = json.loads((DATA / "tiny-dit.json").read_text())
    traffic = {"sampler": {"T": 4, "guidance_scale": 1.5}}
    cell = types.SimpleNamespace(config=tiny, work=work)

    def served(rid, finish, budget=1.0):
        r = generator.Req(index=rid, due_s=0.0, cond=1, budget=budget,
                          key=None)
        return drive.Served(r, 0.0, 0.0, rid, finish=finish)

    reqs = [served(0, 0.5), served(1, 2.0), served(2, None)]
    start, end = {1: 1, 2: 0}, {2: 3}
    steps = drive.window_steps(cell, traffic, reqs, start, end, 1.0)
    per_step = 2 * work.block_flops(tiny, 0)
    assert steps["block_flops"] == pytest.approx((3 + 3) * per_step)
    assert steps["request_steps"] == 6


def test_recorded_chip_excerpt_with_engine_spans():
    """40 ms of a traced run of dit-xl-2.tiers-open on one TPU v5e chip,
    from its first step: the engine's phases share the harness's clock
    and nest in its ``chipbench.step`` spans, the ``op_name`` each device
    op carries holds the packed step's scopes, and the reduction's parts
    add up to the device's busy and idle time."""
    ex = json.loads((DATA / "trace_excerpt_engine.json").read_text())
    names = [ex["op_name_table"][i] for i in ex["op_name_ids"]]
    trace = {k: ex[k] for k in ("device_ops", "host_spans", "engine_spans")}
    trace["op_names"] = names
    steps = [(s, s + d) for n, s, d in ex["host_spans"]
             if n == "chipbench.step"]
    engine_steps = [(s, s + d) for n, s, d in ex["engine_spans"]
                    if n == "engine.step"]
    assert len(ex["engine_spans"]) == 42 and len(engine_steps) == 7
    for n, s, d in ex["engine_spans"]:
        assert any(a <= s and s + d <= b for a, b in steps), n
        assert any(a <= s and s + d <= b for a, b in engine_steps), n
    scopes = [enginetrace.scope_path(m) for m in names]
    flash = [p for (n, _s, _d), p in zip(ex["device_ops"], scopes)
             if devtrace.op_group(n) == "flash_attention"]
    assert flash and set(flash) == {"dit_block/attn"}
    assert sum(map(bool, scopes)) > 0.9 * len(scopes)
    r = enginetrace.reduce(trace)
    d = devtrace.reduce(trace)
    assert sum(r["idle_s"].values()) == pytest.approx(
        d["window_s"] - d["busy_s"])
    assert sum(r["device_s_by_scope"].values()) == pytest.approx(d["busy_s"])
    # recorded: 6.4 ms idle, 5.4 of it inside the engine's phases, most in
    # admission; 25.4 ms of host time waiting in engine.materialize
    assert r["idle_in_engine_s"] == pytest.approx(0.005388248)
    assert max(r["idle_s"], key=r["idle_s"].get) == "engine.admit"
    assert r["idle_s"]["engine.step"] < 0.1 * r["idle_in_engine_s"]
    assert r["sync_wait_s"] == pytest.approx(0.025363814)
    assert r["block_s"] == pytest.approx(0.018696076)
