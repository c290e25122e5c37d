"""The per-layer metric readers on hand-made run records."""
import pytest

from chipbench import peaks, spec

PEAK = peaks.peak_for("TPU v5 lite")


def run_record(**kw):
    rec = {"cell": "c", "chips": 1, "peak": PEAK, "window_s": 10.0,
           "steps": [(900, 1000), (500, 1000)],
           "requests": [{"due": 0.0, "admit": 0.1 * i, "finish": 5.0,
                         "budget": 1.0} for i in range(11)],
           "work": {"request_steps": 100, "model_flops": 197e12,
                    "attn_flops": 19.7e12, "attn_bytes": 8.19e9},
           "trace": {"window_s": 10.0, "busy_s": 8.0,
                     "kernel_s": {"flash_attention": 0.2}}}
    rec.update(kw)
    return rec


def test_readers_on_a_record():
    r = run_record()
    read = lambda n, rr=r: spec.metric_reader(n)(rr)  # noqa: E731
    assert read("packing_eff.open") == pytest.approx(70.0)
    assert read("packing_eff.backlog") == pytest.approx(70.0)
    assert read("queue_wait_p90_s") == pytest.approx(0.9)
    assert read("mfu") == pytest.approx(10.0)
    assert read("idle_share") == pytest.approx(20.0)
    # 19.7 TFLOP at 197 TFLOP/s is 0.1 s, 8.19 GB at 819 GB/s 0.01 s:
    # compute bound, 0.1 s of 0.2 s of kernel time
    assert read("flash_attention_roofline") == pytest.approx(50.0)


def test_readers_return_nothing_where_there_is_nothing():
    r = run_record(trace=None, peak=None, steps=[], requests=[])
    for name in ("packing_eff.open", "queue_wait_p90_s", "mfu",
                 "idle_share", "flash_attention_roofline"):
        assert spec.metric_reader(name)(r) is None
    r = run_record(trace={"window_s": 1.0, "busy_s": 1.0,
                          "kernel_s": {"flash_attention": 0.0}})
    assert spec.metric_reader("flash_attention_roofline")(r) is None


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
