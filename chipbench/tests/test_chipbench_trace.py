"""The trace reduction: busy and idle time, kernel time, the op ranking
and the idle gaps labelled by the harness's host spans."""
import json
from pathlib import Path

import pytest

from chipbench import devtrace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_op_names_from_hlo_text():
    ev = ("%flash_attention.1 = bf16[64,256,72]{2,1,0} custom-call(s32[16] "
          "%reshape.28), custom_call_target=\"tpu_custom_call\"")
    assert devtrace.op_name(ev) == "flash_attention.1"
    assert devtrace.op_group("flash_attention.1") == "flash_attention"
    assert devtrace.op_group("fusion") == "fusion"


def test_synthetic_trace():
    trace = {
        "device_ops": [
            ["fusion.1", -5 * MS, 10 * MS],          # clipped to [0, 5)
            ["flash_attention.3", 4 * MS, 3 * MS],   # overlaps the first
            ["while.7", 18 * MS, 14 * MS],           # a loop around ...
            ["fusion.2", 20 * MS, 10 * MS],          # ... its body op
            ["flash_attention.4", 95 * MS, 10 * MS],  # clipped to 5 ms
        ],
        "host_spans": [
            ["chipbench.window", 0, 100 * MS],
            ["chipbench.step", 6 * MS, 4 * MS],
            ["chipbench.wait_arrival", 10 * MS, 10 * MS],
            ["chipbench.step", 30 * MS, 70 * MS],
        ],
    }
    r = devtrace.reduce(trace)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [0, 7) + [18, 32) + [95, 100) = 26 ms
    assert r["busy_s"] == pytest.approx(0.026)
    assert r["kernel_s"]["flash_attention"] == pytest.approx(0.008)
    ops = dict(r["device_ops"])
    # fusion.1 keeps 5 ms, less the 1 ms that flash_attention.3 overlaps
    assert ops["fusion"] == pytest.approx(0.014)
    assert ops["flash_attention"] == pytest.approx(0.008)
    # the loop's own time is what its body leaves: 14 - 10 ms
    assert ops["while"] == pytest.approx(0.004)
    # gaps: [7, 18) mostly waiting for arrivals; [32, 95) in a step
    gaps = dict(r["idle_gaps"])
    assert gaps["chipbench.wait_arrival"] == pytest.approx(0.011)
    assert gaps["chipbench.step"] == pytest.approx(0.063)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.026)


def brute_force(trace, kernel="flash_attention", step_ns=1000):
    """Busy and kernel time by marking every microsecond."""
    w = next(s for s in trace["host_spans"] if s[0] == devtrace.WINDOW_SPAN)
    w0, w1 = w[1], w[1] + w[2]
    n = (w1 - w0) // step_ns
    busy = bytearray(n)
    k_ns = 0
    for name, s, d in trace["device_ops"]:
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        if devtrace.op_group(name) == kernel:
            k_ns += b - a
        for i in range((a - w0) // step_ns, -(-(b - w0) // step_ns)):
            busy[min(i, n - 1)] = 1
    return sum(busy) * step_ns / 1e9, k_ns / 1e9, (w1 - w0) / 1e9


def test_recorded_chip_trace():
    """40 ms of a traced run of dit-xl-2.tiers-open on one TPU v5e chip:
    the end of a wait for arrivals, then packed steps. Busy, idle and
    kernel time agree with marking every microsecond, and the ops' own
    times add up to the busy time, nested ``while`` loops and all."""
    trace = json.loads((DATA / "trace_excerpt.json").read_text())
    r = devtrace.reduce(trace)
    busy, kern, win = brute_force(trace)
    assert r["window_s"] == pytest.approx(win)
    assert r["busy_s"] == pytest.approx(busy, abs=2e-5 * len(
        trace["device_ops"]))
    assert r["kernel_s"]["flash_attention"] == pytest.approx(kern)
    assert r["kernel_s"]["flash_attention"] > 0
    # recorded: 17.4 ms busy of 40, 3.0 ms of it in flash_attention
    assert r["busy_s"] == pytest.approx(0.017423646)
    assert r["kernel_s"]["flash_attention"] == pytest.approx(0.002950526)
    assert "flash_attention" in dict(r["device_ops"])
    own = devtrace.window_ops(trace).own
    assert min(own) >= 0
    assert sum(own) / 1e9 == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert max(gaps, key=gaps.get) == "chipbench.wait_arrival"
