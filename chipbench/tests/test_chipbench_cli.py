"""The benchmark command without a chip; and the cells of
BENCHMARK.json held to the benchmark's contract."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_no_tpu_exits_non_zero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2 ** 31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for text in ([c["why"] for c in BENCH["configs"]]
                 + [w["why"] for w in BENCH["workloads"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = spec.load_cell(ROOT, cell)
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer
    for name in c.per_layer:
        assert callable(spec.metric_reader(name))
    lim = c.limits["rel_rms"]
    assert lim["limit"] > 0
    assert callable(c.program.build) and callable(c.program.draw_conds)
    for fn in ("step_modes", "request_flops", "request_attention",
               "request_block_flops"):
        assert callable(getattr(c.work, fn))
    assert callable(c.reference.make_params) and callable(c.reference.sample)
    cfg_file = {x["name"]: x for x in BENCH["configs"]}[
        {w["name"]: w for w in BENCH["workloads"]}[cell]["config"]]["file"]
    assert cfg_file.startswith(BENCH["paths"][0] + "/")
