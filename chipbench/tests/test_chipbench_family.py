"""A configuration of another family is a cell of new files. The
test-only family in ``ddpm_family/`` names its own program module (the
engine on a cosine noise schedule, served by unguided ancestral DDPM,
with class labels drawn from a Zipf law), work module and reference. It
runs through the harness's set-up, window and check on the CPU and comes
out correct, its control does not, and no shared harness file names it.
"""
import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import check, control, generator, run, spec

FAMILY = Path(__file__).resolve().parent / "ddpm_family"
SEED = 2 ** 33 + 7
E2E = {"latency_p50_s": "s", "latency_p90_s": "s", "img_per_s": "img/s",
       "setup_s": "s"}
#: the shared harness: every file the benchmark ships but its tests
SHARED = sorted(p for p in spec.HERE.rglob("*")
                if p.is_file() and p.suffix in (".py", ".json")
                and "tests" not in p.relative_to(spec.HERE).parts)
GENERIC = ("drive", "generator", "loops", "check", "run", "control")


def family_cell():
    return spec.make_cell(
        "tiny-ddpm.open", 1, config_path=FAMILY / "tiny-ddpm.json",
        traffic_path=FAMILY / "ddpm-open.json",
        limits_path=FAMILY / "limits.json", end_to_end=E2E,
        per_layer={"queue_wait_p90_s": "s"})


@pytest.fixture(scope="module")
def prepared():
    src = str(run.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return run.warm(family_cell(), SEED, jax.devices(), 1.5, peak=None)


def test_cell_finds_the_familys_own_modules():
    cell = family_cell()
    for mod in (cell.reference, cell.program, cell.work):
        assert Path(mod.__file__).parent == FAMILY
    reqs = generator.generate(cell.traffic, cell.draw_conds, SEED,
                              generator.STREAM_WINDOW, 200.0)
    counts = np.bincount([r.cond for r in reqs], minlength=10)
    # Zipf over 10 classes: class 0 a third of the draws, class 9 a thirtieth
    assert counts[0] > 3 * counts[9] > 0


def test_builder_takes_its_own_schedule_and_solver(prepared):
    from repro.diffusion import schedule as sch
    eng = prepared.engine
    assert eng.solver == "ddpm" and not eng.guided
    np.testing.assert_array_equal(eng.pipe.sched.betas,
                                  sch.cosine_schedule(1000, 0.008).betas)


def test_control_is_not_correct(prepared):
    checks = control.control_checks(prepared.cell, prepared.seed,
                                    prepared.params)
    assert check.correct(0, checks) is False
    assert checks["rel_rms_max"]["value"] > checks["rel_rms_max"]["limit"]


def test_family_run_is_correct(prepared):
    """Runs last: it hands the engine over, as a run does."""
    result = run.finish(prepared, 1.5, False)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["checks"]["compared"]["value"] == 3
    assert set(result["metrics"]) == set(E2E)


def test_no_shared_harness_file_names_the_family():
    cfg = json.loads((FAMILY / "tiny-ddpm.json").read_text())
    names = [cfg[k] for k in ("name", "reference", "program", "work")] + [
        "ddpm-open", FAMILY.name]
    assert SHARED
    for path in SHARED:
        text = path.read_text()
        assert not any(n in text for n in names), path


@pytest.mark.parametrize("module", GENERIC)
def test_shared_harness_names_no_model_key(module):
    text = (spec.HERE / f"{module}.py").read_text()
    assert not re.search(r"num_classes|learn_sigma|linear_schedule|d_ff|ddim",
                         text)


def test_dit_builder_refuses_more_than_one_chip():
    path = spec.HERE / "configs" / "dit-xl-2.json"
    cfg = json.loads(path.read_text())
    program = spec.config_module(path, cfg["program"])
    traffic = json.loads((spec.HERE / "traffic" / "tiers-open.json")
                         .read_text())
    with pytest.raises(ValueError, match="one chip"):
        program.build(cfg, traffic, params=None, devices=[object()] * 4)
