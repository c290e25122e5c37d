"""sd35-medium's three modules (``configs/sd35_program.py``,
``sd35_work.py``, ``sd35_reference.py``) run end to end through the
harness's set-up, window and check on the CPU, from a tiny configuration
file with the same keys (``sd35_family/tiny-sd35.json``: three MMDiT-X
blocks, the first dual, 12 text tokens) and a backlog of flow Euler
requests at CFG 4.5; the run comes out correct and its float8 control
does not."""
import sys
from pathlib import Path

import jax
import pytest

from chipbench import check, control, run, spec

FAMILY = Path(__file__).resolve().parent / "sd35_family"
SEED = 2 ** 33 + 11
SECONDS = 4.0
E2E = {"img_per_s": "img/s", "setup_s": "s"}


def family_cell():
    return spec.make_cell(
        "tiny-sd35.flow-backlog", 1, config_path=FAMILY / "tiny-sd35.json",
        traffic_path=FAMILY / "flow-backlog.json",
        limits_path=FAMILY / "limits.json", end_to_end=E2E,
        per_layer={"packing_eff.sd35": "%"})


@pytest.fixture(scope="module")
def prepared():
    src = str(run.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return run.warm(family_cell(), SEED, jax.devices(), SECONDS, peak=None)


def test_cell_finds_the_configurations_modules():
    cell = family_cell()
    for mod in (cell.reference, cell.program, cell.work):
        assert Path(mod.__file__).parent == spec.HERE / "configs"


def test_engine_serves_flow_euler_with_text_conditions(prepared):
    eng = prepared.engine
    assert eng.solver == "flow_euler" and eng.guided
    assert eng.cfg.dit.conditioning == "joint"
    # one request's segment: 64 image tokens and 12 text tokens
    assert eng.menu.row_capacity == 64 + 12


def test_control_is_not_correct(prepared):
    checks = control.control_checks(prepared.cell, prepared.seed,
                                    prepared.params)
    assert check.correct(0, checks) is False


def test_family_run_is_correct(prepared):
    """Runs last: it hands the engine over, as a run does."""
    result = run.finish(prepared, SECONDS, False)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["checks"]["compared"]["value"] >= 1
    assert set(result["metrics"]) == set(E2E)
