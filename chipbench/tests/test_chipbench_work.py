"""The needed-work counts against hand arithmetic, and the budget's step
schedule against the program's own."""
import json
from pathlib import Path

import pytest

from chipbench import spec

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
work = spec.load_module(CONFIGS / "dit_work.py")


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_forward_flops_match_hand_arithmetic():
    """24*N*d^2 + 4*N^2*d per layer: about 237 GFLOP per 256-token
    forward and 58 at 64 tokens."""
    c = cfg("dit-xl-2")
    d, L = 1152, 28
    for mode, n, approx in ((0, 256, 237e9), (1, 64, 58e9)):
        assert work.tokens(c, mode) == n
        hand = L * (24 * n * d * d + 4 * n * n * d)
        assert abs(work.nfe_flops(c, mode) / hand - 1) < 0.01
        assert abs(hand / approx - 1) < 0.01


def test_attention_work_and_intensity():
    d = 1152
    assert work.attention_flops(1024, d) == 4 * 1024 ** 2 * d
    assert work.attention_bytes(1024, d) == 4 * 1024 * d * 2
    # FLOP per byte is n / 2: 512 at 1024 tokens, 128 at 256
    assert work.attention_flops(256, d) / work.attention_bytes(256, d) == 128
    c = cfg("dit-xl-2-512")
    att = work.request_attention(c, [0, 1], guided=True)
    assert att["flops"] == 2 * 28 * (work.attention_flops(1024, d)
                                     + work.attention_flops(256, d))


@pytest.mark.parametrize("name", ["dit-xl-2", "dit-xl-2-512"])
@pytest.mark.parametrize("budget", [0.4, 0.6, 1.0])
def test_step_schedule_matches_the_program(name, budget):
    """The reference serves the same weak/powerful steps that the
    program resolves for a budget."""
    from repro.pipeline import SamplingPlan
    program = spec.load_module(CONFIGS / "dit_program.py")
    c = cfg(name)
    mcfg = program.model_config(c)
    plan = SamplingPlan(T=50, budget=budget, solver="ddim",
                        guidance_scale=1.5)
    phases = plan.resolve_schedule(mcfg).phases
    program = [m for m, n in phases for _ in range(n)]
    assert work.step_modes(c, 50, budget) == program
