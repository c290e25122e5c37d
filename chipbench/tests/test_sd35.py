"""sd35-medium's program against its plain reference at a test size on
the CPU: three MMDiT-X blocks (the first dual), d = 64 as 4 heads of 16,
a 16x16x16 latent (64 image tokens at patch 2, 16 at the weak patch 4),
12 text tokens, seeded weights, T = 4 flow Euler steps at CFG 4.5.

Tolerance: the worst relative RMS error of x0 against the float32
reference is at most 0.035. The program computes in bfloat16 (weights
and activations), which reads 0.010-0.012 here; the reference with
float8 operands reads 0.091, and so do programs that leave out part of
the block: without QK RMS-norm 0.11, without the dual blocks' image-only
attention 0.38. The limit sits about 3x above bfloat16's reading and
below every one of those."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import spec

FAMILY = Path(__file__).resolve().parent / "sd35_family"
CONFIG = FAMILY / "tiny-sd35.json"
TOL = 0.035
SEED = 2 ** 32 + 77
SAMPLER = {"T": 4, "solver": "flow_euler", "guidance_scale": 4.5}


def modules():
    cfg = json.loads(CONFIG.read_text())
    return (cfg, *(spec.config_module(CONFIG, cfg[k])
                   for k in ("program", "reference", "work")))


@pytest.fixture(scope="module")
def tiny():
    cfg, program, reference, work = modules()
    params = reference.make_params(cfg, SEED)
    rng = np.random.default_rng(3)
    conds = program.draw_conds(cfg, rng, 4)
    keys = rng.integers(0, 2 ** 32, size=(4, 2), dtype=np.uint32)
    budgets = [1.0, 0.4, 0.6, 1.0]
    ref = {i: reference.sample(params, cfg, keys[i:i + 1], [conds[i]],
                               work.step_modes(cfg, 4, budgets[i]),
                               SAMPLER)[0]
           for i in range(4)}
    return dict(cfg=cfg, program=program, reference=reference, work=work,
                params=params, conds=conds, keys=keys, budgets=budgets,
                ref=ref)


def serve(t, which):
    """x0 of requests ``which``, submitted together to one engine."""
    traffic = {"tiers": [[b, 1] for b in sorted(set(t["budgets"]))],
               "sampler": SAMPLER, "policy": "fifo"}
    eng = t["program"].build(t["cfg"], traffic, t["params"],
                             jax.devices()[:1])
    rid = {eng.submit(cond=t["conds"][i], budget=t["budgets"][i],
                      key=t["keys"][i]): i for i in which}
    return {rid[r.request.id]: np.asarray(r.x0, np.float64)
            for r in eng.run()}


def worst(t, out):
    return max(np.sqrt(np.mean((x - t["ref"][i]) ** 2)
                       / np.mean(t["ref"][i] ** 2)) for i, x in out.items())


@pytest.mark.parametrize("which", [(0,), (1,), (0, 1, 2, 3)],
                         ids=["powerful", "weak", "mixed-pack"])
def test_engine_matches_the_reference(tiny, which):
    out = serve(tiny, which)
    assert sorted(out) == sorted(which)
    assert worst(tiny, out) <= TOL


def test_without_qk_norm_the_program_fails_the_tolerance(tiny, monkeypatch):
    from repro.models import dit as dit_mod
    monkeypatch.setattr(dit_mod, "_qk_norm", lambda x, scale, eps=1e-6: x)
    assert worst(tiny, serve(tiny, (0, 1, 2, 3))) > TOL


def test_without_attn2_the_program_fails_the_tolerance(tiny, monkeypatch):
    from repro.core import packing
    block = packing._packed_joint_block

    def no_attn2(p, *a, **k):
        x = {n: v for n, v in p["x"].items() if n not in ("attn2", "ada2")}
        return block({**p, "x": x}, *a, **k)
    monkeypatch.setattr(packing, "_packed_joint_block", no_attn2)
    assert worst(tiny, serve(tiny, (0, 1, 2, 3))) > TOL


def test_float8_reference_fails_the_tolerance(tiny):
    low = {i: tiny["reference"].sample(
        tiny["params"], tiny["cfg"], tiny["keys"][i:i + 1],
        [tiny["conds"][i]],
        tiny["work"].step_modes(tiny["cfg"], 4, tiny["budgets"][i]),
        SAMPLER, precision="fp8")[0] for i in range(4)}
    assert worst(tiny, low) > TOL


def test_seeded_weights_have_the_programs_layout(tiny):
    mcfg = tiny["program"].model_config(tiny["cfg"])
    spec.load_module(spec.HERE / "configs" / "dit_program.py").check_layout(
        tiny["params"], mcfg)
    assert set(tiny["params"]) >= {"dual_blocks", "blocks", "last_block",
                                   "null_text", "context_embed"}


FULL = json.loads((spec.HERE / "configs" / "sd35-medium.json").read_text())


@pytest.mark.parametrize("budget,weak", [(0.4, 31), (0.6, 21), (1.0, 0)])
def test_step_schedule_matches_the_program(budget, weak):
    """At the published sizes and T = 40 the program's budget → weak-step
    schedule (``pipeline/plan.py`` over ``core/scheduler.dit_nfe_flops``)
    is the work module's."""
    from repro.pipeline import SamplingPlan
    _cfg, program, _ref, work = modules()
    plan = SamplingPlan(T=40, budget=budget, solver="flow_euler",
                        guidance_scale=4.5)
    mcfg = program.model_config(FULL)
    steps = [m for m, n in plan.resolve_schedule(mcfg).phases
             for _ in range(n)]
    assert work.step_modes(FULL, 40, budget) == steps
    assert steps.count(1) == weak


def test_program_cost_model_counts_the_needed_work():
    """The program's dense per-forward FLOPs are the work module's less
    the timestep MLP, which the program's count leaves out: about 11.3
    TFLOP at 4,096 image tokens and 2.45 at 1,024."""
    from repro.core.scheduler import dit_nfe_flops
    _cfg, program, _ref, work = modules()
    mcfg = program.model_config(FULL)
    d = mcfg.d_model
    for mode, approx in ((0, 11.3e12), (1, 2.45e12)):
        need = work.nfe_flops(FULL, mode)
        assert need - dit_nfe_flops(mcfg, mode) == 2 * (256 * d + d * d)
        assert need == pytest.approx(approx, rel=0.02)


def test_conditions_are_bf16_text_encoder_outputs():
    cfg, program, _ref, _work = modules()
    (ctx, pooled), = program.draw_conds(cfg, np.random.default_rng(0), 1)
    assert ctx.shape == (12, 32) and pooled.shape == (24,)
    assert str(ctx.dtype) == str(pooled.dtype) == "bfloat16"
    # CLIP's rows carry clip_width features, zero-padded to context_dim
    assert not np.asarray(ctx[:5, 24:], np.float32).any()
    assert np.asarray(ctx[5:, 24:], np.float32).any()


def test_text_stream_share_reads_the_context_scope():
    """Own device time under ``dit_block/context`` over that under
    ``dit_block``; nothing where no op ran there, or with no trace."""
    from chipbench import devtrace, enginetrace
    ms = 1_000_000
    body = "jit(joint_step)/jit(main)/while/body/dit_block"
    ops = [["fusion.1", 0, 3 * ms, body + "/context/dot_general"],
           ["fusion.2", 3 * ms, 5 * ms, body + "/mlp/dot_general"],
           ["flash_attention.3", 8 * ms, 2 * ms,
            body + "/attn/joint_attn/pallas_call"],
           ["fusion.4", 10 * ms, 1 * ms, "jit(joint_step)/embed/conv"]]
    trace = {"device_ops": [o[:3] for o in ops],
             "op_names": [o[3] for o in ops],
             "host_spans": [["chipbench.window", 0, 12 * ms]],
             "engine_spans": [["engine.step", 0, 12 * ms]]}

    def read(tr):
        red = devtrace.reduce(tr)
        red["engine"] = enginetrace.reduce(tr)
        return spec.metric_reader("text_stream_share")({"trace": red})
    assert read(trace) == pytest.approx(30.0)
    trace["op_names"][0] = body + "/adaln/dot_general"
    assert read(trace) is None
    assert spec.metric_reader("text_stream_share")({"trace": None}) is None
