"""What the existing cells read stays as it was before the harness found
its model-specific code through the configuration's modules:
``data/identity.json`` holds the requests, check samples, step schedules,
work counts and tiny weights that the harness gave when it drew class
labels and counted DiT work itself, and these tests recompute them."""
import hashlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import drive, generator, spec

HERE = Path(__file__).resolve().parent
PINNED = json.loads((HERE / "data" / "identity.json").read_text())
CONFIGS = spec.HERE / "configs"
MIXES = ("tiers-open", "tiers-backlog")
DIT_CONFIGS = ("dit-xl-2", "dit-xl-2-512")


def config(name):
    path = CONFIGS / f"{name}.json"
    cfg = json.loads(path.read_text())
    return cfg, spec.config_module(path, cfg["program"]), \
        spec.config_module(path, cfg["work"])


def traffic(mix):
    return json.loads((spec.HERE / "traffic" / f"{mix}.json").read_text())


def rows(reqs):
    return [[r.index, repr(r.due_s), int(r.cond), repr(r.budget),
             [int(k) for k in r.key]] for r in reqs]


def digest(obj):
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", PINNED["seeds"])
def test_requests_and_check_sample_are_unchanged(mix, seed):
    """Due times, budgets, conditions and keys of every stream, drawn
    through each configuration's program module, and the check's sample."""
    t = traffic(mix)
    for name in DIT_CONFIGS:
        cfg, program, _work = config(name)
        draw = lambda rng, n: program.draw_conds(cfg, rng, n)  # noqa: E731
        for stream in PINNED["streams"]:
            reqs = generator.generate(t, draw, seed, stream,
                                      PINNED["seconds"])
            assert digest(rows(reqs)) == \
                PINNED["requests"][f"{mix}/{seed}/{stream}"], (name, stream)
        reqs = generator.generate(t, draw, seed, generator.STREAM_WINDOW,
                                  PINNED["seconds"])
        sample = generator.sample_for_check(t, reqs, seed)
        assert [r.index for r in sample] == \
            PINNED["check_sample"][f"{mix}/{seed}"]


@pytest.mark.parametrize("name", DIT_CONFIGS)
def test_step_schedules_and_work_counts_are_unchanged(name):
    cfg, _program, work = config(name)
    for budget, want in PINNED["configs"][name]["tiers"].items():
        modes = work.step_modes(cfg, 50, float(budget))
        assert modes == want["modes"]
        att = work.request_attention(cfg, modes, True)
        assert repr(work.request_flops(cfg, modes, True)) == \
            want["request_flops"]
        assert repr(att["flops"]) == want["attn_flops"]
        assert repr(att["bytes"]) == want["attn_bytes"]
        assert repr(work.request_block_flops(cfg, modes, True)) == \
            want["request_block_flops"]


@pytest.mark.parametrize("name", DIT_CONFIGS)
def test_window_work_is_unchanged(name):
    """A window with a request finished before it, one finished in it and
    two in flight at its end, one of each tier."""
    cfg, _program, work = config(name)
    t = {"sampler": {"T": 50, "guidance_scale": 1.5}}
    served = []
    for rid, (budget, finish) in enumerate([(1.0, 0.5), (0.4, 2.0),
                                            (0.6, None), (1.0, None)]):
        r = generator.Req(index=rid, due_s=0.0, cond=1, budget=budget,
                          key=None)
        served.append(drive.Served(r, 0.0, 0.0, rid, finish=finish))
    cell = types.SimpleNamespace(config=cfg, work=work)
    got = drive.window_steps(cell, t, served, {1: 7, 2: 3, 3: 0},
                             {2: 41, 3: 20}, 1.0)
    assert {k: repr(v) for k, v in got.items()} == \
        PINNED["configs"][name]["window"]


def test_seeded_weights_are_unchanged():
    import jax
    path = HERE / "data" / "tiny-dit.json"
    cfg = json.loads(path.read_text())
    ref = spec.config_module(path, cfg["reference"])
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(ref.make_params(cfg, PINNED["seeds"][1])):
        h.update(np.asarray(leaf.astype("float32")).tobytes())
    assert h.hexdigest() == PINNED["tiny_weights"]
