"""The traffic generator: deterministic per seed, different across seeds,
the same work in the same order for every seed, and arrival processes
found by name."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import generator, spec

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
PROGRAM = spec.load_module(spec.HERE / "configs" / "dit_program.py")
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
ARRIVALS = sorted(p.stem for p in generator.ARRIVALS.glob("*.py")
                  if p.stem != "__init__")
BIG = 2 ** 31 + 12345        # seeds beyond 32 signed bits


def draw(rng, n):
    """Class labels of a 1000-class configuration."""
    return PROGRAM.draw_conds({"num_classes": 1000}, rng, n)


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def as_tuples(reqs):
    return [(r.index, r.due_s, r.cond, r.budget, tuple(r.key))
            for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    t = load(mix)
    a = generator.generate(t, draw, BIG, generator.STREAM_WINDOW, 30.0)
    b = generator.generate(t, draw, BIG, generator.STREAM_WINDOW, 30.0)
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_and_streams_differ(mix):
    t = load(mix)
    a = generator.generate(t, draw, BIG, generator.STREAM_WINDOW, 30.0)
    b = generator.generate(t, draw, BIG + 1, generator.STREAM_WINDOW, 30.0)
    c = generator.generate(t, draw, BIG, generator.STREAM_WARMUP, 30.0)
    assert [r.cond for r in a] != [r.cond for r in b]
    assert [tuple(r.key) for r in a] != [tuple(r.key) for r in c]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    """The same tiers and due times, in the same order, for every seed:
    the mix's ``order_seed`` orders them."""
    t = load(mix)
    a = generator.generate(t, draw, 7, generator.STREAM_WINDOW, 30.0)
    b = generator.generate(t, draw, BIG, generator.STREAM_WINDOW, 30.0)
    assert [r.budget for r in a] == [r.budget for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert sorted({r.budget for r in a}) == sorted(
        float(b) for b, _w in t["tiers"])


def test_order_seed_fixes_the_schedule_not_the_values():
    t = dict(load("tiers-backlog"), order_seed=5)
    a = generator.generate(t, draw, 7, generator.STREAM_WINDOW, 30.0)
    b = generator.generate(t, draw, BIG, generator.STREAM_WINDOW, 30.0)
    assert [r.budget for r in a] == [r.budget for r in b]
    assert [r.cond for r in a] != [r.cond for r in b]
    u = dict(t, order_seed=6)
    c = generator.generate(u, draw, 7, generator.STREAM_WINDOW, 30.0)
    assert [r.budget for r in a] != [r.budget for r in c]


def test_tier_counts_are_exact_shares():
    tiers = [[0.4, 1], [0.6, 2], [1.0, 1]]
    assert generator.tier_counts(tiers, 120) == [30, 60, 30]
    assert sum(generator.tier_counts(tiers, 121)) == 121


def test_poisson_rate_and_check_sample():
    t = load("tiers-open")
    reqs = generator.generate(t, draw, 3, generator.STREAM_WINDOW, 40.0)
    assert len(reqs) == round(t["rate_per_s"] * 40.0)
    assert all(b.due_s >= a.due_s for a, b in zip(reqs, reqs[1:]))
    sample = generator.sample_for_check(t, reqs, 3)
    assert sorted({r.budget for r in sample}) == sorted(
        float(b) for b, _w in t["tiers"])
    assert len(sample) == t["sample_per_tier"] * len(t["tiers"])
    assert [r.index for r in sample] == [
        r.index for r in generator.sample_for_check(t, reqs, 3)]


def test_poisson_gaps_are_one_set_of_quantiles():
    """Another ``order_seed`` reorders the same gaps; each order leaves
    one of them out, after the last arrival."""
    t = load("tiers-open")
    u = dict(t, order_seed=t["order_seed"] + 1)
    a = generator.generate(t, draw, 7, generator.STREAM_WINDOW, 30.0)
    b = generator.generate(u, draw, 7, generator.STREAM_WINDOW, 30.0)
    gaps = lambda rs: set(np.diff([r.due_s for r in rs]).round(9))  # noqa
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert len(gaps(a) & gaps(b)) >= len(a) - 3
    assert abs(a[-1].due_s - 30.0) < 30.0 * 0.25


@pytest.mark.parametrize("name", ARRIVALS)
def test_arrival_process_found_by_name(name):
    process = generator.arrival({"arrival": name})
    assert callable(process.count) and callable(process.offsets)
    assert hasattr(process.Loop, "warm_pass")
    assert hasattr(process.Loop, "window")
    assert hasattr(process.Loop, "outcome")


def test_unknown_arrival_is_refused():
    t = dict(load("tiers-open"), arrival="no_such_process")
    with pytest.raises(ValueError, match="unknown arrival"):
        generator.generate(t, draw, 7, generator.STREAM_WINDOW, 30.0)

