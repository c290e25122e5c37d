"""Unified pipeline API: plan validation + FLOPs golden tests, budget
solving, baseline equivalence, compile-once cache behaviour, the adaptive
path's FLOPs ledger, and the DiT serving driver (DESIGN.md §pipeline)."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FlexiSchedule, GuidanceConfig, flexify, make_eps_fn,
                        relative_compute, schedule_flops)
from repro.core.scheduler import dit_nfe_flops, lora_nfe_overhead
from repro.diffusion import sampler, schedule as sch
from repro.pipeline import (AdaptiveBudget, FlexiPipeline, SamplingPlan,
                            solve_t_weak)

pytestmark = pytest.mark.tier1

T = 10
N = 4


@pytest.fixture(scope="module")
def flexi(tiny_dit_cfg, trained_like_dit):
    fparams, fcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)])
    return fparams, fcfg, sch.linear_schedule(100)


@pytest.fixture(scope="module")
def pipe(flexi):
    fparams, fcfg, sched = flexi
    return FlexiPipeline(fparams, fcfg, sched)


# ---------------------------------------------------------------------------
# SamplingPlan: FLOPs golden tests + budget solving


def test_plan_flops_matches_schedule_flops(flexi):
    _, fcfg, _ = flexi
    fs = FlexiSchedule.weak_first(T, 6)
    plan = SamplingPlan(T=T, budget=fs, guidance_scale=1.5)
    assert plan.flops(fcfg) == pytest.approx(
        schedule_flops(fcfg, fs, cfg_scale_active=True))
    assert plan.relative_compute(fcfg) == pytest.approx(
        relative_compute(fcfg, fs))
    # unguided: one NFE per step
    plain = SamplingPlan(T=T, budget=fs, guidance_scale=0.0)
    assert plain.flops(fcfg) == pytest.approx(
        schedule_flops(fcfg, fs, cfg_scale_active=False))
    # batch scaling
    assert plan.flops(fcfg, batch=7) == pytest.approx(7 * plan.flops(fcfg))
    # 2nd-order solvers evaluate the model twice per step
    dpm2 = SamplingPlan(T=T, budget=fs, guidance_scale=1.5, solver="dpm2")
    assert dpm2.flops(fcfg) == pytest.approx(2 * plan.flops(fcfg))


def test_plan_flops_unmerged_lora(tiny_dit_cfg, trained_like_dit):
    _, lcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)],
                      lora_rank=4)
    fs = FlexiSchedule.weak_first(T, 6)
    merged = SamplingPlan(T=T, budget=fs, guidance_scale=1.5, lora="merged")
    unmerged = SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                            lora="unmerged")
    assert unmerged.flops(lcfg) == pytest.approx(
        schedule_flops(lcfg, fs, cfg_scale_active=True, lora_unmerged=True))
    overhead = unmerged.flops(lcfg) - merged.flops(lcfg)
    # 6 weak guided steps → 12 weak NFEs paying the adapter overhead
    assert overhead == pytest.approx(12 * lora_nfe_overhead(lcfg, 1))


def test_fraction_budget_solves_cheapest_t_weak(flexi):
    _, fcfg, _ = flexi
    target = 0.6
    plan = SamplingPlan(T=T, budget=target, guidance_scale=1.5)
    fs = plan.resolve_schedule(fcfg)
    t_weak = fs.phases[0][1]
    assert relative_compute(fcfg, fs) <= target
    # fewest weak steps: one step fewer must miss the target
    assert t_weak >= 1
    assert relative_compute(
        fcfg, FlexiSchedule.weak_first(T, t_weak - 1)) > target
    assert solve_t_weak(fcfg, T, target) == t_weak
    # trivial budgets
    assert SamplingPlan(T=T, budget=1.0).resolve_schedule(fcfg).phases[0][1] == 0
    # impossible budgets are rejected up front
    with pytest.raises(ValueError, match="floor"):
        SamplingPlan(T=T, budget=0.05).validate(fcfg)


def test_plan_validation_errors(flexi):
    _, fcfg, _ = flexi
    with pytest.raises(ValueError, match="solver"):
        SamplingPlan(T=T, solver="euler")
    with pytest.raises(ValueError, match="fraction"):
        SamplingPlan(T=T, budget=1.5)
    with pytest.raises(ValueError, match="covers"):
        SamplingPlan(T=T, budget=FlexiSchedule.weak_first(T + 2, 1))
    with pytest.raises(ValueError, match="adaptive"):
        SamplingPlan(T=T, budget=AdaptiveBudget(), solver="dpm2")
    with pytest.raises(ValueError, match="unguided"):
        SamplingPlan(T=T, solver="flow_heun", guidance_scale=1.5)
    with pytest.raises(ValueError, match="vanilla CFG"):
        SamplingPlan(T=T, solver="flow_euler", guidance_scale=1.5,
                     guidance_kind="weak_cond")
    with pytest.raises(ValueError, match="modes"):
        SamplingPlan(T=T, weak_mode=3).validate(fcfg)
    with pytest.raises(ValueError, match="LoRA"):
        SamplingPlan(T=T, lora="unmerged").validate(fcfg)


# ---------------------------------------------------------------------------
# FlexiPipeline: baseline equivalence + compile-once cache


def test_t_weak_zero_matches_all_powerful_baseline(flexi, pipe):
    """budget=1.0 (→ T_weak=0) must reproduce the hand-wired all-powerful
    CFG run bit-for-bit (same key derivation)."""
    fparams, fcfg, sched = flexi
    key = jax.random.PRNGKey(42)
    res = pipe.sample(SamplingPlan(T=T, budget=1.0, guidance_scale=1.5,
                                   solver="ddim"), N, key)
    # manual wiring (the pre-pipeline call-site pattern)
    ts = sch.respaced_timesteps(sched.num_steps, T)
    y = jnp.arange(N) % fcfg.dit.num_classes
    null = jnp.full((N,), fcfg.dit.num_classes)
    g = GuidanceConfig(scale=1.5, mode_cond=0, mode_uncond=0)
    eps_fn = make_eps_fn(fparams, fcfg, y, null, g)
    x_T = jax.random.normal(key, (N,) + fcfg.dit.latent_shape)
    want = sampler.sample_phased([(eps_fn, ts)], sched, x_T,
                                 jax.random.fold_in(key, 1), solver="ddim")
    np.testing.assert_allclose(np.asarray(res.x0), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert res.relative_compute == pytest.approx(1.0)


def test_repeat_and_mode_switch_never_recompile(pipe):
    key = jax.random.PRNGKey(0)
    plan_a = SamplingPlan(T=T, budget=1.0, guidance_scale=1.5)
    plan_b = SamplingPlan(T=T, budget=0.6, guidance_scale=1.5)
    pipe.sample(plan_a, N, key)
    base = pipe.cache_stats()
    # same plan, same batch shape → pure cache hit, zero new compilations
    pipe.sample(plan_a, N, jax.random.PRNGKey(1))
    s = pipe.cache_stats()
    assert s["compiled"] == base["compiled"]
    assert s["misses"] == base["misses"]
    assert s["hits"] == base["hits"] + 1
    # budget switch compiles its own runner ONCE...
    pipe.sample(plan_b, N, key)
    s2 = pipe.cache_stats()
    assert s2["compiled"] == base["compiled"] + 1
    # ...and switching back and forth stays compile-free
    pipe.sample(plan_a, N, key)
    pipe.sample(plan_b, N, key)
    assert pipe.cache_stats()["compiled"] == s2["compiled"]


def test_update_params_keeps_compiled_runners(flexi, pipe):
    fparams, _, _ = flexi
    key = jax.random.PRNGKey(3)
    plan = SamplingPlan(T=T, budget=1.0, guidance_scale=1.5)
    pipe.sample(plan, N, key)
    before = pipe.cache_stats()["compiled"]
    bumped = jax.tree.map(lambda x: x * 1.001, fparams)
    pipe.update_params(bumped)
    out = pipe.sample(plan, N, key)
    assert np.isfinite(np.asarray(out.x0)).all()
    assert pipe.cache_stats()["compiled"] == before
    pipe.update_params(fparams)


def test_weak_guidance_plan(flexi, pipe):
    """§3.4 weak-model guidance routes through the pipeline."""
    _, fcfg, _ = flexi
    fs = FlexiSchedule.weak_first(T, 6)
    plan = SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                        guidance_kind="weak_cond")
    res = pipe.sample(plan, N, jax.random.PRNGKey(5))
    assert np.isfinite(np.asarray(res.x0)).all()
    # the powerful phase's guidance NFE runs at the weak mode → cheaper
    # than vanilla CFG on the same schedule
    vanilla = SamplingPlan(T=T, budget=fs, guidance_scale=1.5)
    assert plan.flops(fcfg) < vanilla.flops(fcfg)


def test_flow_solver_plan(pipe):
    fs = FlexiSchedule.weak_first(T, 5)
    plan = SamplingPlan(T=T, budget=fs, solver="flow_euler",
                        guidance_scale=0.0)
    res = pipe.sample(plan, N, jax.random.PRNGKey(6))
    assert res.x0.shape == (N,) + pipe.cfg.dit.latent_shape
    assert np.isfinite(np.asarray(res.x0)).all()


# ---------------------------------------------------------------------------
# Adaptive plans


def test_adaptive_flops_ledger(flexi, pipe):
    """Guided NFEs cost 2 NFEs each; probes are reused, not recomputed."""
    _, fcfg, _ = flexi
    B = 2
    key = jax.random.PRNGKey(7)
    f_w = 2.0 * dit_nfe_flops(fcfg, 1)      # CFG multiplier
    f_p = 2.0 * dit_nfe_flops(fcfg, 0)
    # threshold 0 → first probe switches → 1 weak + 1 powerful probe NFE,
    # then T powerful steps
    plan0 = SamplingPlan(T=T, budget=AdaptiveBudget(threshold=0.0),
                         guidance_scale=1.5)
    r0 = pipe.sample(plan0, B, key)
    assert r0.trace["switch_step"] == 0
    assert r0.flops == pytest.approx(B * (f_w + f_p + T * f_p))
    assert r0.trace["flops_static_powerful"] == pytest.approx(B * T * f_p)
    # threshold ∞ → never switches: T weak steps + ceil(T/2) probes, and
    # every probe's weak ε is REUSED for its step (no extra weak NFEs)
    plan_inf = SamplingPlan(T=T, budget=AdaptiveBudget(threshold=1e9,
                                                       probe_every=2),
                            guidance_scale=1.5)
    r_inf = pipe.sample(plan_inf, B, key)
    assert r_inf.trace["switch_step"] == T
    n_probes = len(range(0, T, 2))
    assert r_inf.flops == pytest.approx(B * (T * f_w + n_probes * f_p))
    assert r_inf.relative_compute < 1.0
    assert np.isfinite(np.asarray(r_inf.x0)).all()
    assert len(r_inf.trace["gaps"]) == n_probes


def test_adaptive_worst_case_bound(flexi):
    _, fcfg, _ = flexi
    plan = SamplingPlan(T=T, budget=AdaptiveBudget(threshold=1e9),
                        guidance_scale=1.5)
    # plan.flops is the never-switch worst case = the actual spend above
    f_w = 2.0 * dit_nfe_flops(fcfg, 1)
    f_p = 2.0 * dit_nfe_flops(fcfg, 0)
    assert plan.flops(fcfg) == pytest.approx(T * f_w + 5 * f_p)


# ---------------------------------------------------------------------------
# LoRA variants through the pipeline


def test_lora_merged_matches_unmerged_sampling(tiny_dit_cfg,
                                               trained_like_dit):
    lparams, lcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)],
                            lora_rank=4)
    # give the adapters non-zero effect so the equivalence is non-trivial
    lora = lparams["blocks"]["lora"]
    lora["attn"]["wq"]["b"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(8), lora["attn"]["wq"]["b"].shape)
    p = FlexiPipeline(lparams, lcfg, sch.linear_schedule(100))
    fs = FlexiSchedule.weak_first(T, 6)
    key = jax.random.PRNGKey(9)
    r_un = p.sample(SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                                 lora="unmerged"), N, key)
    r_me = p.sample(SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                                 lora="merged"), N, key)
    np.testing.assert_allclose(np.asarray(r_un.x0), np.asarray(r_me.x0),
                               atol=1e-4, rtol=1e-4)
    assert r_un.flops > r_me.flops        # unmerged pays the adapter FLOPs


def test_lora_merged_weak_guidance_nfe(tiny_dit_cfg, trained_like_dit):
    """§3.4 weak-model guidance under merged LoRA: the guidance NFE must
    see the merged weak-mode weights (same result as unmerged, and the
    analytic ledger's merged-⇒-no-overhead promise holds at runtime)."""
    lparams, lcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)],
                            lora_rank=4)
    lora = lparams["blocks"]["lora"]
    lora["mlp"]["w_in"]["b"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(12), lora["mlp"]["w_in"]["b"].shape)
    p = FlexiPipeline(lparams, lcfg, sch.linear_schedule(100))
    fs = FlexiSchedule.weak_first(T, 4)
    key = jax.random.PRNGKey(13)
    r_un = p.sample(SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                                 guidance_kind="weak_cond",
                                 lora="unmerged"), N, key)
    r_me = p.sample(SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                                 guidance_kind="weak_cond",
                                 lora="merged"), N, key)
    np.testing.assert_allclose(np.asarray(r_un.x0), np.asarray(r_me.x0),
                               atol=1e-4, rtol=1e-4)


def test_adaptive_unmerged_lora_ledger(tiny_dit_cfg, trained_like_dit):
    """Adaptive plans on unmerged LoRA count the adapter FLOPs per weak NFE."""
    lparams, lcfg = flexify(trained_like_dit, tiny_dit_cfg, [(1, 4, 4)],
                            lora_rank=4)
    p = FlexiPipeline(lparams, lcfg, sch.linear_schedule(100))
    plan = SamplingPlan(T=T, budget=AdaptiveBudget(threshold=1e9,
                                                   probe_every=2),
                        guidance_scale=1.5, lora="unmerged")
    r = p.sample(plan, 2, jax.random.PRNGKey(14))
    f_w = 2.0 * (dit_nfe_flops(lcfg, 1) + lora_nfe_overhead(lcfg, 1))
    f_p = 2.0 * dit_nfe_flops(lcfg, 0)
    assert r.flops == pytest.approx(2 * (T * f_w + 5 * f_p))
    assert r.flops == pytest.approx(plan.flops(lcfg, batch=2))


def test_lora_merged_per_phase_mode(tiny_dit_cfg, trained_like_dit):
    """A schedule using a weak mode other than plan.weak_mode must merge
    THAT mode's adapters (regression: all weak phases used to get the
    plan.weak_mode merge)."""
    lparams, lcfg = flexify(trained_like_dit, tiny_dit_cfg,
                            [(1, 4, 4), (1, 8, 8)], lora_rank=4)
    lora = lparams["blocks"]["lora"]
    lora["attn"]["wq"]["b"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(10), lora["attn"]["wq"]["b"].shape)
    p = FlexiPipeline(lparams, lcfg, sch.linear_schedule(100))
    fs = FlexiSchedule(((2, 4), (0, T - 4)))     # weak phase at mode 2
    key = jax.random.PRNGKey(11)
    r_un = p.sample(SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                                 lora="unmerged"), N, key)
    r_me = p.sample(SamplingPlan(T=T, budget=fs, guidance_scale=1.5,
                                 lora="merged"), N, key)
    np.testing.assert_allclose(np.asarray(r_un.x0), np.asarray(r_me.x0),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Serving driver


def test_serve_dit_smoke(capsys):
    """The DiT serving driver now runs the continuous-batching engine:
    two identical waves (warmup + steady state) of --requests each."""
    from repro.configs import get_config
    from repro.launch.serve import serve_dit
    args = argparse.Namespace(budget=0.6, T=6, train_T=100, solver="ddim",
                              cfg_scale=1.5, requests=3, batch_slots=2,
                              budget_levels="0.6,1.0")
    serve_dit(get_config("dit-xl-2").reduced(), args)
    out = capsys.readouterr().out
    assert "served 6 requests" in out
    assert "[metrics]" in out
    assert "[cache]" in out
