"""Pieces the joint-attention (MMDiT, SD3) configurations brought into
the DiT: QK RMS-norm wherever a config states it, SD3's cropped sin-cos
positions, the rectified-flow sigma ladder and the segment length."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.diffusion.flow import FlowSchedule, euler_step
from repro.models import dit as dit_mod


@pytest.mark.parametrize("arch", ["t2i-transformer", "video-dit"])
def test_qk_norm_reaches_the_block(arch):
    """Both declare ``qk_norm``: the block carries per-head scales and
    its output moves with them."""
    cfg = get_config(arch).reduced()
    assert cfg.attn.qk_norm
    params = dit_mod.init_dit(cfg, jax.random.PRNGKey(0))
    attn = params["blocks"]["attn"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (
        cfg.num_layers, cfg.attn.head_dim)
    bp = jax.tree.map(lambda a: a[0], params["blocks"])
    # non-zero adaLN so the attention branch reaches the output
    bp["ada"]["w"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                             bp["ada"]["w"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg.d_model))
    c = jax.random.normal(jax.random.PRNGKey(3), (1, cfg.d_model))

    def out(q_scale):
        p = {**bp, "attn": {**bp["attn"], "q_norm": q_scale}}
        return dit_mod.dit_block_apply(p, x, c, cfg, attn_backend="dense")
    ones = jnp.ones(cfg.attn.head_dim)
    assert not np.allclose(out(ones), out(3.0 * ones), atol=1e-4)


def test_qk_norm_is_an_rms_norm_per_head():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 16)) * 3.0
    scale = jnp.linspace(0.5, 2.0, 16)
    y = dit_mod._qk_norm(x, scale)
    rms = np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True))
    np.testing.assert_allclose(y, np.asarray(x) / rms * np.asarray(scale),
                               rtol=1e-4)


def _diffusers_pos_embed(d, max_size, base, gh, gw):
    """diffusers' get_2d_sincos_pos_embed(d, max_size, base_size=base)
    and PatchEmbed.cropped_pos_embed(gh, gw), written out."""
    grid_h = np.arange(max_size) / (max_size / base)
    grid_w = np.arange(max_size) / (max_size / base)
    grid = np.stack(np.meshgrid(grid_w, grid_h), 0).reshape(
        2, 1, max_size, max_size)

    def one_d(dd, pos):
        omega = 1.0 / 10000 ** (np.arange(dd // 2) / (dd / 2.0))
        out = np.einsum("m,d->md", pos.reshape(-1), omega)
        return np.concatenate([np.sin(out), np.cos(out)], 1)
    emb = np.concatenate([one_d(d // 2, grid[0]), one_d(d // 2, grid[1])],
                         1).reshape(max_size, max_size, d)
    top, left = (max_size - gh) // 2, (max_size - gw) // 2
    return emb[top:top + gh, left:left + gw].reshape(-1, d)


def test_cropped_positions_are_sd3s():
    ls, d = (1, 16, 16, 16), 32
    got = dit_mod._cropped_pos_embed_np(ls, (1, 2, 2), (1, 2, 2), d, 24)
    np.testing.assert_allclose(got, _diffusers_pos_embed(d, 24, 8, 8, 8),
                               atol=1e-6)
    # a patch-4 token sits at the mean position of the patch-2 tokens it
    # covers: token (0, 0) at grid index 8.5 of 24 (crop offset 8), in
    # units of 24 / 8 — index 17 of a 48 grid at half the step
    weak = dit_mod._cropped_pos_embed_np(ls, (1, 4, 4), (1, 2, 2), d, 24)
    assert weak.shape == (16, d)
    half = _diffusers_pos_embed(d, 48, 8, 48, 48)
    np.testing.assert_allclose(weak[0], half[17 * 48 + 17], atol=1e-6)
    np.testing.assert_allclose(weak[5], half[21 * 48 + 21], atol=1e-6)


def test_flow_sigmas_are_diffusers_shifted_ladder():
    s = FlowSchedule(1000, 3.0).sigmas(40)
    assert s.shape == (40,) and s[0] == pytest.approx(1.0)
    assert np.all(np.diff(s) < 0)
    lo = 3 * 0.001 / (1 + 2 * 0.001)
    assert s[-1] == pytest.approx(3 * lo / (1 + 2 * lo))
    x = jnp.ones((2, 1, 2, 2, 1))
    v = jnp.full_like(x, 2.0)
    y = euler_step(x, v, jnp.asarray([1.0, 0.5]), jnp.asarray([0.5, 0.0]))
    np.testing.assert_allclose(y[:, 0, 0, 0, 0], [0.0, 0.0])


def test_joint_segments_carry_their_text_tokens():
    cfg = get_config("dit-xl-2")
    joint = dataclasses.replace(cfg, dit=dataclasses.replace(
        cfg.dit, conditioning="joint", text_len=333, pooled_dim=8))
    assert dit_mod.segment_tokens(cfg, 0) == dit_mod.tokens_for_mode(cfg, 0)
    assert dit_mod.segment_tokens(joint, 1) == 64 + 333
    with pytest.raises(ValueError, match="packed step"):
        dit_mod.dit_forward({}, jnp.zeros((1, 1, 32, 32, 4)),
                            jnp.zeros(1), None, joint)


def _joint_step_op_names():
    """The HLO ``op_name`` of every instruction of a tiny joint model's
    compiled packed step (a powerful and a weak segment under CFG)."""
    import json
    import re

    from chipbench import spec
    from repro.diffusion.flow import FlowSchedule
    from repro.pipeline.packed import PackLayout, make_packed_step_fn
    path = spec.HERE / "tests" / "sd35_family" / "tiny-sd35.json"
    cfg = spec.config_module(path, "sd35_program").model_config(
        json.loads(path.read_text()))
    layout = PackLayout(groups=((0, 1), (1, 1)), guided=True)
    step = make_packed_step_fn(cfg, FlowSchedule(1000, 3.0), layout,
                               solver="flow_euler", guidance_scale=4.5)
    params = jax.eval_shape(lambda k: dit_mod.init_dit(cfg, k),
                            jax.random.PRNGKey(0))
    dit = cfg.dit

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    groups = layout.groups
    hlo = jax.jit(step).lower(
        params, tuple(sds((n,) + dit.latent_shape) for _m, n in groups),
        tuple(sds((1, 3, n)) for _m, n in groups),
        tuple(sds((1, n, 2), jnp.uint32) for _m, n in groups),
        tuple((sds((n, dit.text_len, dit.text_dim), jnp.bfloat16),
               sds((n, dit.pooled_dim), jnp.bfloat16)) for _m, n in groups),
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', hlo))


def test_joint_step_names_its_text_stream_and_attentions():
    """The packed step of a joint model carries the scopes a trace
    reduction reads: the text stream under ``dit_block/context``, the
    joint and the image-only attention under ``attn``, QK-norm inside
    them."""
    names = _joint_step_op_names()
    for scope in ("dit_block/context/", "dit_block/attn/joint_attn/",
                  "dit_block/attn/attn2/", "/qk_norm/"):
        assert any(scope in n for n in names), scope
    assert any("dit_block/context/qk_norm/" in n for n in names)


def test_text_stream_is_what_the_block_scopes_leave():
    """``text_stream_share`` reads the ops whose kept scope path is
    ``dit_block`` alone as the text stream's: in the joint block each of
    them is under ``context``, and each op under ``context`` has that
    path."""
    from chipbench import enginetrace
    names = _joint_step_op_names()
    bare = [n for n in names
            if enginetrace.scope_path(n) == enginetrace.BLOCK_SCOPE]
    assert bare and all("dit_block/context/" in n for n in bare), bare
    assert all(enginetrace.scope_path(n) == enginetrace.BLOCK_SCOPE
               for n in names if "dit_block/context/" in n)
