"""Compile-only rehearsal of the chip's main-path programs for a described
TPU v5e (2x2 topology): nothing here runs, it only asks the TPU compiler
to accept the programs at the widths they are served at.

Mosaic refuses what the Pallas interpreter accepts (blocks whose last two
dims break the (8, 128) tiling, too much VMEM), and XLA refuses a step
that does not fit the chip's 16 GB. These tests catch both without a
chip. The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and the test runner's
workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.diffusion import schedule as sch
from repro.kernels.attention import flash_attention as fa_mod
from repro.kernels.attention.flash_attention import flash_attention
from repro.launch.roofline import device_peaks
from repro.models import dit as dit_mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Code that picks interpret mode from ``jax.default_backend()`` sees
    the CPU here; steer the flash kernel to its compiled form. Traces
    cached under the other mode are dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(fa_mod, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


# (B, S, H, hd, segmented): dit-xl-2 rows of 256 tokens (one patch-2
# image, or four patch-4 ones), a lone 64-token weak row shorter than one
# 128-token tile, DiT-XL/2-512's 1,024-token rows, t2i-transformer's 4,096
# and 1,024 tokens at head dim 128, video-dit's 33,792 (a 66 x 66
# block map in SMEM), and sd35-medium's 4,429-token rows at head dim 64
# (a request's 4,096 image and 333 text tokens; not lane-aligned, so
# padded to the tiles with padding ids). Segment ids are traced data, so
# one compile covers every packing.
FLASH_CASES = {
    "xl2-packed-256": (8, 256, 16, 72, True),
    "xl2-dense-256": (8, 256, 16, 72, False),
    "xl2-packed-64": (8, 64, 16, 72, True),
    "xl2-dense-64": (8, 64, 16, 72, False),
    "xl2-512-packed-1024": (8, 1024, 16, 72, True),
    "xl2-512-dense-1024": (8, 1024, 16, 72, False),
    "t2i-packed-4096": (2, 4096, 16, 128, True),
    "t2i-dense-4096": (2, 4096, 16, 128, False),
    "t2i-packed-1024": (2, 1024, 16, 128, True),
    "video-packed-33792": (1, 33792, 24, 128, True),
    "sd35-packed-4429": (2, 4429, 24, 64, True),
}


@pytest.mark.parametrize("case", list(FLASH_CASES))
def test_flash_kernel_compiles_for_v5e(one_chip, compiled_kernels, case):
    B, S, H, hd, segmented = FLASH_CASES[case]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qkv = [sds((B, S, H, hd), jnp.bfloat16)] * 3
    if not segmented:
        fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=False))
        lowered = fn.lower(*qkv)
    else:
        fn = jax.jit(lambda q, k, v, s: flash_attention(
            q, k, v, causal=False, segment_ids=s))
        lowered = fn.lower(*qkv, sds((B, S), jnp.int32))
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


# (B, S, H, K, hd, window, softcap): causal language-model prefills that
# run the same kernel through models/attention.py — t2i-transformer's
# widths, gemma3-4b's sliding-window local layers (8 query heads over 4
# kv heads of 256), gemma2-9b's soft-capped window of 4,096.
FLASH_CAUSAL_CASES = {
    "causal-4096": (2, 4096, 16, 16, 128, 0, 0.0),
    "gemma3-window-4096": (2, 4096, 8, 4, 256, 1024, 0.0),
    "gemma2-window-softcap-8192": (1, 8192, 16, 8, 256, 4096, 50.0),
}


@pytest.mark.parametrize("case", list(FLASH_CAUSAL_CASES))
def test_flash_kernel_compiles_causal_for_v5e(one_chip, compiled_kernels,
                                              case):
    B, S, H, K, hd, window, softcap = FLASH_CAUSAL_CASES[case]
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, K, hd), jnp.bfloat16,
                              sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, softcap=softcap))
    hlo = fn.lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_ulysses_attention_compiles_on_2x2_mesh(topo, compiled_kernels):
    """The sequence-parallel (``--mesh 1x4``) attention over the four chips
    of the described host: all-to-alls around the compiled flash kernel."""
    from repro.distributed.attention import ulysses_attention
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "seq"))
    B, S, H, hd = 4, 256, 16, 72
    repl = NamedSharding(mesh, P())
    qkv = [jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16,
                                sharding=repl)] * 3
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=repl)
    fn = jax.jit(lambda q, k, v, s: ulysses_attention(
        q, k, v, mesh=mesh, axis="seq", segment_ids=s))
    hlo = fn.lower(*qkv, seg).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-to-all" in hlo


def test_full_depth_packed_step_compiles_and_fits(one_chip, compiled_kernels):
    """The engine's packed step at dit-xl-2's published widths and full
    depth (28 layers), mixing patch-2 and patch-4 requests under CFG,
    compiles with the Mosaic kernel and fits the chip's HBM."""
    from repro.pipeline.packed import PackLayout, make_packed_step_fn
    cfg = get_config("dit-xl-2")
    assert cfg.num_layers == 28 and cfg.d_model == 1152
    layout = PackLayout(groups=((0, 2), (1, 2)), guided=True)
    step = jax.jit(make_packed_step_fn(cfg, sch.linear_schedule(1000),
                                       layout, solver="ddim",
                                       guidance_scale=1.5))
    params = jax.eval_shape(lambda k: dit_mod.init_dit(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    xs = tuple(sds((n,) + cfg.dit.latent_shape, jnp.float32)
               for _m, n in layout.groups)
    metas = tuple(sds((1, 3, n), jnp.int32) for _m, n in layout.groups)
    keys = tuple(sds((1, n, 2), jnp.uint32) for _m, n in layout.groups)
    compiled = step.lower(params, xs, metas, keys).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 1.3e9      # bf16 params, ~675M
    assert total < device_peaks("TPU v5 lite").hbm_bytes


def test_sd35_packed_step_compiles_and_fits(one_chip, compiled_kernels):
    """sd35-medium's packed flow-Euler step at its published widths and
    full depth (24 MMDiT-X blocks), two powerful and two weak requests
    under CFG with their text conditions, compiles with the Mosaic kernel
    and fits the chip's HBM."""
    import json
    from chipbench import spec
    from repro.diffusion.flow import FlowSchedule
    from repro.pipeline.packed import PackLayout, make_packed_step_fn
    path = spec.HERE / "configs" / "sd35-medium.json"
    cfg = spec.config_module(path, "sd35_program").model_config(
        json.loads(path.read_text()))
    layout = PackLayout(groups=((0, 2), (1, 2)), guided=True)
    step = jax.jit(make_packed_step_fn(cfg, FlowSchedule(1000, 3.0), layout,
                                       solver="flow_euler",
                                       guidance_scale=4.5))
    params = jax.eval_shape(lambda k: dit_mod.init_dit(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.bfloat16, sharding=one_chip), params)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dit = cfg.dit
    xs = tuple(sds((n,) + dit.latent_shape, jnp.float32)
               for _m, n in layout.groups)
    metas = tuple(sds((1, 3, n), jnp.float32) for _m, n in layout.groups)
    keys = tuple(sds((1, n, 2), jnp.uint32) for _m, n in layout.groups)
    texts = tuple((sds((n, dit.text_len, dit.text_dim), jnp.bfloat16),
                   sds((n, dit.pooled_dim), jnp.bfloat16))
                  for _m, n in layout.groups)
    compiled = step.lower(params, xs, metas, keys, texts).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 4.4e9      # bf16 params, ~2.2B
    assert total < device_peaks("TPU v5 lite").hbm_bytes
