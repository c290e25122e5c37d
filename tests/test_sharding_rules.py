"""Direct unit tests for runtime/sharding.py: dp_axes / batch_spec /
token_spec / rules_for (incl. the sequence-parallel 'tokens' rule) and the
ambient-mesh lookup (``jax.sharding.get_abstract_mesh``) behind ``constrain`` —
previously only exercised indirectly through train/serve paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.runtime import sharding as sh

pytestmark = pytest.mark.tier1


def _mesh(shape, axes):
    devs = np.asarray(jax.devices()[:1]).reshape(shape)
    return Mesh(devs, axes)


def test_dp_axes_and_axis_sizes():
    m = _mesh((1, 1), ("data", "model"))
    assert sh.dp_axes(m) == ("data",)
    assert sh.axis_sizes(m) == {"data": 1, "model": 1}
    m3 = _mesh((1, 1, 1), ("pod", "data", "model"))
    assert sh.dp_axes(m3) == ("pod", "data")
    ms = _mesh((1, 1), ("data", "seq"))
    assert sh.dp_axes(ms) == ("data",)     # 'seq' is never a DP axis


def test_batch_spec_divisibility():
    """batch_spec greedily takes data axes whose cumulative product divides
    the batch; on 1-sized axes everything divides."""
    m = _mesh((1, 1), ("data", "model"))
    assert sh.batch_spec(4, m) == P(("data",))
    assert sh.batch_spec(3, m) == P(("data",))
    # a real multi-device shape check needs fake devices; the pure
    # arithmetic is covered via the distributed suite's meshes


def test_token_spec():
    ms = _mesh((1, 1), ("data", "seq"))
    assert sh.token_spec(4, ms) == P(("data",), "seq")
    mm = _mesh((1, 1), ("data", "model"))
    assert sh.token_spec(4, mm) == P(("data",), None)


def test_rules_for_profiles_and_seq_axis():
    cfg = get_config("dit-xl-2").reduced()     # tiny → resolves to 'dp'
    ms = _mesh((1, 1), ("data", "seq"))
    rules = sh.rules_for(cfg, ms, "auto")
    assert rules["embed"] is None              # dp: replicated weights
    assert rules["tokens"] == sh.SEQ_AXIS      # activations scatter on seq
    mm = _mesh((1, 1), ("data", "model"))
    assert sh.rules_for(cfg, mm, "auto")["tokens"] is None
    big = get_config("dit-xl-2")               # 675M... still under 3e9 → dp
    assert sh.resolve_profile(big, "auto") in ("dp", "fsdp2d")
    r2 = sh.rules_for(cfg, ms, "fsdp2d")
    assert r2["embed"] == ("data",) and r2["mlp"] == "model"
    assert r2["tokens"] == sh.SEQ_AXIS
    r3 = sh.rules_for(cfg, ms, "tp_only")
    assert r3["embed"] is None and r3["heads"] == "model"
    assert r3["tokens"] == sh.SEQ_AXIS


def test_base_profile_strips_suffixes():
    assert sh.base_profile("fsdp2d_sp") == "fsdp2d"
    assert sh.base_profile("tp_only_kvq") == "tp_only"
    assert sh.base_profile("dp") == "dp"


def test_ambient_mesh_shim_and_constrain_noop():
    """Outside any mesh context the shim reports no axes and ``constrain``
    is the identity (keeps single-device tests mesh-free)."""
    assert sh._ambient_axis_names() == ()
    x = jnp.ones((2, 2))
    assert sh.constrain(x, P("data", None)) is x
    # under `jax.set_mesh` the shim surfaces the axis names and
    # constrain filters specs down to the axes that exist
    m = _mesh((1, 1), ("data", "model"))
    with jax.set_mesh(m):
        assert set(sh._ambient_axis_names()) == {"data", "model"}
        # unknown axis dropped
        y = jax.jit(lambda a: sh.constrain(a, P("data", "nope")))(x)
        assert y.shape == x.shape
        # tuple filtering
        z = jax.jit(lambda a: sh.constrain(a, P(("pod", "data"), None)))(x)
        assert z.shape == x.shape
