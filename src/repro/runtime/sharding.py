"""Logical→mesh sharding rules and helpers.

Baseline profile ``fsdp2d``: weights 2D-sharded over ('data','model') —
'embed'-type dims over the data axis (FSDP/ZeRO-3 storage; XLA inserts the
per-layer all-gathers) and 'mlp'/'heads'/'vocab'/'expert' dims megatron-style
over the model axis. Optimizer state inherits the same specs, so it is fully
sharded ("ZeRO") with no extra machinery.

``tp_only``: weights sharded over 'model' only (replicated across data) —
lower collective volume per step, higher per-device bytes. Used by the perf
pass for serving cells where weights fit.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

PROFILES = ("auto", "fsdp2d", "fsdp2d_sp", "tp_only", "dp")

# Named mesh axis used by the sequence-parallel inference engine
# (repro.distributed): activations scatter their token dim over it; weights
# never map a dim onto it (replicated across the axis).
SEQ_AXIS = "seq"

# Models whose bf16 params fit comfortably replicated skip FSDP (wrapping
# threshold, like torch FSDP's min_num_params): pure DP avoids pointless
# per-layer weight all-gathers on sub-3B models.
DP_PARAM_THRESHOLD = 3e9


def resolve_profile(cfg: "ModelConfig", profile: str) -> str:
    if profile != "auto":
        return profile
    return "dp" if cfg.num_params() < DP_PARAM_THRESHOLD else "fsdp2d"


def base_profile(profile: str) -> str:
    """Strip feature suffixes (_sp sequence-parallel, _kvq int8 KV cache) —
    the sharding rules are identical."""
    for suf in ("_sp", "_kvq"):
        profile = profile.replace(suf, "")
    return profile


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes used for data parallelism (batch sharding)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def rules_for(cfg: ModelConfig, mesh: Mesh, profile: str = "auto"
              ) -> Dict[str, Any]:
    """Logical axis rules. Non-divisible shardings are dropped later by
    ``spec_tree(axis_sizes=...)``.

    Every profile also carries the activation-side ``tokens`` rule: on
    meshes with a ``'seq'`` axis the sequence-parallel engine scatters the
    token dim over it (weights never map onto 'seq' — they stay replicated
    across that axis)."""
    profile = base_profile(resolve_profile(cfg, profile))
    tokens = SEQ_AXIS if SEQ_AXIS in mesh.axis_names else None
    if profile == "dp":            # replicated weights, batch-sharded data
        rules = {k: None for k in ("embed", "mlp", "heads", "kv_heads",
                                   "vocab", "expert", "layers")}
        rules["tokens"] = tokens
        return rules
    fsdp = dp_axes(mesh) if profile == "fsdp2d" else None
    rules: Dict[str, Any] = {
        "embed": fsdp,
        "mlp": "model",
        "heads": "model",
        "kv_heads": "model",
        "vocab": "model",
        "expert": "model",
        "layers": None,
        "tokens": tokens,
    }
    return rules


def batch_spec(batch: int, mesh: Mesh) -> P:
    """Shard batch over as many data axes as divide it."""
    axes = []
    prod = 1
    sizes = axis_sizes(mesh)
    for a in dp_axes(mesh):
        prod *= sizes[a]
        if batch % prod == 0:
            axes.append(a)
        else:
            prod //= sizes[a]
    return P(tuple(axes) if axes else None)


def seq_axes_for_cache(batch: int, mesh: Mesh) -> Tuple[Any, Any]:
    """(batch_sharding, seq_sharding) for KV caches: batch over data axes when
    divisible, sequence over the model axis (context-parallel decode); when
    batch==1 the idle data axes also shard the sequence."""
    sizes = axis_sizes(mesh)
    b_axes, s_axes = [], []
    prod = 1
    for a in dp_axes(mesh):
        prod *= sizes[a]
        if batch % prod == 0:
            b_axes.append(a)
        else:
            prod //= sizes[a]
            s_axes.append(a)
    s_axes.append("model")
    return (tuple(b_axes) if b_axes else None,
            tuple(s_axes) if len(s_axes) > 1 else s_axes[0])


def token_spec(batch: int, mesh: Mesh) -> P:
    """[B, N, ...] activation spec for the sequence-parallel engine: batch
    over whichever data axes divide it, tokens over the 'seq' axis."""
    b = batch_spec(batch, mesh)[0]
    seq = SEQ_AXIS if SEQ_AXIS in mesh.axis_names else None
    return P(b, seq)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_tree(mesh: Mesh, spec_pytree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_pytree,
        is_leaf=lambda x: isinstance(x, P))


def _ambient_axis_names() -> Tuple[str, ...]:
    """Axis names of the ambient mesh set by ``jax.set_mesh``, () if none
    is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return () if mesh.empty else tuple(mesh.axis_names)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint if an ambient mesh is set; no-op otherwise
    (keeps single-device tests mesh-free)."""
    names = set(_ambient_axis_names())
    if not names:
        return x
    flat = []
    for part in spec:
        if part is None:
            flat.append(None)
        elif isinstance(part, str):
            flat.append(part if part in names else None)
        else:
            kept = tuple(a for a in part if a in names)
            flat.append(kept if kept else None)
    return jax.lax.with_sharding_constraint(x, P(*flat))
