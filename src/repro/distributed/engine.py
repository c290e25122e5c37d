"""Mesh-bound sequence-parallel engine (DESIGN.md §distributed).

:class:`SeqParallel` is the runtime object the pipeline threads through
``make_eps_fn`` → ``dit_forward`` → ``_mha``: it owns the mesh, the
resolved all-to-all implementation, and the token-level pad/shard/unshard
plumbing. It is built per ``(mesh fingerprint, ParallelSpec)`` at runner
compile time — sampling code only ever sees the declarative
:class:`~repro.distributed.partition.ParallelSpec` on the plan.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import attention as dist_attn
from repro.distributed.partition import ParallelSpec, resolve_impl
from repro.runtime.padding import pad_to, round_up_to_multiple


def mesh_fingerprint(mesh: Optional[Mesh]) -> Optional[Tuple]:
    """Hashable identity of a mesh for compile-cache keys: axis layout plus
    the physical device assignment (a new mesh over the same devices with
    the same layout reuses executables)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


@dataclasses.dataclass(frozen=True)
class SeqParallel:
    """A ParallelSpec bound to a mesh, ready to run inside jit."""
    mesh: Mesh
    axis: str
    impl: str                    # 'ulysses' | 'ring' (resolved)
    # attention backend for the post-all-to-all inner attend (Ulysses);
    # DESIGN.md §attention-backend. 'auto' → the segment-aware Pallas
    # flash kernel (padding segments become skipped blocks, not masks).
    attn_backend: str = "auto"

    @classmethod
    def create(cls, mesh: Optional[Mesh], spec: ParallelSpec,
               cfg: ModelConfig, attn_backend: str = "auto") -> "SeqParallel":
        if mesh is None:
            raise ValueError("plan.parallel needs a device mesh; construct "
                             "FlexiPipeline(..., mesh=...) or set_mesh()")
        if spec.axis not in mesh.axis_names:
            raise ValueError(f"mesh has no '{spec.axis}' axis "
                             f"(axes: {mesh.axis_names})")
        return cls(mesh=mesh, axis=spec.axis,
                   impl=resolve_impl(cfg, spec, mesh.shape[spec.axis]),
                   attn_backend=attn_backend)

    @property
    def sp(self) -> int:
        return self.mesh.shape[self.axis]

    # ------------------------------------------------------------------
    # Token plumbing (inside jit)

    def pad_and_shard(self, tok: jax.Array,
                      segment_ids: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Pad [B, N, d] tokens to a multiple of sp and pin them to the
        engine's inter-layer layout. Padding tokens get segment id -1 so
        they never contribute as attention keys."""
        B, N = tok.shape[:2]
        target = round_up_to_multiple(N, self.sp)
        if target != N:
            tok = pad_to(tok, target, axis=1)
            if segment_ids is None:
                segment_ids = jnp.zeros((B, N), jnp.int32)
            segment_ids = pad_to(segment_ids, target, axis=1, value=-1)
        tok = jax.lax.with_sharding_constraint(
            tok, NamedSharding(self.mesh, self._interlayer_spec(tok.ndim)))
        return tok, segment_ids

    def _interlayer_spec(self, ndim: int) -> P:
        """Layout activations keep BETWEEN shard_map calls. jax 0.4.x GSPMD
        miscompiles resharding jit-internal intermediates onto the sequence
        axis, so outside the collectives we keep activations replicated
        (batch sharding across data axes is reintroduced by the harness
        once that bug is gone — see ROADMAP 'Open items')."""
        return P(*(None,) * ndim)

    def unshard(self, tok: jax.Array, n_tokens: int) -> jax.Array:
        """Drop padding rows after the blocks (before de-embedding)."""
        return tok[:, :n_tokens]

    def attend(self, q: jax.Array, k: jax.Array, v: jax.Array,
               segment_ids: Optional[jax.Array] = None) -> jax.Array:
        # Pin the operands to a replicated layout before the shard_map
        # boundary: jax 0.4.x GSPMD miscompiles the direct reshard of
        # jit-internal intermediates into the (data, seq) layout (verified
        # against the dense path — values upstream of the boundary change).
        # Entering from the replicated layout is correct, and the slice to
        # per-shard blocks is local.
        repl = NamedSharding(self.mesh, P())
        q = jax.lax.with_sharding_constraint(q, repl)
        k = jax.lax.with_sharding_constraint(k, repl)
        v = jax.lax.with_sharding_constraint(v, repl)
        if segment_ids is not None:
            segment_ids = jax.lax.with_sharding_constraint(segment_ids, repl)
        fn = dist_attn.ATTN_FNS[self.impl]
        out = fn(q, k, v, mesh=self.mesh, axis=self.axis,
                 segment_ids=segment_ids, attn_backend=self.attn_backend)
        # ... and pin the collective's output the same way so downstream
        # consumers never see a seq-sharded intermediate either. On a mesh
        # with Explicit axes (``jax.make_mesh``'s default) the sharding is
        # part of the array's type, so this must be a ``reshard``: a bare
        # constraint would leave the layer scan's carry typed seq-sharded.
        return jax.sharding.reshard(out, repl)
