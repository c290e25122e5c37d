"""Step-granular packed runners (DESIGN.md §serving).

A :class:`PackLayout` is the static shape of ONE engine step: how many
requests of each patch mode advance together, whether CFG doubles each
request into a (conditional, unconditional) segment pair, and the token
capacity of each packed row. :func:`make_packed_step_fn` builds the
executable for a layout — embed every segment at its own mode, pack rows
with block-diagonal attention (``core.packing.packed_mixed_forward``),
combine guidance, and apply one solver update per request at that
request's own ``(t, t_prev)``. Timesteps, conditioning, latents, params,
and solver keys are all traced, so a layout compiles exactly once no
matter which requests, denoise steps, or budgets flow through it —
``FlexiPipeline.packed_step`` caches these next to the phase runners so
``cache_stats()`` covers bucket warmup too.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import packing
from repro.core.guidance import split_model_out
from repro.diffusion import schedule as sch
from repro.models import dit as dit_mod
from repro.telemetry import taps as taps_mod

PACKED_SOLVERS = ("ddim", "ddpm")


@dataclasses.dataclass(frozen=True)
class PackLayout:
    """Static shape of one packed engine step.

    ``groups``: ``((mode, n_requests), ...)`` sorted by mode, all counts
    positive. ``guided``: CFG doubles every request into two segments.
    ``row_capacity``: tokens per packed row; 0 resolves to the mode-0
    sequence length at build time.
    """
    groups: Tuple[Tuple[int, int], ...]
    guided: bool = True
    row_capacity: int = 0

    def __post_init__(self):
        if not self.groups:
            raise ValueError("layout needs at least one (mode, n) group")
        modes = [m for m, _ in self.groups]
        if sorted(modes) != modes or len(set(modes)) != len(modes):
            raise ValueError(f"groups must be mode-sorted and unique, "
                             f"got {self.groups}")
        if any(n < 1 for _, n in self.groups) or any(m < 0 for m in modes):
            raise ValueError(f"modes must be >= 0 and counts >= 1, "
                             f"got {self.groups}")

    @property
    def n_requests(self) -> int:
        return sum(n for _, n in self.groups)

    def capacity_for(self, m: int) -> int:
        """Request slots this layout offers at mode ``m``."""
        return dict(self.groups).get(m, 0)

    def resolve_capacity(self, cfg: ModelConfig) -> int:
        if self.row_capacity:
            return self.row_capacity
        return max([dit_mod.tokens_for_mode(cfg, 0)]
                   + [dit_mod.tokens_for_mode(cfg, m) for m, _ in self.groups])

    def segment_modes(self) -> Tuple[int, ...]:
        """Flat per-segment mode list (CFG doubling applied)."""
        mult = 2 if self.guided else 1
        out = []
        for m, n in self.groups:
            out.extend([m] * (mult * n))
        return tuple(out)

    def cost(self, cfg: ModelConfig,
             attn_backend: str = "dense") -> packing.MixedPackCost:
        """Rows / FLOPs / token ledger of one step at this layout."""
        return packing.mixed_pack_cost(cfg, self.segment_modes(),
                                       self.resolve_capacity(cfg),
                                       attn_backend=attn_backend)

    def attention_block_stats(self, cfg: ModelConfig) -> Tuple[int, int]:
        """(active, total) attention block-tile visits of one step at
        this layout under the segment-aware Pallas kernel."""
        return packing.pack_attention_block_stats(
            cfg, self.segment_modes(), self.resolve_capacity(cfg))

    @staticmethod
    def for_counts(counts: Dict[int, int], guided: bool = True,
                   row_capacity: int = 0) -> "PackLayout":
        groups = tuple(sorted((m, n) for m, n in counts.items() if n > 0))
        return PackLayout(groups=groups, guided=guided,
                          row_capacity=row_capacity)


def make_packed_step_fn(cfg: ModelConfig, sched: sch.DiffusionSchedule,
                        layout: PackLayout, *, solver: str = "ddim",
                        guidance_scale: float = 1.5,
                        clip_x0: float = 0.0,
                        k_steps: int = 1,
                        cache_split: Optional[int] = None,
                        attn_backend: str = "auto",
                        taps: bool = False) -> Callable:
    """Build ``step(params, xs, metas, keys)`` for a layout.

    Per group ``g`` (one per mode): ``xs[g]`` [n_g, F, H, W, C] latents;
    ``metas[g]`` [k, 3, n_g] int32 with rows ``(t, t_prev, cond)`` per
    micro-step — each request at its OWN denoise step (``t_prev=-1``
    means the final x0 step), one host→device transfer per group;
    ``keys[g]`` [k, n_g, 2] uint32 per-request solver keys (DDPM
    ancestral noise; ignored by DDIM). Returns one ``x`` array per group
    after ``k_steps`` solver updates.

    ``k_steps > 1`` runs the packed step body under ``lax.scan`` — the
    engine dispatches K consecutive same-mode denoise steps in one call,
    recovering the whole-trajectory sampler's scan fusion while keeping
    join/leave at K-step granularity. Matches per-request
    ``FlexiPipeline.sample`` bit-for-bit in expectation: same embedding
    path, same guidance combine, same solver arithmetic, and DDPM noise
    drawn per request from the same key derivation.

    ``cache_split`` enables the cross-step activation cache (DESIGN.md
    §cache): the step becomes ``step(params, xs, metas, keys, deltas,
    refreshes) → (xs', deltas')`` where ``deltas[g]`` is
    [n_g, mult, N_mode, d] per-request deep-block residuals (mult = 2
    under CFG) and ``refreshes[g]`` is [k, n_g] bool — each request's
    own staleness clock, threaded through the ``lax.scan`` carry so a
    K-deep dispatch refreshes exactly where the request's policy says.
    Refresh flags are traced data: one compiled layout serves every
    policy.

    ``taps`` appends on-device telemetry outputs (DESIGN.md §telemetry)
    as pure extra DATA: the step becomes ``... → (xs'[, deltas'], tap)``
    where ``tap = {"eps_norm": ([k, n_g], ...), "attn_blocks": [2]}``
    plus ``"drift": ([k, n_g], ...)`` on the cached family —
    per-request RMS of the post-guidance eps, the kernel ledger's
    (active, total) block tiles, and the realized replay drift
    ``‖h_fresh − h_replay‖`` computed from residuals the step already
    materializes. Latents and deltas are bit-identical to ``taps=False``
    (DCE of the tap outputs recovers the untapped jaxpr — asserted in
    ``analysis/jaxpr_audit.py``), and taps join the runner cache key, so
    flipping telemetry never retraces a serving executable.
    """
    if solver not in PACKED_SOLVERS:
        raise ValueError(f"packed steps support solvers {PACKED_SOLVERS}, "
                         f"got {solver!r}")
    if cfg.dit.conditioning != "class":
        raise ValueError("packed steps currently serve class-conditioned "
                         "DiTs (text conditioning needs per-segment "
                         "cross-attention plumbing)")
    if k_steps < 1:
        raise ValueError(f"k_steps must be >= 1, got {k_steps}")
    if cache_split is not None and not 1 <= cache_split < cfg.num_layers:
        raise ValueError(f"cache_split {cache_split} must leave at least "
                         f"one deep block (model has {cfg.num_layers} "
                         f"layers)")
    guided = layout.guided
    if guided and guidance_scale == 0.0:
        raise ValueError("guided layout with guidance_scale=0; build an "
                         "unguided layout instead")
    null_label = cfg.dit.num_classes
    groups = layout.groups
    cap = layout.resolve_capacity(cfg)
    seg_groups = tuple((m, (2 if guided else 1) * n) for m, n in groups)

    cached = cache_split is not None
    # kernel-ledger block counts are layout-static: resolved on the host
    # once at build time, emitted as a tap constant (data, not structure)
    blk_stats = layout.attention_block_stats(cfg) if taps else None

    def one_step(params, xs, metas, keys, deltas=None, refreshes=None):
        seg_xs, seg_ts, seg_conds = [], [], []
        seg_deltas, seg_refresh = [], []
        for g, (mode, n) in enumerate(groups):
            t_g, cond_g = metas[g][0], metas[g][2]
            if guided:
                seg_xs.append(jnp.concatenate([xs[g], xs[g]], axis=0))
                seg_ts.append(jnp.concatenate([t_g, t_g], axis=0))
                null = jnp.full((n,), null_label, jnp.int32)
                seg_conds.append(jnp.concatenate([cond_g, null], axis=0))
            else:
                seg_xs.append(xs[g])
                seg_ts.append(t_g)
                seg_conds.append(cond_g)
            if cached:
                # [n, mult, N, d] → segment order (all cond, then all
                # uncond) matching seg_xs; both branches share the clock
                d_g = deltas[g]
                seg_deltas.append(jnp.concatenate(
                    [d_g[:, b] for b in range(d_g.shape[1])], axis=0))
                rf = refreshes[g]
                seg_refresh.append(jnp.concatenate([rf, rf], axis=0)
                                   if guided else rf)
        if cached:
            outs, new_seg_deltas = packing.packed_mixed_forward(
                params, cfg, seg_groups, seg_xs, seg_ts, seg_conds,
                row_capacity=cap, cache_deltas=seg_deltas,
                cache_refresh=seg_refresh, cache_split=cache_split,
                attn_backend=attn_backend)
            new_deltas = []
            for g, (mode, n) in enumerate(groups):
                mult = deltas[g].shape[1]
                new_deltas.append(jnp.stack(
                    jnp.split(new_seg_deltas[g], mult, axis=0), axis=1))
        else:
            outs = packing.packed_mixed_forward(params, cfg, seg_groups,
                                                seg_xs, seg_ts, seg_conds,
                                                row_capacity=cap,
                                                attn_backend=attn_backend)
        x_prevs, eps_taps = [], []
        for g, (mode, n) in enumerate(groups):
            t_g, tp_g = metas[g][0], metas[g][1]
            with jax.named_scope("guidance_solver"):
                eps, logvar = split_model_out(outs[g], cfg)
                if guided:
                    e_c, e_u = jnp.split(eps, 2, axis=0)
                    eps_g = e_u + guidance_scale * (e_c - e_u)
                    lv = None if logvar is None else jnp.split(logvar, 2,
                                                               axis=0)[0]
                else:
                    eps_g, lv = eps, logvar
                if taps:
                    eps_taps.append(taps_mod.eps_norm_tap(eps_g))
                if solver == "ddim":
                    x_prev = sch.ddim_step(sched, xs[g], eps_g, t_g,
                                           tp_g, 0.0, None)
                else:
                    # per-request ancestral noise: vmap draws each
                    # request's noise from its own key, exactly as an n=1
                    # pipeline batch
                    if lv is None:
                        x_prev = jax.vmap(
                            lambda x1, e1, t1, k1: sch.ddpm_step(
                                sched, x1, e1, t1, k1, None, clip_x0)
                        )(xs[g], eps_g, t_g, keys[g])
                    else:
                        x_prev = jax.vmap(
                            lambda x1, e1, t1, k1, lv1: sch.ddpm_step(
                                sched, x1, e1, t1, k1, lv1, clip_x0)
                        )(xs[g], eps_g, t_g, keys[g], lv)
            x_prevs.append(x_prev)
        if taps:
            tap = {"eps_norm": tuple(eps_taps),
                   # per-request-slot all-finite flag of the step OUTPUT —
                   # pure DATA riding the tap channel, so quarantine can
                   # read it at an existing sync point without adding one
                   "finite": tuple(taps_mod.finite_tap(xp)
                                   for xp in x_prevs)}
            if cached:
                # ‖h_fresh − h_replay‖: the cached forward writes
                # new_delta = where(refresh, h_deep − h_shallow, old), so
                # the realized replay error is one subtraction of arrays
                # the step already materialized — free at refresh steps,
                # exactly 0 at skip steps
                tap["drift"] = tuple(
                    taps_mod.drift_tap(nd, deltas[g])
                    for g, nd in enumerate(new_deltas))
                return tuple(x_prevs), tuple(new_deltas), tap
            return tuple(x_prevs), tap
        if cached:
            return tuple(x_prevs), tuple(new_deltas)
        return tuple(x_prevs)

    def _tap_out(tap):
        """Attach the layout-static kernel-ledger constant; tap arrays
        keep a leading k axis either way (scan stacks, k=1 expands)."""
        tap["attn_blocks"] = jnp.asarray(blk_stats, jnp.int32)
        return tap

    if k_steps == 1:
        if cached:
            if taps:
                def step(params, xs, metas, keys, deltas, refreshes):
                    m1 = tuple(m[0] for m in metas)
                    k1 = tuple(k[0] for k in keys)
                    r1 = tuple(r[0] for r in refreshes)
                    out, dout, tap = one_step(params, xs, m1, k1,
                                              tuple(deltas), r1)
                    tap = jax.tree_util.tree_map(lambda a: a[None], tap)
                    return out, dout, _tap_out(tap)
                return step

            def step(params, xs, metas, keys, deltas, refreshes):
                m1 = tuple(m[0] for m in metas)
                k1 = tuple(k[0] for k in keys)
                r1 = tuple(r[0] for r in refreshes)
                return one_step(params, xs, m1, k1, tuple(deltas), r1)
            return step

        if taps:
            def step(params, xs, metas, keys):
                m1 = tuple(m[0] for m in metas)
                k1 = tuple(k[0] for k in keys)
                out, tap = one_step(params, xs, m1, k1)
                tap = jax.tree_util.tree_map(lambda a: a[None], tap)
                return out, _tap_out(tap)
            return step

        def step(params, xs, metas, keys):
            m1 = tuple(m[0] for m in metas)
            k1 = tuple(k[0] for k in keys)
            return one_step(params, xs, m1, k1)
        return step

    if cached:
        if taps:
            def step(params, xs, metas, keys, deltas, refreshes):
                def body(carry, per_step):
                    cxs, cdeltas = carry
                    m, k, r = per_step
                    nxs, nds, tap = one_step(params, cxs, m, k, cdeltas, r)
                    return (nxs, nds), tap
                (out, dout), tap = jax.lax.scan(
                    body, (tuple(xs), tuple(deltas)),
                    (tuple(metas), tuple(keys), tuple(refreshes)))
                return out, dout, _tap_out(tap)
            return step

        def step(params, xs, metas, keys, deltas, refreshes):
            def body(carry, per_step):
                cxs, cdeltas = carry
                m, k, r = per_step
                nxs, nds = one_step(params, cxs, m, k, cdeltas, r)
                return (nxs, nds), None
            (out, dout), _ = jax.lax.scan(
                body, (tuple(xs), tuple(deltas)),
                (tuple(metas), tuple(keys), tuple(refreshes)))
            return out, dout
        return step

    if taps:
        def step(params, xs, metas, keys):
            def body(carry, per_step):
                m, k = per_step
                nxs, tap = one_step(params, carry, m, k)
                return nxs, tap
            out, tap = jax.lax.scan(body, tuple(xs),
                                    (tuple(metas), tuple(keys)))
            return out, _tap_out(tap)
        return step

    def step(params, xs, metas, keys):
        def body(carry, per_step):
            m, k = per_step
            return one_step(params, carry, m, k), None
        out, _ = jax.lax.scan(body, tuple(xs), (tuple(metas), tuple(keys)))
        return out

    return step
