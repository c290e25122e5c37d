"""Packed inference (App. B.2, Fig. 12) — uniform and mixed-mode packs.

When NFEs at different patch sizes must run together, their sequence
lengths differ. Four approaches for packed CFG (Fig. 12):

  1. two separate NFEs (one powerful, one weak);
  2. one NFE per patch size with batch-2 stacking when both branches share a
     size (vanilla CFG fast path — ``core.guidance`` implements it);
  3. pad the weak sequence to the powerful length and batch both → 1 call,
     wasted FLOPs on padding;
  4. pack r = N_p/N_w weak sequences into one powerful-length row with
     block-diagonal (segment-id) attention masks (NaViT-style).

On TPU shapes must be static, so approach 4 packs to a fixed row length and
masks via segment ids inside attention (never materializing a [N,N] bool
mask in HBM). :func:`packed_mixed_forward` generalizes this to *mixed-mode*
packs — segments of different patch modes (weak AND powerful) share rows —
which is what the serving engine's continuous batcher composes every step
(``repro.serving``, DESIGN.md §serving). :func:`packed_weak_forward` is the
uniform special case. FLOPs/latency accounting (including the per-token
adaLN conditioning overhead packing introduces) is in :func:`packing_cost`
/ :func:`packed_row_flops` / :func:`mixed_pack_cost`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.scheduler import dit_block_flops, dit_nfe_flops
from repro.models import dit as dit_mod


def pack_ratio(cfg: ModelConfig, mode: int) -> int:
    """How many mode-``mode`` sequences fit in one powerful-length row."""
    return dit_mod.tokens_for_mode(cfg, 0) // dit_mod.tokens_for_mode(cfg, mode)


# ---------------------------------------------------------------------------
# Static row assembly (shared by execution and cost accounting)


def assign_rows(seg_tokens: Sequence[int], capacity: int) -> List[List[int]]:
    """First-fit-decreasing bin packing: place segments (by token count)
    into rows of ``capacity`` tokens; a segment never splits across rows.
    Returns rows of segment *indices* (into ``seg_tokens``)."""
    for i, n in enumerate(seg_tokens):
        if n > capacity:
            raise ValueError(f"segment {i} ({n} tokens) exceeds row "
                             f"capacity {capacity}")
    order = sorted(range(len(seg_tokens)), key=lambda i: -seg_tokens[i])
    rows: List[List[int]] = []
    free: List[int] = []
    for i in order:
        n = seg_tokens[i]
        for r, rem in enumerate(free):
            if rem >= n:
                rows[r].append(i)
                free[r] = rem - n
                break
        else:
            rows.append([i])
            free.append(capacity - n)
    for row in rows:                 # deterministic within-row order
        row.sort()
    return rows


# ---------------------------------------------------------------------------
# Packed forwards


def packed_mixed_forward(params: Any, cfg: ModelConfig,  # repro: traced
                         groups: Tuple[Tuple[int, int], ...],
                         xs: Sequence[jax.Array], ts: Sequence[jax.Array],
                         conds: Sequence[jax.Array], *,
                         row_capacity: Optional[int] = None,
                         cache_deltas: Optional[Sequence[jax.Array]] = None,
                         cache_refresh: Optional[Sequence[jax.Array]] = None,
                         cache_split: Optional[int] = None,
                         attn_backend: str = "auto") -> Any:
    """Run NFEs for segments of (possibly) different patch modes packed
    token-wise into fixed-capacity rows.

    ``groups``: static ``((mode, n_segments), ...)``, one entry per mode;
    ``xs[g]``: [n_g, F, H, W, C] latents; ``ts[g]``: [n_g] timesteps;
    ``conds[g]``: [n_g] class labels. Rows of ``row_capacity`` tokens
    (default: the mode-0 sequence length) are filled first-fit-decreasing,
    attention is block-diagonal via segment ids, and adaLN conditioning is
    applied per token — so each segment's output equals its unpacked NFE.
    Returns one [n_g, F, H, W, c_out] array per group.

    Mixing modes inside one forward requires mode-independent transformer
    *blocks* (the shared-parameter recipe): per-mode LoRA adapters pick
    weights per row, not per token. Uniform packs (one group) work on any
    recipe.

    Cross-step activation cache (DESIGN.md §cache): with ``cache_split``
    set, ``cache_deltas[g]`` ([n_g, N_m, d] per segment) and
    ``cache_refresh[g]`` ([n_g] bool) thread each segment's OWN
    staleness clock through the pack. Shallow blocks always recompute on
    the packed rows; the deep blocks run under ``lax.cond`` only when
    ANY segment refreshes this step (attention is segment-masked, so a
    refreshing segment's fresh features never leak into a stale
    neighbour), and each token picks fresh vs replayed deltas by its
    segment's flag. Returns ``(outs, new_deltas)`` instead of ``outs``;
    a step where every segment refreshes is bit-identical to the
    uncached forward.
    """
    modes_present = [m for m, n in groups if n > 0]
    if len(modes_present) > 1 and cfg.dit.lora_rank > 0:
        raise ValueError("mixed-mode packs need mode-independent blocks "
                         "(LoRA recipe adapters are per-mode); pack "
                         "uniformly or merge/disable LoRA")
    block_mode = modes_present[0] if len(modes_present) == 1 else 0
    d = cfg.d_model
    from repro.models.common import dtype_of
    dtype = dtype_of(cfg.compute_dtype)
    seg_n = [dit_mod.tokens_for_mode(cfg, m) for m, _ in groups]
    capacity = row_capacity or max([dit_mod.tokens_for_mode(cfg, 0)] + seg_n)

    # per-group token streams [n_g, N_m, d] and conditioning vectors [n_g, d]
    toks, cvecs = [], []
    with jax.named_scope("embed"):
        for g, (mode, n) in enumerate(groups):
            toks.append(dit_mod.embed_mode_tokens(params, xs[g], cfg, mode))
            cvecs.append(dit_mod.condition_vector(params, ts[g], conds[g],
                                                  cfg, dtype))

    # flat segment list (group, index-within-group, tokens)
    segs: List[Tuple[int, int, int]] = []
    for g, (mode, n) in enumerate(groups):
        segs.extend((g, i, seg_n[g]) for i in range(n))
    rows = assign_rows([s[2] for s in segs], capacity)
    n_seg = len(segs)

    with jax.named_scope("pack_rows"):
        # adaLN conditioning is applied per token but COMPUTED per
        # segment: every block projects the [S+1, d] segment conditioning
        # (last row = zeros for padding) and gathers it token-wise —
        # identical values to a per-token projection at 1/N_seg the
        # matmul cost
        seg_c = jnp.concatenate(
            [jnp.stack([cvecs[segs[s][0]][segs[s][1]]
                        for s in range(n_seg)]),
             jnp.zeros((1, d), dtype)]) if n_seg else jnp.zeros((1, d),
                                                                dtype)

        row_toks, row_seg, row_idx, placement = [], [], [], {}
        sid = 0
        for r, row in enumerate(rows):
            parts, sparts, iparts, off = [], [], [], 0
            for si in row:
                g, i, n = segs[si]
                parts.append(toks[g][i])
                sparts.append(jnp.full((n,), sid, jnp.int32))
                iparts.append(jnp.full((n,), si, jnp.int32))
                placement[(g, i)] = (r, off)
                sid += 1
                off += n
            if off < capacity:
                pad = capacity - off
                parts.append(jnp.zeros((pad, d), dtype))
                sparts.append(jnp.full((pad,), -1, jnp.int32))
                iparts.append(jnp.full((pad,), n_seg, jnp.int32))
            row_toks.append(jnp.concatenate(parts))
            row_seg.append(jnp.concatenate(sparts))
            row_idx.append(jnp.concatenate(iparts))
        packed = jnp.stack(row_toks)                 # [R, C, d]
        segment_ids = jnp.stack(row_seg)             # [R, C]
        token_idx = jnp.stack(row_idx)               # [R, C] → seg_c row

    def body(h, bp):
        with jax.named_scope("dit_block"):
            h = _packed_block(bp, h, seg_c, token_idx, cfg, block_mode,
                              segment_ids, attn_backend)
        return h, None

    from repro.models.common import scan_or_unroll
    cached = cache_split is not None
    if not cached:
        tok, _ = scan_or_unroll(body, packed, params["blocks"], cfg.unroll)
    else:
        # cached deltas packed row-wise with the SAME placement as the
        # tokens; each token selects fresh vs replayed by its segment's
        # refresh flag (padding rides along with flag False, delta 0)
        with jax.named_scope("pack_rows"):
            drow_parts = []
            for row in rows:
                parts, off = [], 0
                for si in row:
                    g, i, n = segs[si]
                    parts.append(cache_deltas[g][i].astype(dtype))
                    off += n
                if off < capacity:
                    parts.append(jnp.zeros((capacity - off, d), dtype))
                drow_parts.append(jnp.concatenate(parts))
            delta_rows = jnp.stack(drow_parts)       # [R, C, d]
            refresh_flat = jnp.concatenate(
                [jnp.asarray(cache_refresh[g]).reshape(-1).astype(bool)
                 for g in range(len(groups))])       # [n_seg]
            rf_pad = jnp.concatenate([refresh_flat,
                                      jnp.zeros((1,), bool)])
            rmask = jnp.take(rf_pad, token_idx)[..., None]   # [R, C, 1]

        shallow, deep = dit_mod.split_blocks(params["blocks"], cache_split)
        h_s, _ = scan_or_unroll(body, packed, shallow, cfg.unroll)

        def _with_deep(args):
            h, cached_rows = args
            h_d, _ = scan_or_unroll(body, h, deep, cfg.unroll)
            return (jnp.where(rmask, h_d, h + cached_rows),
                    jnp.where(rmask, h_d - h, cached_rows))

        def _no_deep(args):
            h, cached_rows = args
            return h + cached_rows, cached_rows

        tok, new_rows = jax.lax.cond(jnp.any(refresh_flat), _with_deep,
                                     _no_deep, (h_s, delta_rows))

    with jax.named_scope("final"):
        ada = dit_mod._linear(
            jax.nn.silu(seg_c.astype(jnp.float32)).astype(dtype),
            params["final"]["ada"]["w"], params["final"]["ada"]["b"])
        sh, sc = jnp.split(jnp.take(ada, token_idx, axis=0), 2, axis=-1)
        tok = dit_mod._ln(tok) * (1.0 + sc) + sh

        outs: List[jax.Array] = []
        new_deltas: List[jax.Array] = []
        for g, (mode, n) in enumerate(groups):
            if n == 0:
                outs.append(jnp.zeros((0,) + cfg.dit.latent_shape[:-1]
                                      + (dit_mod.c_out_dim(cfg),), dtype))
                if cached:
                    new_deltas.append(jnp.zeros((0, seg_n[g], d), dtype))
                continue
            slices, dslices = [], []
            for i in range(n):
                r, off = placement[(g, i)]
                slices.append(tok[r, off:off + seg_n[g]])
                if cached:
                    dslices.append(new_rows[r, off:off + seg_n[g]])
            outs.append(dit_mod.deembed_mode_tokens(
                params, jnp.stack(slices), cfg, mode))
            if cached:
                new_deltas.append(jnp.stack(dslices))
    return (outs, new_deltas) if cached else outs


def packed_weak_forward(params: Any, x_ts: jax.Array, t: jax.Array,
                        conds: jax.Array, cfg: ModelConfig, mode: int
                        ) -> jax.Array:
    """Run ``r`` weak NFEs packed into one sequence row per batch element
    (the uniform special case of :func:`packed_mixed_forward`).

    x_ts: [r, B, F, H, W, C] — r independent latents (e.g. the conditional
    and unconditional branches of several samples);
    t: [B]; conds: [r, B] class labels.
    Returns eps for each: [r, B, F, H, W, c_out].
    """
    r, B = x_ts.shape[:2]
    N_w = dit_mod.tokens_for_mode(cfg, mode)
    # flatten b-major so first-fit fills row b with that element's r segments
    xs = jnp.swapaxes(x_ts, 0, 1).reshape((B * r,) + x_ts.shape[2:])
    ts = jnp.repeat(t, r)
    cs = conds.T.reshape(-1)
    out = packed_mixed_forward(params, cfg, ((mode, B * r),), [xs], [ts],
                               [cs], row_capacity=r * N_w)[0]
    out = out.reshape((B, r) + out.shape[1:])
    return jnp.swapaxes(out, 0, 1)


def _packed_block(p: Any, x: jax.Array, seg_c: jax.Array,
                  token_idx: jax.Array, cfg: ModelConfig,
                  mode: int, segment_ids: jax.Array,
                  attn_backend: str = "auto") -> jax.Array:
    """DiT block with per-segment adaLN conditioning (gathered to token
    level via ``token_idx``) + segment-masked attention."""
    H = cfg.attn.num_heads
    dtype = x.dtype
    with jax.named_scope("adaln"):
        ada = dit_mod._linear(
            jax.nn.silu(seg_c.astype(jnp.float32)).astype(dtype),
            p["ada"]["w"], p["ada"]["b"])
        ada = jnp.take(ada, token_idx, axis=0)       # [R, C, 6d]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(ada, 6, axis=-1)
    lora = p.get("lora", {})
    with jax.named_scope("attn"):
        h = dit_mod._ln(x) * (1.0 + sc1) + sh1
        attn = dit_mod._mha(p["attn"], h, H, lora=lora.get("attn"),
                            mode=mode, segment_ids=segment_ids,
                            attn_backend=attn_backend)
        x = x + g1 * attn
    with jax.named_scope("mlp"):
        h2 = dit_mod._ln(x) * (1.0 + sc2) + sh2
        mlp_lora = lora.get("mlp", {})
        h2 = dit_mod._linear(h2, p["mlp"]["w_in"], p["mlp"]["b_in"],
                             lora=mlp_lora.get("w_in"), mode=mode)
        h2 = jax.nn.gelu(h2.astype(jnp.float32),
                         approximate=True).astype(dtype)
        h2 = dit_mod._linear(h2, p["mlp"]["w_out"], p["mlp"]["b_out"],
                             lora=mlp_lora.get("w_out"), mode=mode)
        return x + g2 * h2


# ---------------------------------------------------------------------------
# FLOPs / latency accounting (Fig. 12 + serving packs)


@dataclasses.dataclass(frozen=True)
class PackingCost:
    approach: int
    nfe_calls: int          # sequential NFE launches
    flops: float            # total FLOPs
    longest_row_tokens: int  # latency proxy: tokens in the critical NFE


def packed_row_flops(cfg: ModelConfig, modes: Sequence[int],
                     capacity: Optional[int] = None,
                     attn_backend: str = "dense") -> float:
    """FLOPs of ONE packed row holding segments of the given modes.

    Accounts for the conditioning overhead packing introduces: every
    packed segment carries its OWN adaLN conditioning (the 6d block
    projection and the 2d final projection run once per segment, then
    gather to token level), the blocks see the full (padded) row, and
    (de-)embedding runs per segment at that segment's real length.

    ``attn_backend``: 'dense'/'xla-blocked' price the row's attention at
    the full C² score matrix (what the XLA paths compute, masked or
    not); 'pallas'/'auto' price only the block tiles the segment-aware
    flash kernel visits (cross-segment and padding tiles are skipped) —
    the serving controller and benches use this to charge what the
    default backend actually issues.
    """
    from repro.kernels.attention import costing
    seg_tokens = [dit_mod.tokens_for_mode(cfg, m) for m in modes]
    C = capacity if capacity is not None else sum(seg_tokens)
    if sum(seg_tokens) > C:
        raise ValueError(f"segments ({sum(seg_tokens)} tokens) exceed row "
                         f"capacity {C}")
    d, L = cfg.d_model, cfg.num_layers
    S = len(modes)
    fl = dit_block_flops(cfg, C)
    if attn_backend in ("pallas", "auto"):
        fl += L * (costing.block_sparse_attention_flops(seg_tokens, C, d)
                   - costing.dense_attention_flops(C, C, d))
    fl += L * 2 * (S - 1) * d * 6 * d        # block adaLN: one per SEGMENT
    fl += 2 * S * d * 2 * d                  # final adaLN, per segment
    c_in = cfg.dit.latent_shape[-1]
    c_out = dit_mod.c_out_dim(cfg)
    for m, N in zip(modes, seg_tokens):
        npix = int(np.prod(dit_mod.patch_sizes(cfg)[m]))
        fl += 2 * N * npix * c_in * d        # per-segment embed
        fl += 2 * N * d * npix * c_out       # per-segment de-embed
    return float(fl)


@dataclasses.dataclass(frozen=True)
class MixedPackCost:
    """Static cost of one mixed pack: rows actually assembled (first-fit,
    mirroring :func:`packed_mixed_forward`), total FLOPs, and the token
    ledger used for packing-efficiency metrics."""
    rows: int
    flops: float
    real_tokens: int        # sum of segment lengths
    packed_tokens: int      # rows * capacity (what the hardware computes)

    @property
    def efficiency(self) -> float:
        return self.real_tokens / self.packed_tokens if self.packed_tokens \
            else 1.0


def mixed_pack_cost(cfg: ModelConfig, modes: Sequence[int],
                    row_capacity: Optional[int] = None,
                    attn_backend: str = "dense") -> MixedPackCost:
    """Cost of packing one segment per entry of ``modes`` into rows of
    ``row_capacity`` tokens (default: the mode-0 length)."""
    seg_tokens = [dit_mod.tokens_for_mode(cfg, m) for m in modes]
    capacity = row_capacity or max([dit_mod.tokens_for_mode(cfg, 0)]
                                   + seg_tokens)
    rows = assign_rows(seg_tokens, capacity)
    fl = sum(packed_row_flops(cfg, [modes[i] for i in row], capacity,
                              attn_backend=attn_backend)
             for row in rows)
    return MixedPackCost(rows=len(rows), flops=fl,
                         real_tokens=sum(seg_tokens),
                         packed_tokens=len(rows) * capacity)


def pack_attention_block_stats(cfg: ModelConfig, modes: Sequence[int],
                               row_capacity: Optional[int] = None
                               ) -> Tuple[int, int]:
    """(active, total) attention block-tile visits for the pack one
    segment-per-``modes``-entry assembles (same first-fit row assembly
    as :func:`packed_mixed_forward`). ``1 - active/total`` is the
    cross-segment block skip rate ``serving.metrics`` reports."""
    from repro.kernels.attention import costing
    seg_tokens = [dit_mod.tokens_for_mode(cfg, m) for m in modes]
    capacity = row_capacity or max([dit_mod.tokens_for_mode(cfg, 0)]
                                   + seg_tokens)
    rows = assign_rows(seg_tokens, capacity)
    return costing.pack_attention_stats(
        [[seg_tokens[i] for i in row] for row in rows], capacity)


def packing_cost(cfg: ModelConfig, mode_weak: int, n_images: int
                 ) -> List[PackingCost]:
    """Costs for generating ``n_images`` with CFG where the conditional runs
    powerful and the guidance weak (per denoising step)."""
    f_p = dit_nfe_flops(cfg, 0)
    f_w = dit_nfe_flops(cfg, mode_weak)
    N_p = dit_mod.tokens_for_mode(cfg, 0)
    N_w = dit_mod.tokens_for_mode(cfg, mode_weak)
    r = max(1, N_p // N_w)
    n = n_images
    n_rows = int(np.ceil(n / r))
    # approach 4: the weak branch packs r segments per powerful-length row;
    # each row pays the per-token conditioning overhead (the last row is
    # padded to capacity, so it costs the same as a full one)
    packed_rows = n_rows * packed_row_flops(cfg, [mode_weak] * r,
                                            capacity=N_p)
    out = [
        # 1: separate sequential calls per branch
        PackingCost(1, 2, n * (f_p + f_w), N_p),
        # 2: batch conditional calls together; batch weak calls together
        PackingCost(2, 2, n * (f_p + f_w), N_p),
        # 3: pad weak rows to powerful length, single batched call
        PackingCost(3, 1, n * 2 * f_p, N_p),
        # 4: pack r weak rows into powerful-length rows, single call
        PackingCost(4, 1, n * f_p + packed_rows, N_p),
    ]
    return out
