"""Tile x heads-per-step sweep of the flash attention kernel on a TPU.

Geometries: the DiT rows the tiered cells pack (dit-xl-2's 256-token
rows with 64-token weak segments, DiT-XL/2-512's 1,024-token rows with
256-token weak segments, 16 heads of 72), and two language-model
prefills that run the same kernel: a causal 4,096-token row at
t2i-transformer's widths (16 heads of 128) and a sliding-window one at
gemma3-4b's (window 1,024, 8 query heads over 4 kv heads of 256). For
each geometry and each :class:`TilePlan` it times 28 chained kernel
calls (one per layer, each call's output the next one's query, so none
is elided) in one jitted loop, and checks one call against the f32
oracle. Prints one JSON line per plan, marks ``tile_plan``'s own choice,
and writes them all to ``--out`` (default ``bench_out/flash_sweep.jsonl``).

    python3 benchmarks/flash_sweep.py                 # on one TPU chip
    python3 benchmarks/flash_sweep.py --default-only
    python3 benchmarks/flash_sweep.py --src OTHER/src # another checkout
    JAX_PLATFORMS=cpu python3 benchmarks/flash_sweep.py --compile-only

``--src`` imports the kernel from another checkout's ``src`` and times
its public ``ops.flash_attention`` at its own default tiles, so an older
kernel is measured by the same loop (it needs no ``tile_plan``).
``--compile-only`` compiles every plan for a described v5e instead of
running it, to find the plans Mosaic refuses, and their compile times,
before spending chip time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]

LAYERS = 28


@dataclasses.dataclass(frozen=True)
class Geometry:
    name: str
    S: int
    H: int
    K: int
    hd: int
    causal: bool = False
    window: int = 0
    # rows as segment lengths (packed DiT rows), or a count of unsegmented
    # rows
    rows: Optional[Sequence[Sequence[int]]] = None
    batch: int = 1
    # (block_q, block_k, heads) plans swept besides tile_plan's choice
    plans: Tuple[Tuple[int, int, int], ...] = ()


def _grid(tiles, heads):
    return tuple((bq, bk, hb) for bq, bk in tiles for hb in heads)


GEOMETRIES = (
    # about one weak row in six, as the tiered cells pack them
    Geometry("xl2-256", 256, 16, 16, 72,
             rows=[[256]] * 10 + [[64] * 4] * 2,
             plans=_grid(((128, 128), (256, 256)), (1, 2, 4, 8, 16))),
    Geometry("xl2-512-1024", 1024, 16, 16, 72,
             rows=[[1024]] * 5 + [[256] * 4],
             plans=_grid(((128, 128), (256, 256), (512, 512), (256, 1024),
                          (512, 1024), (1024, 512), (1024, 1024)),
                         (1, 2, 4, 8, 16))),
    Geometry("causal-4096", 4096, 16, 16, 128, causal=True, batch=2,
             plans=_grid(((128, 128), (256, 256), (512, 512)), (1, 4, 16))),
    Geometry("window-4096", 4096, 8, 4, 256, causal=True, window=1024,
             batch=2,
             plans=_grid(((128, 128), (256, 256), (512, 512)), (1, 2, 8))),
)


def seg_ids(rows, S):
    import numpy as np
    ids = np.full((len(rows), S), -1, np.int32)
    for r, lengths in enumerate(rows):
        off = 0
        for i, n in enumerate(lengths):
            ids[r, off:off + n] = i
            off += n
    return ids


def needed_flops(geo: Geometry) -> float:
    """QK^T and PV FLOPs of the visible (query, key) pairs alone."""
    if geo.rows is not None:
        pairs = sum(n * n for r in geo.rows for n in r)
    else:
        q = range(geo.S)
        if geo.window:
            per = [min(i + 1, geo.window) if geo.causal
                   else min(i + geo.window, geo.S) - max(0, i - geo.window
                                                         + 1)
                   for i in q]
        else:
            per = [i + 1 if geo.causal else geo.S for i in q]
        pairs = geo.batch * sum(per)
    return 4.0 * pairs * geo.H * geo.hd


def attend(kernel, plan, geo: Geometry):
    """One kernel call at ``plan`` (None: the public API's own tiles)."""
    def call(q, k, v, seg):
        kw = dict(causal=geo.causal, window=geo.window, segment_ids=seg)
        if plan is None:
            return kernel.ops.flash_attention(q, k, v, **kw)
        return kernel.fa.flash_attention_planned(q, k, v, plan, **kw)
    return call


def chained(call):
    import jax

    def run(q, k, v, seg):
        return jax.lax.fori_loop(0, LAYERS,
                                 lambda _i, x: call(x, k, v, seg), q)
    return jax.jit(run)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", type=Path,
                    default=ROOT / "bench_out" / "flash_sweep.jsonl")
    ap.add_argument("--default-only", action="store_true",
                    help="only the plan tile_plan picks")
    ap.add_argument("--src", type=Path, default=ROOT / "src",
                    help="the checkout's src whose kernel is timed")
    ap.add_argument("--geometry", default="",
                    help="comma-separated geometry names (default: all)")
    args = ap.parse_args()
    sys.path.insert(0, str(args.src.resolve()))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.attention import flash_attention as fa
    from repro.kernels.attention import ops
    from repro.kernels.attention import ref as attn_ref
    kernel = argparse.Namespace(fa=fa, ops=ops)
    planned = hasattr(fa, "tile_plan")

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        # the backend is the CPU here: compile the kernel, do not
        # interpret it (set once, before anything is traced)
        fa.interpret_mode = lambda: False
    elif jax.devices()[0].platform != "tpu":
        print("no TPU: the sweep times the compiled kernel only",
              file=sys.stderr)
        return 1

    wanted = set(filter(None, args.geometry.split(",")))
    args.out.parent.mkdir(parents=True, exist_ok=True)
    lines = []
    for geo in GEOMETRIES:
        if wanted and geo.name not in wanted:
            continue
        B = len(geo.rows) if geo.rows is not None else geo.batch
        q_shape = (B, geo.S, geo.H, geo.hd)
        kv_shape = (B, geo.S, geo.K, geo.hd)
        seg = None if geo.rows is None else seg_ids(geo.rows, geo.S)
        if args.compile_only:
            def sds(shape, dtype):
                return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            qkv = [sds(q_shape, jnp.bfloat16)] + [
                sds(kv_shape, jnp.bfloat16)] * 2
            segs = None if seg is None else sds(seg.shape, jnp.int32)
        else:
            ks = jax.random.split(jax.random.PRNGKey(geo.S + geo.hd), 3)
            qkv = [jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk, shape in zip(ks, (q_shape, kv_shape, kv_shape))]
            segs = None if seg is None else jnp.asarray(seg)
            want = np.asarray(attn_ref.attention_ref(
                *qkv, causal=geo.causal, window=geo.window,
                segment_ids=segs), np.float32)
            real = (np.ones((B, geo.S), bool) if seg is None
                    else seg >= 0)
        default = (fa.tile_plan(geo.S, geo.S, geo.H, geo.K, geo.hd, 2)
                   if planned else None)
        plans = [default]
        if planned and not args.default_only:
            plans += [p for p in (fa.TilePlan(*t) for t in geo.plans)
                      if p != default]
        needed = needed_flops(geo)
        for plan in plans:
            rec = {"geometry": geo.name, "src": str(args.src),
                   "default": plan == default}
            if plan is not None:
                rec.update(block_q=plan.block_q, block_k=plan.block_k,
                           heads=plan.heads,
                           vmem_est=fa.step_vmem_bytes(
                               plan, geo.hd, 2,
                               max(1, plan.heads // (geo.H // geo.K))))
            call = attend(kernel, plan, geo)
            fn = chained(call)
            try:
                if args.compile_only:
                    t0 = time.perf_counter()
                    fn.lower(*qkv, segs).compile()
                    rec["compile_s"] = time.perf_counter() - t0
                else:
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*qkv, segs))
                    rec["first_call_s"] = time.perf_counter() - t0
                    times = []
                    for _ in range(args.reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(*qkv, segs))
                        times.append(time.perf_counter() - t0)
                    per_call = min(times) / LAYERS
                    got = np.asarray(jax.jit(call)(*qkv, segs), np.float32)
                    rec.update(
                        ms_per_call=per_call * 1e3,
                        needed_tflops=needed / per_call / 1e12,
                        max_abs_err=float(np.abs(got - want)[real].max()))
            except Exception as e:          # a refused plan is data
                rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(rec), flush=True)
            lines.append(rec)
    with open(args.out, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
