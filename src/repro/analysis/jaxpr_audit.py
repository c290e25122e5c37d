"""Level 2 — jaxpr structural auditor (DESIGN.md §analysis).

Traces the repo's REAL step-function families with ``jax.make_jaxpr``
over a tiny (but fully flexified) DiT and computes a **structural
fingerprint** of each closed jaxpr: primitives, operand/result avals,
equation params (sub-jaxprs walked recursively), and — crucially —
value digests of the trace-time *constants*. Arguments are abstracted
by ``make_jaxpr``, so any input-value dependence that survives into the
fingerprint must have leaked through a closure or been baked as a
constant: exactly the recompile-hazard bug class.

Invariances asserted (``jaxpr-fingerprint-drift`` on violation):

* the packed step function, traced at two different timestep-ladder
  metas (a budget switch in the serving engine is *only* a metas
  change);
* the packed cached step, traced at two different refresh-flag
  patterns (a cache-policy switch is *only* a flag change);
* two independently built ``FlexiPipeline`` cached runners whose
  ``CacheSpec`` differ in every data-only knob (policy / interval /
  threshold) at the same split;
* the dense attention backend traced at two different segment-id
  contents at fixed geometry (a pack-layout occupancy change);
* the plain eps + DDIM step at two different timesteps;
* the tapped packed step (``taps=True``, DESIGN.md §telemetry): dead-code
  eliminating the tap outputs must recover the untapped jaxpr **exactly**
  (``pe.dce_jaxpr_consts`` keeping only the primary outputs), proving taps
  are pure extra data — they read the step's existing intermediates and
  feed nothing back; and the tapped family must itself be
  ladder/policy-invariant (turning telemetry on costs zero recompiles
  across budget or policy switches).

What the fingerprint does NOT prove: full phase-runner equality across
*budgets* — a budget switch changes the phase split, so those jaxprs
legitimately differ and zero-recompile there is cache *replay*,
guarded by the cache-key completeness rule plus the runtime recompile
counters in the benches (DESIGN.md §analysis).

Each traced jaxpr is also walked (into every sub-jaxpr) for host
callbacks, silent widening dtype conversions, and the ``jax.jit`` entry
points of the hot pipeline path are checked for buffer donation.
"""
from __future__ import annotations

import ast
import dataclasses
import functools
import hashlib
import re
from typing import Any, Callable, Dict, Iterable, List, Tuple

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np

from repro.analysis.engine import REPO_ROOT, Finding, relpath

PIPELINE_PATH = "src/repro/pipeline/pipeline.py"

#: primitives that call back into Python from compiled code
HOST_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                       "outside_call", "host_callback"}

#: silent widenings worth flagging ({} entries are (operand, result))
WIDENINGS = {("float32", "float64"), ("bfloat16", "float32"),
             ("float16", "float32")}

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


# ---------------------------------------------------------------------------
# Fingerprinting


def _digest_value(x: Any) -> str:
    arr = np.asarray(x)
    h = hashlib.sha256()
    h.update(str(arr.dtype).encode())
    h.update(str(arr.shape).encode())
    h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:16]


def _aval_str(v: Any) -> str:
    if isinstance(v, jcore.Literal):
        return f"lit#{_digest_value(v.val)}"
    a = v.aval
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    return f"{dtype}{tuple(shape) if shape is not None else ''}"


def _canon_param(v: Any) -> str:
    """Equation params, canonicalized: sub-jaxprs recurse structurally,
    callables reduce to qualnames, arrays to value digests, and memory
    addresses are stripped from reprs."""
    if isinstance(v, jcore.ClosedJaxpr):
        return "{" + _canon_closed(v) + "}"
    if isinstance(v, jcore.Jaxpr):
        return "{" + _canon_closed(jcore.ClosedJaxpr(v, ())) + "}"
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_canon_param(x) for x in v) + ")"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{_canon_param(x)}"
                              for k, x in sorted(v.items())) + "}"
    if isinstance(v, (np.ndarray, jax.Array)):
        return f"arr#{_digest_value(v)}"
    if callable(v):
        return getattr(v, "__qualname__", None) or type(v).__name__
    return _ADDR_RE.sub("0x", repr(v))


def _canon_closed(closed: jcore.ClosedJaxpr) -> str:
    j = closed.jaxpr
    parts = ["in:" + ",".join(_aval_str(v) for v in j.invars),
             "const:" + ",".join(
                 f"{_aval_str(v)}#{_digest_value(c)}"
                 for v, c in zip(j.constvars, closed.consts))]
    for eqn in j.eqns:
        ps = ";".join(f"{k}={_canon_param(v)}"
                      for k, v in sorted(eqn.params.items()))
        parts.append(
            f"{eqn.primitive.name}"
            f"({','.join(_aval_str(v) for v in eqn.invars)})"
            f"->({','.join(_aval_str(v) for v in eqn.outvars)})[{ps}]")
    parts.append("out:" + ",".join(_aval_str(v) for v in j.outvars))
    return "\n".join(parts)


def fingerprint(closed: jcore.ClosedJaxpr) -> str:
    """Stable structural digest of a closed jaxpr (incl. constant
    values — baked data is a per-trace recompile hazard)."""
    return hashlib.sha256(_canon_closed(closed).encode()).hexdigest()[:32]


def _iter_eqns(closed: jcore.ClosedJaxpr):
    """Every equation, recursing into sub-jaxprs (scan/cond/pjit/...)."""
    stack = [closed.jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))


def _sub_jaxprs(v: Any) -> List[jcore.Jaxpr]:
    if isinstance(v, jcore.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jcore.Jaxpr):
        return [v]
    if isinstance(v, (tuple, list)):
        out: List[jcore.Jaxpr] = []
        for x in v:
            out.extend(_sub_jaxprs(x))
        return out
    return []


# ---------------------------------------------------------------------------
# Per-jaxpr violation walks


def check_jaxpr(closed: jcore.ClosedJaxpr, unit: str,
                path: str = PIPELINE_PATH) -> List[Finding]:
    findings: List[Finding] = []
    for eqn in _iter_eqns(closed):
        name = eqn.primitive.name
        if name in HOST_CALLBACK_PRIMS:
            findings.append(Finding(
                "jaxpr-host-callback", "error", path, 0,
                f"`{name}` in the {unit} jaxpr — compiled hot path "
                f"calls back into Python", unit))
        elif name == "convert_element_type":
            src = eqn.invars[0]
            if isinstance(src, jcore.Literal):
                continue
            if getattr(src.aval, "weak_type", False):
                continue          # python-scalar promotion, not a leak
            old = str(getattr(src.aval, "dtype", ""))
            new = str(eqn.params.get("new_dtype", ""))
            if (old, new) in WIDENINGS:
                findings.append(Finding(
                    "jaxpr-dtype-promotion", "error", path, 0,
                    f"silent {old}->{new} widening in the {unit} jaxpr",
                    unit))
    return findings


# ---------------------------------------------------------------------------
# Tiny audited model (mirrors tests/conftest.py, kept self-contained so
# `python -m repro.analysis` works outside pytest)


@functools.lru_cache(maxsize=1)
def _tiny():
    from repro.configs.base import AttnConfig, DiTConfig, ModelConfig
    from repro.core import flexify
    from repro.diffusion import schedule as sch
    from repro.models import dit as dit_mod
    cfg = ModelConfig(
        name="audit-dit", family="dit", num_layers=2, d_model=64, d_ff=256,
        vocab_size=0, attn=AttnConfig(4, 4, 16, use_rope=False),
        dit=DiTConfig(latent_shape=(1, 16, 16, 4), patch_size=(1, 2, 2),
                      flex_patch_sizes=(), underlying_patch_size=(1, 2, 2),
                      conditioning="class", num_classes=10),
        mlp_activation="gelu", norm_type="layernorm",
        param_dtype="float32", compute_dtype="float32", remat="none",
        max_seq_len=256)
    params = dit_mod.init_dit(cfg, jax.random.PRNGKey(0))
    fparams, fcfg = flexify(params, cfg, [(1, 4, 4)])
    sched = sch.linear_schedule(100)
    return fparams, fcfg, sched


@dataclasses.dataclass
class AuditReport:
    findings: List[Finding]
    fingerprints: Dict[str, str]


def _drift(unit: str, fps: Dict[str, str], what: str) -> List[Finding]:
    """One finding if the fingerprints in ``fps`` are not all equal."""
    if len(set(fps.values())) <= 1:
        return []
    detail = ", ".join(f"{k}={v[:10]}" for k, v in fps.items())
    return [Finding(
        "jaxpr-fingerprint-drift", "error", PIPELINE_PATH, 0,
        f"{unit}: jaxpr fingerprint differs across {what} — a data-only "
        f"switch recompiles ({detail})", unit)]


def _trace(unit: str, fn: Callable, *args
           ) -> Tuple[jcore.ClosedJaxpr | None, List[Finding]]:
    try:
        return jax.make_jaxpr(fn)(*args), []
    except Exception as e:      # ConcretizationTypeError, shape leaks, ...
        return None, [Finding(
            "jaxpr-trace-failure", "error", PIPELINE_PATH, 0,
            f"{unit} no longer traces: {type(e).__name__}: {e}", unit)]


# ---------------------------------------------------------------------------
# Audited units


def audit_plain_step() -> AuditReport:
    """Guided eps + DDIM update, traced at two timesteps."""
    from repro.core.guidance import GuidanceConfig, make_eps_fn
    from repro.diffusion import schedule as sch
    fparams, fcfg, sched = _tiny()
    B = 2
    cond = jnp.zeros((B,), jnp.int32)
    null = jnp.full((B,), fcfg.dit.num_classes, jnp.int32)
    eps = make_eps_fn(fparams, fcfg, cond, null,
                      GuidanceConfig(scale=1.5, mode_cond=0, mode_uncond=0))

    def step(x, t, t_next):
        e, _lv = eps(x, t)
        return sch.ddim_step(sched, x, e, t, t_next)

    x = jnp.zeros((B,) + fcfg.dit.latent_shape, jnp.float32)
    findings: List[Finding] = []
    fps: Dict[str, str] = {}
    last = None
    for tag, (t, tn) in {"t=90": (90, 80), "t=10": (10, 0)}.items():
        closed, errs = _trace("plain_step", step, x,
                              jnp.full((B,), t, jnp.int32),
                              jnp.full((B,), tn, jnp.int32))
        findings.extend(errs)
        if closed is None:
            continue
        fps[tag] = fingerprint(closed)
        last = closed
    findings.extend(_drift("plain_step", fps, "timesteps"))
    if last is not None:
        findings.extend(check_jaxpr(last, "plain_step"))
    return AuditReport(findings, {"plain_step": fps.get("t=90", "")})


def _packed_args(layout, k_steps: int, ts: Iterable[int],
                 cache_split: int | None = None):
    from repro.cache import apply as cache_apply
    fparams, fcfg, _sched = _tiny()
    ts = list(ts)
    xs, metas, keys, deltas, refreshes = [], [], [], [], []
    for mode, n in layout.groups:
        xs.append(jnp.zeros((n,) + fcfg.dit.latent_shape, jnp.float32))
        rows = []
        for s in range(k_steps):
            t = ts[s % len(ts)]
            rows.append([[t] * n, [max(t - 10, -1)] * n, [0] * n])
        metas.append(jnp.asarray(rows, jnp.int32))
        keys.append(jnp.zeros((k_steps, n, 2), jnp.uint32))
        if cache_split is not None:
            _eb, N, d = cache_apply.delta_shape(fcfg, mode, n, layout.guided)
            mult = 2 if layout.guided else 1
            deltas.append(jnp.zeros((n, mult, N, d), jnp.float32))
            refreshes.append(jnp.ones((k_steps, n), bool))
    if cache_split is None:
        return fparams, xs, metas, keys
    return fparams, xs, metas, keys, deltas, refreshes


def audit_packed_step() -> AuditReport:
    """Packed step fn: a budget switch is a metas-value change only."""
    from repro.pipeline.packed import PackLayout, make_packed_step_fn
    fparams, fcfg, sched = _tiny()
    layout = PackLayout(groups=((0, 1), (1, 2)), guided=True)
    step = make_packed_step_fn(fcfg, sched, layout, k_steps=2)
    findings: List[Finding] = []
    fps: Dict[str, str] = {}
    last = None
    for tag, ladder in {"ladder-hi": (90, 80), "ladder-lo": (30, 20)}.items():
        args = _packed_args(layout, 2, ladder)
        closed, errs = _trace("packed_step", step, *args)
        findings.extend(errs)
        if closed is None:
            continue
        fps[tag] = fingerprint(closed)
        last = closed
    findings.extend(_drift("packed_step", fps, "budget ladders"))
    if last is not None:
        findings.extend(check_jaxpr(last, "packed_step"))
    return AuditReport(findings, {"packed_step": fps.get("ladder-hi", "")})


def audit_packed_cached_step() -> AuditReport:
    """Cached packed step: a policy switch is a refresh-flag change only."""
    from repro.pipeline.packed import PackLayout, make_packed_step_fn
    fparams, fcfg, sched = _tiny()
    layout = PackLayout(groups=((0, 1), (1, 2)), guided=True)
    step = make_packed_step_fn(fcfg, sched, layout, k_steps=2,
                               cache_split=1)
    findings: List[Finding] = []
    fps: Dict[str, str] = {}
    last = None
    for tag, flip in {"refresh-all": False, "refresh-alt": True}.items():
        args = list(_packed_args(layout, 2, (90, 80), cache_split=1))
        if flip:
            args[5] = [r.at[1::2].set(False) for r in args[5]]
        closed, errs = _trace("packed_cached_step", step, *args)
        findings.extend(errs)
        if closed is None:
            continue
        fps[tag] = fingerprint(closed)
        last = closed
    findings.extend(_drift("packed_cached_step", fps, "refresh policies"))
    if last is not None:
        findings.extend(check_jaxpr(last, "packed_cached_step"))
    return AuditReport(findings,
                       {"packed_cached_step": fps.get("refresh-all", "")})


def audit_cached_runner() -> AuditReport:
    """Two independently built cached runners whose CacheSpec differ in
    every data-only knob (same split) must trace identically."""
    from repro.cache import policy as cache_policy
    from repro.cache.policy import CacheSpec
    from repro.diffusion import schedule as sch
    from repro.pipeline import FlexiPipeline, SamplingPlan
    fparams, fcfg, sched = _tiny()
    pipe = FlexiPipeline(fparams, fcfg, sched)
    B = 2
    findings: List[Finding] = []
    fps: Dict[str, str] = {}
    last = None
    for tag, spec in {
        "interval": CacheSpec(policy="interval", interval=2, split=1),
        "proxy": CacheSpec(policy="proxy", threshold=0.1, split=1),
    }.items():
        plan = SamplingPlan(T=6, cache=spec)
        ts = sch.respaced_timesteps(sched.num_steps, plan.T)
        schedule = plan.resolve_schedule(fcfg)
        runner = pipe._cached_runner(plan, schedule, ts)
        masks = tuple(jnp.asarray(cache_policy.refresh_mask(spec, tsub))
                      for _m, tsub in schedule.split_timesteps(ts))
        x_T = jnp.zeros((B,) + fcfg.dit.latent_shape, jnp.float32)
        cond = jnp.zeros((B,), jnp.int32)
        null = jnp.full((B,), fcfg.dit.num_classes, jnp.int32)
        closed, errs = _trace(
            "cached_runner", runner, (fparams,), x_T, cond, null,
            jax.random.PRNGKey(0), None, None, masks)
        findings.extend(errs)
        if closed is None:
            continue
        fps[tag] = fingerprint(closed)
        last = closed
    findings.extend(_drift("cached_runner", fps,
                           "cache policies (same split)"))
    if last is not None:
        findings.extend(check_jaxpr(last, "cached_runner"))
    return AuditReport(findings, {"cached_runner": fps.get("interval", "")})


def _dce_keep_primary(closed: jcore.ClosedJaxpr,
                      n_keep: int) -> jcore.ClosedJaxpr:
    """Dead-code-eliminate all but the first ``n_keep`` outputs, dropping
    the constants whose constvars die with them."""
    from jax.interpreters import partial_eval as pe
    used = [True] * n_keep + [False] * (len(closed.jaxpr.outvars) - n_keep)
    dj, used_consts, _used_in = pe.dce_jaxpr_consts(closed.jaxpr, used)
    consts = [c for c, u in zip(closed.consts, used_consts) if u]
    return jcore.ClosedJaxpr(dj, consts)


def audit_tapped_step() -> AuditReport:
    """Telemetry taps are data, not structure (DESIGN.md §telemetry).

    For the plain and cached packed families: DCE-ing the tap outputs
    out of the tapped jaxpr must reproduce the untapped jaxpr
    fingerprint byte-for-byte (both sides normalized through the same
    DCE pass), and the tapped jaxpr must be invariant under the same
    data-only switches PR 6 proves for the untapped one."""
    from repro.pipeline.packed import PackLayout, make_packed_step_fn
    fparams, fcfg, sched = _tiny()
    layout = PackLayout(groups=((0, 1), (1, 2)), guided=True)
    findings: List[Finding] = []
    fingerprints: Dict[str, str] = {}
    for split, unit in ((None, "packed_step_tapped"),
                        (1, "packed_cached_step_tapped")):
        off = make_packed_step_fn(fcfg, sched, layout, k_steps=2,
                                  cache_split=split)
        on = make_packed_step_fn(fcfg, sched, layout, k_steps=2,
                                 cache_split=split, taps=True)
        fps: Dict[str, str] = {}
        last = None
        for tag, ladder in {"ladder-hi": (90, 80),
                            "ladder-lo": (30, 20)}.items():
            args = _packed_args(layout, 2, ladder, cache_split=split)
            ct, errs = _trace(unit, on, *args)
            findings.extend(errs)
            if ct is None:
                continue
            fps[tag] = fingerprint(ct)
            last = ct
            co, errs = _trace(unit, off, *args)
            findings.extend(errs)
            if co is None:
                continue
            n_primary = len(co.jaxpr.outvars)
            dce_t = fingerprint(_dce_keep_primary(ct, n_primary))
            dce_o = fingerprint(_dce_keep_primary(co, n_primary))
            if dce_t != dce_o:
                findings.append(Finding(
                    "jaxpr-tap-structure", "error", PIPELINE_PATH, 0,
                    f"{unit} ({tag}): DCE-ing the tap outputs does not "
                    f"recover the untapped jaxpr ({dce_t[:10]} != "
                    f"{dce_o[:10]}) — taps changed the step's structure, "
                    f"not just its outputs", unit))
        findings.extend(_drift(unit, fps, "budget ladders (taps on)"))
        if last is not None:
            findings.extend(check_jaxpr(last, unit))
            fingerprints[unit] = fps.get("ladder-hi", "")
    return AuditReport(findings, fingerprints)


def audit_attention_segments() -> AuditReport:
    """Dense attention backend at fixed geometry, two segment-id
    contents (a pack-layout occupancy change)."""
    from repro.models import attention as attn_mod
    fparams, fcfg, _sched = _tiny()
    a = fcfg.attn
    d = fcfg.d_model
    params = {
        "wq": jnp.zeros((d, a.num_heads, a.head_dim)),
        "wk": jnp.zeros((d, a.num_kv_heads, a.head_dim)),
        "wv": jnp.zeros((d, a.num_kv_heads, a.head_dim)),
        "wo": jnp.zeros((a.num_heads, a.head_dim, d)),
    }
    S = 32
    x = jnp.zeros((1, S, d), jnp.float32)
    seg_a = jnp.concatenate(
        [jnp.zeros((1, S // 2), jnp.int32), jnp.ones((1, S // 2), jnp.int32)],
        axis=1)
    seg_b = jnp.zeros((1, S), jnp.int32)

    def run(x, seg):
        return attn_mod.attention(params, x, a, causal=False,
                                  segment_ids=seg, backend="xla")

    findings: List[Finding] = []
    fps: Dict[str, str] = {}
    last = None
    for tag, seg in {"two-seg": seg_a, "one-seg": seg_b}.items():
        closed, errs = _trace("attention_segments", run, x, seg)
        findings.extend(errs)
        if closed is None:
            continue
        fps[tag] = fingerprint(closed)
        last = closed
    findings.extend(_drift("attention_segments", fps,
                           "segment-id contents"))
    if last is not None:
        findings.extend(check_jaxpr(last, "attention_segments"))
    return AuditReport(findings,
                       {"attention_segments": fps.get("two-seg", "")})


# ---------------------------------------------------------------------------
# Donation check (AST over the hot pipeline path: jit entry points that
# carry large recurrent buffers should donate them)


def audit_donation(path: str = PIPELINE_PATH) -> AuditReport:
    findings: List[Finding] = []
    src = (REPO_ROOT / path)
    if not src.exists():
        return AuditReport([], {})
    tree = ast.parse(src.read_text(), filename=str(src))
    stack: List[str] = []

    class V(ast.NodeVisitor):
        def visit_FunctionDef(self, node):
            stack.append(node.name)
            self.generic_visit(node)
            stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            f = node.func
            is_jit = (isinstance(f, ast.Attribute) and f.attr == "jit") or \
                     (isinstance(f, ast.Name) and f.id == "jit")
            if is_jit and not any(k.arg and "donate" in k.arg
                                  for k in node.keywords):
                sym = stack[-1] if stack else "<module>"
                findings.append(Finding(
                    "jaxpr-nondonated-hotbuf", "error", path, node.lineno,
                    f"hot-path jax.jit in `{sym}` does not donate its "
                    f"recurrent buffers (x_T/deltas re-allocate per call)",
                    sym))
            self.generic_visit(node)

    V().visit(tree)
    return AuditReport(findings, {})


# ---------------------------------------------------------------------------
# Entry point


def audit_step_functions() -> AuditReport:
    """Run every audit unit; units that cannot even build surface as
    ``jaxpr-trace-failure`` findings rather than crashing the CLI."""
    findings: List[Finding] = []
    fingerprints: Dict[str, str] = {}
    units = [audit_plain_step, audit_packed_step, audit_packed_cached_step,
             audit_cached_runner, audit_tapped_step,
             audit_attention_segments, audit_donation]
    for unit in units:
        try:
            rep = unit()
        except Exception as e:
            findings.append(Finding(
                "jaxpr-trace-failure", "error", PIPELINE_PATH, 0,
                f"audit unit {unit.__name__} failed to build: "
                f"{type(e).__name__}: {e}", unit.__name__))
            continue
        findings.extend(rep.findings)
        fingerprints.update(rep.fingerprints)
    return AuditReport(findings, fingerprints)
