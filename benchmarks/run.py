"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Benches that emit a
``BENCH {...}`` json line get that summary persisted: after a run the
harness writes ``benchmarks/BENCH_<suite>.json`` (git sha, timestamp,
one summary dict per bench) so CI diffs and dashboards read artifacts,
not stdout scrollback.

  PYTHONPATH=src python -m benchmarks.run                  # all
  PYTHONPATH=src python -m benchmarks.run fig6 fig12       # substring filter
  PYTHONPATH=src python -m benchmarks.run --suite pipeline # named suite
  PYTHONPATH=src python -m benchmarks.run --suite profile --strict-analysis

Besides the per-run ``BENCH_<suite>.json`` (gitignored), each run also
folds its suite's headline numbers into the COMMITTED compact
``benchmarks/BENCH.json`` — one entry per suite with git sha — so the
perf trajectory is visible in plain git history. ``--strict-analysis``
pre-flights ``python -m repro.analysis --strict src/repro`` and refuses
to run any bench when the static-analysis gate fails.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback
from datetime import datetime, timezone
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

ARTIFACT_DIR = Path(__file__).resolve().parent

# Named suites: exact bench names run together by `--suite <name>`.
SUITES = {
    "pipeline": ("pipeline_cache", "fig6_fid_vs_compute", "fig7_t2i",
                 "adaptive_scheduler", "flow_matching"),
    "distributed": ("distributed_seqpar",),
    "serving": ("serving_engine",),
    "fleet": ("fleet_router",),
    "chaos": ("chaos_resilience",),
    "cache": ("activation_cache",),
    "attention": ("attention_kernel",),
    "analysis": ("static_analysis",),
    "telemetry": ("telemetry",),
    "profile": ("compiled_profile",),
}

#: the committed perf-trajectory file (unlike BENCH_<suite>.json, this
#: one is NOT gitignored — regressions show up in plain `git log -p`)
TRAJECTORY_PATH = ARTIFACT_DIR / "BENCH.json"


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=Path(__file__).resolve().parents[1], capture_output=True,
            text=True, timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


class _BenchCapture:
    """stdout tee that collects ``BENCH {json}`` summary lines."""

    def __init__(self, wrapped):
        self._wrapped = wrapped
        self._buf = ""
        self.summaries = {}

    def write(self, s: str) -> int:
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("BENCH "):
                try:
                    d = json.loads(line[len("BENCH "):])
                    self.summaries[d.get("name", f"bench{len(self.summaries)}")] = d
                except (json.JSONDecodeError, AttributeError):
                    pass
        return self._wrapped.write(s)

    def flush(self) -> None:
        self._wrapped.flush()

    def __getattr__(self, name):
        return getattr(self._wrapped, name)


def write_artifact(suite: str, summaries: dict, sha: str,
                   out_dir: Path = ARTIFACT_DIR) -> Path:
    """Persist one suite run's BENCH summaries as
    ``BENCH_<suite>.json`` (overwritten per run — the git sha inside is
    the provenance, the file name is the stable handle)."""
    out = out_dir / f"BENCH_{suite}.json"
    out.write_text(json.dumps({
        "suite": suite,
        "git_sha": sha,
        "timestamp": datetime.now(timezone.utc).isoformat(),
        "benches": summaries,
    }, indent=1, sort_keys=True) + "\n")
    return out


def _headline(node, prefix: str = "", out: dict = None) -> dict:
    """Flatten one bench summary to dotted-key numeric headlines (the
    same paths ``baselines.json`` bounds use); strings/lists dropped."""
    if out is None:
        out = {}
    if isinstance(node, dict):
        for k, v in sorted(node.items()):
            _headline(v, f"{prefix}.{k}" if prefix else str(k), out)
    elif isinstance(node, bool):
        out[prefix] = int(node)
    elif isinstance(node, (int, float)):
        out[prefix] = node
    return out


def update_trajectory(suite: str, summaries: dict, sha: str,
                      path: Path = TRAJECTORY_PATH) -> Path:
    """Fold one suite run's headline numbers into the committed compact
    trajectory file: other suites' entries are preserved, this suite's
    entry is replaced. No timestamp — the file must be byte-stable for a
    given (sha, results) so re-runs don't dirty the tree."""
    doc = {"suites": {}}
    if path.exists():
        try:
            doc = json.loads(path.read_text())
        except json.JSONDecodeError:
            pass
    doc.setdefault("suites", {})[suite] = {
        "git_sha": sha,
        "benches": {name: _headline(s) for name, s in summaries.items()},
    }
    path.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    return path


def main() -> None:
    from benchmarks import (bench_analysis, bench_attention, bench_cache,
                            bench_chaos, bench_core, bench_distributed,
                            bench_extensions,
                            bench_fleet, bench_modalities, bench_perf,
                            bench_pipeline, bench_profile, bench_serving,
                            bench_telemetry)
    from benchmarks.baseline import BaselineRegression
    from benchmarks.roofline_table import bench_roofline
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    benches = [
        ("fig2_spectral", bench_core.bench_fig2_spectral),
        ("fig4_pred_gap", bench_core.bench_fig4_pred_gap),
        ("fig6_fid_vs_compute", bench_core.bench_fig6_fid_vs_compute),
        ("fig6_T_orthogonality", bench_core.bench_fig6_T_orthogonality),
        ("fig7_t2i", bench_modalities.bench_fig7_t2i),
        ("fig8_video", bench_modalities.bench_fig8_video),
        ("fig10_pruning", bench_core.bench_fig10_pruning_baselines),
        ("fig11_mmd", bench_modalities.bench_fig11_mmd_gap),
        ("fig9_utilization", bench_perf.bench_fig9_utilization),
        ("fig12_packing", bench_perf.bench_fig12_packing),
        ("adaptive_scheduler", bench_extensions.bench_adaptive_scheduler),
        ("flow_matching", bench_extensions.bench_flow_matching),
        ("pipeline_cache", bench_pipeline.bench_pipeline_cache),
        ("distributed_seqpar", bench_distributed.bench_distributed),
        ("serving_engine", bench_serving.bench_serving),
        ("fleet_router", bench_fleet.bench_fleet),
        ("chaos_resilience", bench_chaos.bench_chaos),
        ("activation_cache", bench_cache.bench_cache),
        ("attention_kernel", bench_attention.bench_attention),
        ("static_analysis", bench_analysis.bench_analysis),
        ("telemetry", bench_telemetry.bench_telemetry),
        ("compiled_profile", bench_profile.bench_profile),
        ("roofline", bench_roofline),
    ]
    argv = sys.argv[1:]
    if "--strict-analysis" in argv:
        argv.remove("--strict-analysis")
        root = Path(__file__).resolve().parents[1]
        env = dict(os.environ)
        env["PYTHONPATH"] = (str(root / "src")
                             + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else ""))
        # the analysis CLI only traces jaxprs: keep it off the chip this
        # process may hold
        env["JAX_PLATFORMS"] = "cpu"
        rc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--strict",
             "src/repro"], cwd=root, env=env).returncode
        if rc != 0:
            raise SystemExit("# strict-analysis pre-flight failed "
                             f"(exit {rc}); refusing to run benches")
        print("# strict-analysis pre-flight passed", flush=True)
    suite = None
    if "--suite" in argv:
        i = argv.index("--suite")
        if i + 1 >= len(argv):
            raise SystemExit(f"--suite needs a name; known: {sorted(SUITES)}")
        suite = argv[i + 1]
        if suite not in SUITES:
            raise SystemExit(f"unknown suite {suite!r}; known: {sorted(SUITES)}")
        del argv[i:i + 2]
    filters = [a for a in argv if not a.startswith("-")]
    cap = _BenchCapture(sys.stdout)
    sys.stdout = cap
    print("name,us_per_call,derived")
    regressions = []
    errors = []
    try:
        for name, fn in benches:
            if suite is not None and name not in SUITES[suite]:
                continue
            if filters and not any(f in name for f in filters):
                continue
            t0 = time.time()
            try:
                fn()
                print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
            except BaselineRegression as e:
                # a recorded analytic baseline was violated: keep running the
                # remaining benches, but fail the harness loudly at the end
                regressions.append((name, str(e)))
                print(f"{name},0.0,REGRESSION:{e}", flush=True)
            except Exception as e:  # keep the harness running
                # ... but fail it at the end: a bench that raised has no
                # result, and an ERROR row must not read as a pass
                traceback.print_exc()
                errors.append((name, f"{type(e).__name__}: {e}"))
                print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
    finally:
        sys.stdout = cap._wrapped
    if cap.summaries:
        sha = _git_sha()
        out = write_artifact(suite or "all", cap.summaries, sha)
        print(f"# wrote {out} ({len(cap.summaries)} bench summaries)",
              flush=True)
        traj = update_trajectory(suite or "all", cap.summaries, sha)
        print(f"# updated trajectory {traj}", flush=True)
    for name, msg in regressions:
        print(f"# BASELINE REGRESSION in {name}: {msg}",
              file=sys.stderr, flush=True)
    for name, msg in errors:
        print(f"# BENCH ERROR in {name}: {msg}", file=sys.stderr, flush=True)
    if regressions or errors:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
