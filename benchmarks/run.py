"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines AND lands each module's full
measurement trajectory as ``BENCH_<tag>.json`` (records + run config + git
sha) in ``--json-dir`` (default: repo root), so benchmark claims are
reproducible artifacts, not scrollback.  ``--quick`` trims sweeps.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig06]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

MODULES = [
    ("fig06_07", "benchmarks.fig06_07_models"),
    ("fig08_09", "benchmarks.fig08_09_qoe_threshold"),
    ("fig10_11", "benchmarks.fig10_11_finish_time"),
    ("fig12_13", "benchmarks.fig12_13_vs_baselines"),
    ("fig14_19", "benchmarks.fig14_19_network"),
    ("ligd", "benchmarks.ligd_convergence"),
    ("batched", "benchmarks.batched_solver"),
    ("sharded", "benchmarks.sharded_solver"),
    ("multihost", "benchmarks.multihost_solver"),
    ("eraplus", "benchmarks.era_plus"),
    ("kernels", "benchmarks.kernel_bench"),
    ("era_step", "benchmarks.era_step"),
    ("multipod", "benchmarks.multipod_scaling"),
    ("online", "benchmarks.online_rescheduling"),
    ("admission", "benchmarks.async_admission"),
    ("cluster", "benchmarks.cluster_churn"),
    ("load", "benchmarks.load_harness"),
]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — benchmarks must run without git
        return "unknown"


def skipped_of(records):
    """Names+reasons of lanes a module recorded via ``common.emit_skip``."""
    return [(r["name"], r["derived"]) for r in records if r.get("skipped")]


def write_json(tag: str, modname: str, records, *, quick: bool,
               elapsed_s: float, json_dir: str) -> str:
    import jax

    from repro.launch import platform as _platform
    # the EFFECTIVE environment (preset name, XLA_FLAGS as jax saw them,
    # forced host device count, allocator preload) — without it, numbers
    # measured under `make bench` and under an ad-hoc shell with
    # XLA_FLAGS exported look like the same run and diff as regressions
    config = {
        "quick": quick,
        "jax_version": jax.__version__,
        # the module's own wall time belongs with the run conditions: a
        # BENCH diff that shows a derived-metric regression next to a
        # 10x module_wall_s change is a different machine/load story,
        # not a code regression
        "module_wall_s": round(elapsed_s, 3),
    }
    config.update(_platform.describe())
    payload = {
        "benchmark": tag,
        "module": modname,
        "git_sha": git_sha(),
        "config": config,
        "elapsed_s": round(elapsed_s, 3),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "records": list(records),
    }
    # skipped lanes surfaced at the top level too, so a reader (or diff)
    # does not have to scan every record to notice partial coverage
    skipped = skipped_of(records)
    if skipped:
        payload["skipped"] = [{"name": n, "reason": r} for n, r in skipped]
    # quick runs land under a distinct name so trimmed-sweep numbers can
    # never silently clobber a committed full-run BENCH_<tag>.json
    suffix = ".quick.json" if quick else ".json"
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{tag}{suffix}")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter on the module tag")
    ap.add_argument("--json-dir", default=_REPO_ROOT,
                    help="where BENCH_<tag>.json files land "
                         "(default: repo root)")
    args = ap.parse_args()

    from benchmarks import common
    from repro.launch import platform
    platform.enable_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    all_skipped = []
    for tag, modname in MODULES:
        if args.only and args.only not in tag:
            continue
        mod = __import__(modname, fromlist=["run"])
        t1 = time.time()
        common.RECORDS.clear()
        mod.run(quick=args.quick)
        dt = time.time() - t1
        path = write_json(tag, modname, common.RECORDS, quick=args.quick,
                          elapsed_s=dt, json_dir=args.json_dir)
        print(f"# {tag} done in {dt:.1f}s -> {path}", file=sys.stderr)
        for name, reason in skipped_of(common.RECORDS):
            print(f"# !! {tag}: SKIPPED {name} ({reason})", file=sys.stderr)
            all_skipped.append((tag, name, reason))
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    if all_skipped:
        print(f"# !! {len(all_skipped)} lane(s) did not run:",
              file=sys.stderr)
        for tag, name, reason in all_skipped:
            print(f"# !!   {tag}/{name}: {reason}", file=sys.stderr)


if __name__ == "__main__":
    main()
