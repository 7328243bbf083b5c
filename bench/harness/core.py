"""The benchmark's harness: finds a cell in ``BENCHMARK.json``, its
configuration file and its traffic mix by name, runs the configuration's
driver on the chip, and prints one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — the configuration's numbers; its
  ``driver`` key names the module under ``bench/harness/`` that runs it
  and its ``reference`` key the plain reference under ``bench/reference/``;
* ``bench/traffic/<mix>.json`` — the traffic mix's parameters;
* ``bench/metrics/<metric>.py`` — a per-layer metric's reader: a function
  ``read(ctx)`` that returns the metric's value, or None where the run
  gave it nothing to read (the metric is then left out of the line).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the run records a profiler trace of the window and the
metrics are the cell's per-layer metrics.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / ".traces"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
SPAN_PREFIX = "bench:"        # the trace reduction reads spans by it


class BenchError(RuntimeError):
    """A run that cannot give a result: no chip, a missing file, a failed
    step.  The harness exits non-zero and prints no result line."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str, reported: set) -> bool:
    """Whether ``metric`` belongs to ``cell``: named in its ``workloads``,
    or, without that key, in every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


# ------------------------------------------------------------- compiles
class Compiles:
    """Counts XLA compile requests (``jax.monitoring``'s backend-compile
    event, which also fires for programs loaded from the persistent cache)
    and the seconds they took, and how many were persistent-cache hits."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0

    def install(self):
        import jax

        def on_duration(event, duration, **_):
            if event == BACKEND_COMPILE:
                self.requests += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return (self.requests, self.seconds, self.hits)


# ---------------------------------------------------------------- spans
class Spans:
    """Host spans written by the benchmark's own wrappers around the
    program's calls: with the trace on, each is a profiler
    ``TraceAnnotation`` (so the trace can say what the host was doing in a
    device idle gap) and, where ``timed``, also a wall-clock duration
    recorded here after syncing on the wrapped call's result."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.wall: Dict[str, List[tuple]] = {}   # name -> [(t_end, s)]

    def record(self, name: str, t0: float, t1: float):
        self.wall.setdefault(name, []).append((t1, t1 - t0))

    def seconds(self, name: str, t0: float, t1: float) -> List[float]:
        """Recorded durations of ``name`` that ended inside [t0, t1]."""
        return [d for t, d in self.wall.get(name, []) if t0 <= t <= t1]

    @contextmanager
    def span(self, name: str):
        if not self.traced:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield

    def wrap(self, owner, attr: str, name: str, timed: bool = False):
        """Replace ``owner.attr`` by a wrapper that opens span ``name``;
        ``timed`` also blocks on the result (trace run only) and records
        the call's wall seconds.  Returns an undo callable."""
        real = getattr(owner, attr)
        spans = self

        def wrapper(*args, **kw):
            if not spans.traced:
                return real(*args, **kw)
            import jax
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                out = real(*args, **kw)
                if timed:
                    jax.block_until_ready(out)
            if timed:
                spans.record(name, t0, time.monotonic())
            return out

        setattr(owner, attr, wrapper)
        return lambda: setattr(owner, attr, real)


# ------------------------------------------------------------------ run
@dataclass
class Run:
    """One invocation: the cell, its configuration and traffic, the seed,
    and the clocks and counters the drivers and readers share."""
    bm: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    compiles: Compiles
    spans: Spans
    t_window0: Optional[float] = None
    t_window1: Optional[float] = None
    compiles_in_window: Optional[tuple] = None
    trace_path: Optional[Path] = None
    setup_compile_s: Optional[float] = None
    control: Optional[str] = None     # a control run's precision
    gc_pauses: List[tuple] = field(default_factory=list)

    def say(self, text: str):
        """An earlier output line (standard output, before the result)."""
        print(text, flush=True)

    @contextmanager
    def window(self):
        """The measured window: starts the profiler when tracing and counts
        the compile requests made inside it.  The profiler runs on until
        ``end_trace``, so that what a driver does to close the window (the
        round answering its last arrivals) does not wait for the trace to
        be written."""
        c0 = self.compiles.snapshot()
        self.setup_compile_s = c0[1]
        started = {}

        def on_gc(phase, info):
            if phase == "start":
                started["t"] = time.monotonic()
            elif "t" in started:
                self.gc_pauses.append((info["generation"],
                                       time.monotonic() - started.pop("t")))
        gc.callbacks.append(on_gc)
        if self.trace:
            import jax
            import shutil
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
        self.t_window0 = time.monotonic()
        try:
            with self.spans.span("window"):
                yield
        finally:
            self.t_window1 = time.monotonic()
            gc.callbacks.remove(on_gc)
            c1 = self.compiles.snapshot()
            self.compiles_in_window = tuple(b - a for a, b in zip(c0, c1))
            gens = [sum(g == k for g, _ in self.gc_pauses) for k in range(3)]
            self.say(f"gc in window: collections by generation {gens}, "
                     f"longest pause s "
                     f"{max((d for _, d in self.gc_pauses), default=0.0)!r}, "
                     f"all pauses s {sum(d for _, d in self.gc_pauses)!r}")

    def end_trace(self):
        """Stop the profiler (once) and find the trace it wrote."""
        if self.trace and self.trace_path is None:
            import jax
            jax.profiler.stop_trace()
            found = sorted(self.trace_dir.glob("**/*.xplane.pb"))
            if not found:
                raise BenchError("the traced run wrote no .xplane.pb")
            self.trace_path = found[-1]

    @property
    def trace_dir(self) -> Path:
        return TRACE_DIR / f"{self.cell['name']}-{self.seed}"

    @property
    def setup_s(self) -> float:
        return self.t_window0 - self.t_process


@dataclass
class Outcome:
    """What a driver hands back: every end-to-end number it measured, the
    raw readings the per-layer readers take their numbers from, and the
    correctness comparison, each number with its limit."""
    e2e: Dict[str, float]
    readings: Dict
    checks: Dict[str, tuple]
    attempted: int
    failed: int
    device: dict            # device_info, read before the references ran


def _load_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:n_chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def check_device(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}")
    if len(devs) < n_chips:
        raise BenchError(f"the cell needs {n_chips} chips, JAX sees "
                         f"{len(devs)}")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    entry = table["devices"].get(device_kind)
    if entry is None:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json")
    return entry


def enable_compile_cache():
    """The program's persistent compile cache, at its fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every
    program however quick its compile, so that a second run of a cell
    compiles nothing.  Keeping every program is part of the deployment
    each configuration file states (its ``assumed``): a program the
    serving path traces anew on every call is then loaded from the cache,
    not compiled again."""
    import jax
    from repro.launch import platform
    platform.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def make_run(argv, t_process: float, config_override: dict = None) -> Run:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bm = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bm["workloads"]}
    if args.workload not in cells:
        raise BenchError(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = config_override or load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return Run(bm=bm, cell=cell, config=config, traffic=traffic,
               seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
               t_process=t_process, compiles=Compiles(),
               spans=Spans(bool(args.trace)))


def execute(run: Run, check_chip: bool = True) -> dict:
    """Run the cell's driver and assemble the result line's object."""
    if check_chip:
        check_device(run.cell["chips"])
    enable_compile_cache()
    run.compiles.install()
    import jax
    run.say(f"device: platform={jax.devices()[0].platform} "
            f"kind={jax.devices()[0].device_kind} "
            f"count={len(jax.devices())} cell={run.cell['name']} "
            f"config={run.cell['config']} traffic={run.cell['traffic']} "
            f"seed={run.seed} seconds={run.seconds} trace={int(run.trace)}")
    driver = importlib.import_module(f"bench.harness.{run.config['driver']}")
    out: Outcome = driver.run(run)
    device = out.device

    name = run.cell["name"]
    e2e_names = [m for m in run.bm["end_to_end"] if applies(m, name, set())]
    reported = {m["name"] for m in e2e_names}
    metrics = {}
    breakdown = None
    if not run.trace:
        for m in e2e_names:
            value = run.setup_s if m["name"] == "setup_s" \
                else out.e2e.get(m["name"])
            if value is None:
                raise BenchError(f"the driver measured no {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from bench.harness import trace as trace_mod
        run.end_trace()
        reduced = trace_mod.reduce_file(run.trace_path, run.cell["chips"],
                                        run.t_window1 - run.t_window0)
        import shutil
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        ctx = dict(out.readings, run=run, trace=reduced,
                   peaks=peaks(device["kind"]))
        for m in run.bm["per_layer"]:
            if not applies(m, name, reported):
                continue
            value = _load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
    correct = all(v <= lim for v, lim in out.checks.values()) \
        and bool(out.checks)
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # plain floats: a numpy scalar's repr, np.float64(...), would hide the
    # number from a reader of the check lines
    result["checks"] = {k: {"value": float(v), "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    return result


def main(argv, t_process: float) -> int:
    try:
        run = make_run(argv, t_process)
        result = execute(run)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
