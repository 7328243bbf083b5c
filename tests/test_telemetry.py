"""Telemetry bus + sinks + instrumented serving seams.

Unit layer: ring-buffer bounding, streaming aggregates (P² quantile
sketch vs exact numpy quantiles), injectable clock, drain/snapshot
semantics, JSONL FileSink round-trip, thread-safety of concurrent
emitters.  Integration layer: the admission/engine/cluster event streams
documented in README "Observability" actually appear — round phases,
solve wall time, swap-to-serve lag, per-cell QoE attainment, bounded
``round_error`` backlog — all under a fake clock, no numpy sort on the
emit path.  Phase spans (``telemetry.span``): the admission round's and
the serve round's phase fields partition their wall time, compile
requests land in the phase that made them, the ``era.`` spans show on a
profiler trace with their bus fields' durations, and the sweep's ops
carry their named scopes."""
import glob
import io
import json
import os
import threading
import tracemalloc

import numpy as np
import pytest

import repro.telemetry.bus as bus_mod
from repro.telemetry import Event, FileSink, TelemetryBus

pytestmark = pytest.mark.telemetry

TELEMETRY_DIR = os.path.dirname(bus_mod.__file__)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ------------------------------------------------------------------ bus
def test_emit_snapshot_and_injected_clock():
    clock = FakeClock()
    bus = TelemetryBus(clock=clock)
    bus.emit("round", n=1)
    clock.advance(2.5)
    bus.emit("round", n=2, note="second")
    evs = bus.snapshot("round")
    assert [e.t for e in evs] == [0.0, 2.5]
    assert evs[0] == Event(0.0, "round", {"n": 1})
    assert evs[1].fields == {"n": 2, "note": "second"}
    assert bus.count("round") == 2
    assert bus.streams() == ["round"]
    assert bus.snapshot("never") == [] and bus.count("never") == 0


def test_ring_bounded_but_aggregates_cover_history():
    bus = TelemetryBus(capacity=8)
    for i in range(100):
        bus.emit("s", v=float(i))
    evs = bus.snapshot("s")
    assert len(evs) == 8                      # ring kept the tail...
    assert [e.fields["v"] for e in evs] == [float(i) for i in range(92, 100)]
    s = bus.summary("s", "v")
    assert s.count == 100                     # ...aggregates kept it all
    assert s.min == 0.0 and s.max == 99.0
    assert s.mean == pytest.approx(49.5)


def test_drain_clears_window_not_aggregates():
    bus = TelemetryBus()
    for i in range(10):
        bus.emit("s", v=float(i))
    assert len(bus.drain("s")) == 10
    assert bus.snapshot("s") == []
    assert bus.count("s") == 10
    assert bus.summary("s", "v").count == 10
    assert bus.drain("s") == []


def test_non_numeric_and_bool_fields_not_aggregated():
    bus = TelemetryBus()
    bus.emit("s", kind="swap", ok=True, n=3)
    assert bus.summary("s", "kind") is None
    assert bus.summary("s", "ok") is None     # bool is not a metric
    assert bus.summary("s", "n").count == 1
    # but all fields ride on the event itself
    assert bus.snapshot("s")[0].fields == {"kind": "swap", "ok": True,
                                           "n": 3}


def test_summary_of_missing_stream_or_field_is_none():
    bus = TelemetryBus()
    bus.emit("s", v=1.0)
    assert bus.summary("s", "w") is None
    assert bus.summary("t", "v") is None


def test_p2_quantiles_match_numpy():
    rng = np.random.default_rng(0)
    bus = TelemetryBus(capacity=16)           # far smaller than the stream
    xs = rng.lognormal(mean=0.0, sigma=1.0, size=20_000)
    for x in xs:
        bus.emit("lat", v=float(x))
    s = bus.summary("lat", "v")
    for got, p in ((s.p50, 50), (s.p95, 95), (s.p99, 99)):
        exact = float(np.percentile(xs, p))
        assert got == pytest.approx(exact, rel=0.05), (p, got, exact)


def test_small_sample_quantiles_exact():
    bus = TelemetryBus()
    for x in (3.0, 1.0, 2.0):
        bus.emit("s", v=x)
    s = bus.summary("s", "v")
    assert s.p50 == 2.0
    bus2 = TelemetryBus()
    assert bus2.summary("s", "v") is None


def test_concurrent_emitters_lose_nothing():
    bus = TelemetryBus(capacity=100_000)
    n, threads = 2_000, 8

    def work(k):
        for i in range(n):
            bus.emit("s", v=float(i), src=k)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert bus.count("s") == n * threads
    assert bus.summary("s", "v").count == n * threads


def test_capacity_validation():
    with pytest.raises(ValueError):
        TelemetryBus(capacity=0)


# ---------------------------------------------------------------- sinks
def test_file_sink_jsonl_roundtrip(tmp_path):
    path = tmp_path / "trace.jsonl"
    clock = FakeClock()
    bus = TelemetryBus(clock=clock)
    sink = FileSink(path)
    bus.attach(sink)
    bus.emit("round", n=1, arr=np.float32(2.5))
    clock.advance(1.0)
    bus.emit("swap", version=3, kind="install")
    bus.close()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines == [
        {"t": 0.0, "event": "round", "n": 1, "arr": 2.5},
        {"t": 1.0, "event": "swap", "version": 3, "kind": "install"},
    ]


def test_detached_sink_sees_nothing_more():
    buf = io.StringIO()
    bus = TelemetryBus()
    sink = FileSink(buf, flush_every=1)
    bus.attach(sink)
    bus.emit("a")
    bus.detach(sink)
    bus.emit("b")
    assert [json.loads(ln)["event"]
            for ln in buf.getvalue().splitlines()] == ["a"]


# ----------------------------------------- instrumented serving seams
def _cluster(bus, governor=None, n_cells=2, n_users=6):
    import jax

    from repro.core import network, profiles
    from repro.core.ligd import SolverSpec
    from repro.serving.cluster import SplitInferenceCluster

    ncfg = network.small_config(n_users=n_users, n_subchannels=3)
    scns = [network.make_scenario(jax.random.PRNGKey(s), ncfg)
            for s in range(n_cells)]
    clock = FakeClock()
    if bus is not None:
        bus.clock = clock
    cluster = SplitInferenceCluster(
        None, None, profiles.get_profile("nin"),
        spec=SolverSpec(max_steps=5, per_user_split=False),
        clock=clock, bus=bus, governor=governor)
    ids = [cluster.add_cell(scn, 0.4) for scn in scns]
    cluster.start(threaded=False)
    return cluster, ids, clock


def test_serving_stack_emits_documented_streams():
    bus = TelemetryBus()
    cluster, ids, clock = _cluster(bus)
    assert bus.count("bootstrap") == 1
    boot = bus.snapshot("bootstrap")[0].fields
    assert boot["version"] == 1 and boot["n_cells"] == 2
    assert boot["solve_wall_s"] > 0 and boot["iters"] > 0
    # bootstrap measured attainment for every cell
    assert bus.count("qoe_attainment") == 2

    clock.advance(1.0)
    cluster.submit(ids[0], 1, 0.2)
    rnd = cluster.step()
    assert rnd is not None
    ev = bus.snapshot("admission_round")[-1].fields
    assert ev["version"] == 2 and ev["n_arrivals"] == 1
    assert ev["n_solved"] == 1 and ev["solve_wall_s"] > 0
    assert ev["round_wall_s"] >= ev["solve_wall_s"]
    # the touched cell's attainment was re-measured
    att = [e.fields for e in bus.snapshot("qoe_attainment")]
    assert att[-1]["cell"] == 0 and att[-1]["version"] == 2
    assert 0.0 <= att[-1]["attainment"] <= 1.0

    # swap-to-serve lag: first snapshot of a fresh version, on the
    # fake clock
    clock.advance(0.25)
    cluster.engine.round_snapshot()
    lags = bus.snapshot("swap_to_serve")
    assert lags[-1].fields["version"] == 2
    assert lags[-1].fields["lag_s"] == pytest.approx(0.25)
    n_lags = len(lags)
    cluster.engine.round_snapshot()           # same version: no new lag
    assert len(bus.snapshot("swap_to_serve")) == n_lags
    # installs are reported by the operation that made them (bootstrap,
    # admission_round, churn, handover), not by a stream of their own
    assert "schedule_swap" not in bus.streams()
    cluster.stop(drain=False)


def test_churn_emits_join_and_leave():
    import jax

    from repro.core import network

    bus = TelemetryBus()
    cluster, ids, clock = _cluster(bus)
    ncfg = network.small_config(n_users=6, n_subchannels=3)
    new_id = cluster.add_cell(
        network.make_scenario(jax.random.PRNGKey(9), ncfg), 0.4)
    join = bus.snapshot("cell_join")[-1].fields
    assert join["lane"] == 2 and join["solve_wall_s"] > 0
    cluster.remove_cell(ids[0])
    leave = bus.snapshot("cell_leave")[-1].fields
    assert leave["lane"] == 0 and leave["n_cells"] == 2
    assert cluster.qoe_attainment(new_id) >= 0.0
    cluster.stop(drain=False)


def test_round_error_event_and_bounded_backlog():
    from repro.serving.admission import ERROR_BACKLOG

    bus = TelemetryBus()
    cluster, ids, clock = _cluster(bus)
    ctl = cluster.controller
    assert ctl.errors.maxlen == ERROR_BACKLOG

    boom = RuntimeError("solver exploded")

    def exploding(*a, **kw):
        raise boom

    ctl.scheduler.schedule = exploding
    ctl.start()
    done = ctl.round_done
    for i in range(ERROR_BACKLOG + 5):
        done.clear()
        cluster.submit(ids[0], 0, 0.2)
        assert done.wait(30.0)
    cluster.stop(drain=False)
    # backlog stayed bounded; every failure still landed on the bus
    assert len(ctl.errors) == ERROR_BACKLOG
    assert all(e is boom for e in ctl.errors)
    assert bus.count("round_error") >= ERROR_BACKLOG + 5
    ev = bus.snapshot("round_error")[-1].fields
    assert ev["kind"] == "RuntimeError" and "solver exploded" in ev["error"]


def test_no_bus_path_touches_no_telemetry():
    # the bus=None serving path must stay allocation-free w.r.t. the
    # telemetry package: no Event, no ring, no sketch updates, no span
    # recorder
    cluster, ids, clock = _cluster(None)
    tracemalloc.start()
    try:
        clock.advance(1.0)
        cluster.submit(ids[0], 1, 0.2)
        cluster.step()
        cluster.engine.round_snapshot()
        snap = tracemalloc.take_snapshot().filter_traces(
            [tracemalloc.Filter(True, os.path.join(TELEMETRY_DIR, "*"))])
        assert sum(s.size for s in snap.statistics("filename")) == 0
    finally:
        tracemalloc.stop()
        cluster.stop(drain=False)


# ------------------------------------------- phase spans (telemetry.span)
ROUND_PHASES = ("ingest", "prep", "warm", "sweep", "finalize", "swap")


def _admission_round(bus, cluster, ids, clock, cells):
    clock.advance(1.0)
    for c in cells:
        cluster.submit(ids[c], 1, 0.2)
    assert cluster.step() is not None
    return bus.snapshot("admission_round")[-1].fields


@pytest.mark.parametrize("cells", [(0,), (0, 1)], ids=["partial", "full"])
def test_admission_round_phases_partition_round_and_solve(cells):
    bus = TelemetryBus()
    cluster, ids, clock = _cluster(bus)
    for _ in range(2):                        # a compiling round, a steady
        ev = _admission_round(bus, cluster, ids, clock, cells)
        for p in ROUND_PHASES:
            assert ev[f"{p}_s"] > 0, p
            assert type(ev[f"{p}_compiles"]) is int, p
        assert type(ev["compiles"]) is int
        outer = ev["ingest_s"] + ev["prep_s"] + ev["solve_wall_s"] \
            + ev["swap_s"]
        assert 0.95 * ev["round_wall_s"] <= outer <= ev["round_wall_s"]
        inner = ev["warm_s"] + ev["sweep_s"] + ev["finalize_s"]
        assert 0.95 * ev["solve_wall_s"] <= inner <= ev["solve_wall_s"]
    cluster.stop(drain=False)


def test_new_shape_compile_lands_in_its_phase():
    from repro.core import ligd

    bus = TelemetryBus()
    cluster, ids, clock = _cluster(bus)
    # the round's sweep compiles anew; nothing else in the round does
    ligd._sweep_batch.clear_cache()
    ev = _admission_round(bus, cluster, ids, clock, (0, 1))
    assert ev["sweep_compiles"] >= 1
    assert ev["compiles"] == ev["sweep_compiles"]
    assert all(ev[f"{p}_compiles"] == 0 for p in ROUND_PHASES
               if p != "sweep")
    ev = _admission_round(bus, cluster, ids, clock, (0, 1))
    assert ev["compiles"] == 0                # the same shapes again
    cluster.stop(drain=False)


def _serve_cluster(bus, seq=12, n_cells=2, n_users=4):
    import jax

    from repro.configs import get_tiny_config
    from repro.core import network, profiles
    from repro.core.ligd import SolverSpec
    from repro.models import transformer as T
    from repro.serving.cluster import SplitInferenceCluster

    cfg = get_tiny_config("gemma-2b").replace(dtype="float32")
    params = T.init(jax.random.PRNGKey(0), cfg)
    ncfg = network.small_config(n_users=n_users, n_subchannels=3)
    clock = FakeClock()
    bus.clock = clock
    cluster = SplitInferenceCluster(
        params, cfg, profiles.transformer_profile(cfg, seq=seq),
        spec=SolverSpec(max_steps=5, per_user_split=False), clock=clock,
        bus=bus)
    ids = [cluster.add_cell(network.make_scenario(jax.random.PRNGKey(b),
                                                  ncfg), 0.4)
           for b in range(n_cells)]
    cluster.start(threaded=False)
    tokens = {c: np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (n_users, seq), 0, cfg.vocab_size))
        for i, c in enumerate(ids)}
    return cluster, ids, clock, tokens


@pytest.mark.parametrize("steps", [1, 3])
def test_serve_round_carries_first_tokens_and_decode_phases(steps):
    bus = TelemetryBus()
    # a prompt length no other test serves: the round's prefill compiles
    cluster, ids, clock, tokens = _serve_cluster(bus, seq=8 + steps)
    out = cluster.serve_round(tokens, decode_steps=steps)
    ev = bus.snapshot("serve_round")[-1].fields
    n_groups = sum(len(cluster.installed_schedule(c).groups())
                   for c in ids)
    assert len(ev["first_token_s"]) == n_groups
    assert ev["first_token_s"] == sorted(ev["first_token_s"])
    assert 0 < ev["first_token_s"][0] and \
        ev["first_token_s"][-1] < ev["serve_s"]
    # the prefill gives each request its first token, a decode step each
    # further one
    assert ev["decode_steps"] == len(ids) * (steps - 1)
    assert all(r.tokens_out.shape == (steps,) for rs in out.values()
               for r in rs)
    assert ev["prefill_s"] > 0
    assert (ev["decode_s"] > 0) == (steps > 1)
    assert ev["prefill_s"] + ev["decode_s"] < ev["serve_s"]
    assert ev["prefill_compiles"] >= 1
    assert ev["compiles"] >= ev["prefill_compiles"] + ev["decode_compiles"]
    cluster.stop(drain=False)


# spans and the bus fields that report them: (span name, event, field)
SPAN_FIELDS = [
    ("era.round.ingest", "admission_round", "ingest_s"),
    ("era.round.prep", "admission_round", "prep_s"),
    ("era.round.solve", "admission_round", "solve_wall_s"),
    ("era.round.swap", "admission_round", "swap_s"),
    ("era.solve.warm", "admission_round", "warm_s"),
    ("era.solve.sweep", "admission_round", "sweep_s"),
    ("era.solve.finalize", "admission_round", "finalize_s"),
    ("era.serve", "serve_round", "serve_s"),
    ("era.serve.prefill", "serve_round", "prefill_s"),
    ("era.serve.decode_step", "serve_round", "decode_s"),
]
NESTING = {"era.round.": "era.round", "era.solve.": "era.round.solve",
           "era.serve.": "era.serve"}


def test_phase_spans_on_the_profiler_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData

    bus = TelemetryBus()
    cluster, ids, clock, tokens = _serve_cluster(bus)
    _admission_round(bus, cluster, ids, clock, (0,))    # compile outside
    cluster.serve_round(tokens, decode_steps=3)
    with jax.profiler.trace(str(tmp_path)):
        _admission_round(bus, cluster, ids, clock, (0,))
        cluster.serve_round(tokens, decode_steps=3)
    cluster.stop(drain=False)
    fields = {name: bus.snapshot(name)[-1].fields
              for name in ("admission_round", "serve_round")}

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events
             if e.name.startswith("era.")]
    by_name = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((s, e))
    assert len(by_name["era.round"]) == len(by_name["era.serve"]) == 1
    assert len(by_name["era.serve.group"]) == \
        len(fields["serve_round"]["first_token_s"])
    assert len(by_name["era.serve.decode_step"]) == \
        fields["serve_round"]["decode_steps"]
    for name, s, e in spans:
        parent = next((p for pre, p in NESTING.items()
                       if name.startswith(pre)), None)
        if parent is None:
            continue
        (ps, pe), = by_name[parent]
        assert ps <= s and e <= pe, (name, parent)
    for name, event, field in SPAN_FIELDS:
        traced = 1e-9 * sum(e - s for s, e in by_name[name])
        bus_s = fields[event][field]
        assert abs(traced - bus_s) <= 1e-3 + 0.05 * bus_s, \
            (name, traced, bus_s)


SWEEP_SCOPES = {"era.beta_t", "era.grad_norm", "era.clip_alloc", "era.loss"}


@pytest.mark.parametrize("backend", ["reference", "sharded"])
def test_sweep_ops_carry_named_scopes(backend):
    """The GD step's XLA-side parts carry their named scopes in the
    compiled sweep's op metadata, on one device and under the mesh."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import ligd, network, profiles
    from repro.core.era import Weights, uniform_alloc
    from repro.distributed import solver_mesh

    ncfg = network.small_config(n_users=6, n_subchannels=3)
    scns = [network.make_scenario(jax.random.PRNGKey(i), ncfg)
            for i in range(2)]
    prep = ligd.prepare_batch(scns, profiles.get_profile("nin"), True)
    args = (prep.scn_b, jnp.full((2, 6), 0.4), uniform_alloc(scns[0]),
            jnp.asarray(prep.pred_b), jnp.float32(0.05), jnp.float32(1e-5))
    if backend == "reference":
        lowered = ligd._sweep_batch.lower(
            *args, 5, Weights(), prep.prof_b, step_impl="fused",
            prof_batched=prep.prof_batched, x_init_batched=False)
    else:
        fn = solver_mesh._sharded_sweep_fn(
            solver_mesh.cells_mesh(1), 5, Weights(), False, 0, "fused", 0,
            prep.prof_batched, False)
        lowered = fn.lower(*args, prep.prof_b)
    text = lowered.compile().as_text()
    scopes = {s for op in re.findall(r'op_name="([^"]*)"', text)
              for s in re.findall(r"era\.[a-z_]+", op)}
    assert SWEEP_SCOPES <= scopes, scopes
