"""Driver for solver configurations: admission rounds of the ERA solve
through ``SplitInferenceCluster`` (no model is served).

Per round, back to back: every arrival of the open-loop Poisson stream due
by the round's start is submitted, every cell's channel advances one step
of its Gauss-Markov chain (``observe``), and ``cluster.step()`` runs the
admission round: drain, solve, swap.  The round ends in host numpy (the
solver's discretisation), so its end is device-synced.

End-to-end metrics:

* ``round_ms`` — the span of the window's rounds divided by their number;
* ``admit_p95_ms`` — over every arrival due in the window, the time from
  its due time to the install of the schedule of the round that consumed
  it; arrivals due during the last round are answered by one more round
  after the window.

Correctness: the plain reference (``bench/reference/era.py``) solves, for
a sample of cells drawn from the seed, the window's first ``check_rounds``
rounds from the same inputs as the program: the channel snapshot, the
thresholds after the round's arrivals, and the schedule the cell had
installed before the round (the warm start).
Each of its outcomes is compared with the one the program installed
(``compare``).  The reference does not follow its own schedules from round
to round: rounding the relaxed subchannel assignment picks among
near-equal subchannels by round-off, so two correct solvers part ways at
the first round and never meet again.  One GD step is also compared on
its own (``step_numbers``): the fused ``era_step`` kernel's Gamma and
gradient at every split point, at the first checked round's inputs,
against the reference's autodiff.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional

import numpy as np

from bench.harness import core, profiles, traffic as tr


@dataclass
class Round:
    t_start: float
    t_end: float
    pos: int                                 # chain step observed
    arrivals: List[tuple]                    # (due_abs, cell, user, q_s)
    cells: tuple = ()                        # cells the program solved
    t_installed: float = float("nan")
    outcomes: Dict[int, object] = field(default_factory=dict)


@dataclass
class Log:
    """Everything a window produced that the metrics and the check read."""
    q0: float
    drift_threshold: float
    net: dict
    prof: dict
    chains: object
    rounds: List[Round] = field(default_factory=list)
    boot_outcomes: Dict[int, object] = field(default_factory=dict)
    window: slice = None                     # rounds inside the window
    check_lanes: List[int] = field(default_factory=list)
    n_check: int = 0                         # rounds up to the last checked


def sample_lanes(rng, n_cells: int, k: int) -> List[int]:
    """One lane drawn from each of ``k`` contiguous blocks of lanes: the
    sharded backend gives each chip one block, so every chip is checked."""
    block = n_cells // k
    return [b * block + int(rng.integers(block)) for b in range(k)]


def _spec(cfg: dict):
    from repro.core.ligd import SolverSpec
    return SolverSpec(**cfg["solver"])


def _net(cfg: dict):
    from repro.core.network import NetworkConfig
    return NetworkConfig(**cfg["network"])


def simulate(run: core.Run) -> Log:
    """Set up the cluster, run the warm-up rounds and the window, and close
    with the round that answers the window's last arrivals."""
    from repro.core.era import Weights
    from repro.serving.cluster import SplitInferenceCluster
    from repro.telemetry import TelemetryBus

    cfg, mix = run.config, run.traffic
    net = _net(cfg)
    prof = profiles.build(cfg)
    n_cells = int(cfg["cells"])
    chains = tr.ChannelChains(run.seed, net, n_cells, mix["chain_steps"],
                              mix["rho"])
    arrivals = tr.PoissonArrivals(run.seed, mix["rate_per_cell"], n_cells,
                                  net.n_users, mix["deadline_s"])
    bus = TelemetryBus(clock=time.monotonic, capacity=1 << 16)
    program_prof = profiles.to_program(prof, cfg["profile"]["name"])
    weights = Weights(**cfg["weights"])
    cluster = SplitInferenceCluster(
        None, None, program_prof, spec=_spec(cfg), weights=weights,
        drift_threshold=cfg["drift_threshold"], clock=time.monotonic,
        bus=bus)
    ids = [cluster.add_cell(chains.scenario(b, 0), q0=mix["q0_s"])
           for b in range(n_cells)]
    rng = tr.np_rng(run.seed, 3)
    log = Log(q0=float(mix["q0_s"]),
              drift_threshold=float(cfg["drift_threshold"]),
              net=dict(cfg["network"]), prof=prof,
              chains=chains,
              check_lanes=sample_lanes(rng, n_cells,
                                       int(cfg["check_lanes"])))
    n_replay = int(mix["warmup_rounds"]) + int(mix["check_rounds"])
    spans = run.spans
    chains.block_until_ready()
    run.say(f"spec: {_spec(cfg)}")
    run.say(f"network: U={net.n_users} N={net.n_aps} M={net.n_subchannels}"
            f" cells={n_cells} profile={cfg['profile']['name']} "
            f"F={len(prof['layer_flops'])} chain_steps={chains.steps}")

    stream0 = time.monotonic()
    cluster.start(threaded=False)
    log.boot_outcomes = {b: cluster.last_outcome(ids[b])
                         for b in log.check_lanes}
    undo = [spans.wrap(cluster.scheduler, "schedule", "scheduler.schedule"),
            spans.wrap(cluster.engine, "swap_schedules",
                       "engine.swap_schedules")]

    def one_round(until: Optional[float] = None):
        t0 = time.monotonic()
        with spans.span("bench.submit"):
            due = arrivals.due_by((t0 if until is None else until) - stream0)
            for d, c, u, q in due:
                cluster.submit(ids[c], u, q)
        pos = len(log.rounds) + 1
        with spans.span("bench.observe"):
            for b in range(n_cells):
                cluster.observe(ids[b], chains.scenario(b, pos))
        with spans.span("cluster.step"):
            rnd = cluster.step()
        rec = Round(t0, time.monotonic(), pos,
                    [(stream0 + d, c, u, q) for d, c, u, q in due])
        if rnd is not None:
            rec.cells, rec.t_installed = tuple(rnd.cells), rnd.t_installed
        if len(log.rounds) < n_replay:
            rec.outcomes = {b: cluster.last_outcome(ids[b])
                            for b in log.check_lanes}
        log.rounds.append(rec)

    try:
        for _ in range(int(mix["warmup_rounds"])):
            one_round()
        first = len(log.rounds)
        with run.window():
            while time.monotonic() - run.t_window0 < run.seconds:
                one_round()
        log.window = slice(first, len(log.rounds))
        while len(log.rounds) < n_replay:      # a window too short to
            one_round()                        # hold the checked rounds
        one_round(until=run.t_window0 + run.seconds)
        run.end_trace()
    finally:
        for u in undo:
            u()
        cluster.stop(drain=False)
    log.n_check = n_replay
    log.bus = bus
    log.program_prof, log.weights = program_prof, weights
    log.attainment = [cluster.qoe_attainment(i) for i in ids]
    log.latency = [np.asarray(cluster.installed_schedule(i).pred_latency)
                   for i in ids]
    log.splits = [np.bincount(cluster.installed_schedule(i).split,
                              minlength=len(prof["layer_flops"]) + 1)
                  for i in ids]
    return log


def say_operating_point(run: core.Run, attainment, latency, splits):
    """Earlier output lines: QoE attainment, the modelled latency of the
    installed schedules (quartiles over all users) and the split
    histogram, per cell."""
    run.say(f"qoe attainment per cell: {attainment}")
    run.say("modelled latency s per cell (min, q1, median, q3, max): "
            f"{[np.quantile(x, [0, .25, .5, .75, 1]).tolist() for x in latency]}")
    run.say(f"split histogram per cell: {[h.tolist() for h in splits]}")


def check_numbers(run: core.Run, log: Log) -> Dict[str, float]:
    """The compared numbers of a solver check: the replayed rounds and the
    fused step, the program's (or, for a control run, the reference's at
    ``run.control`` in the program's place) against the reference's."""
    cfg = run.config
    against = None
    if run.control is not None:
        against, _ = replay(log, cfg, run.control)
    numbers = check(log, cfg, against=against)
    numbers.update(step_numbers(log, cfg, log.program_prof, log.weights,
                                control=run.control))
    return numbers


# ------------------------------------------------------------ end to end
def admit_latencies(log: Log, t0: float, t1: float) -> List[float]:
    """Seconds from due time to install, for arrivals due in [t0, t1)."""
    out = []
    for r in log.rounds:
        for due, _, _, _ in r.arrivals:
            if t0 <= due < t1:
                lat = r.t_installed - due
                out.append(lat if np.isfinite(lat) else float("inf"))
    return out


# ---------------------------------------------------------- correctness
def _reference_inputs(log: Log):
    """Per replayed round: the scenario step each checked lane was solved
    on, the thresholds the round solved with, and the lanes it touched,
    from the benchmark's own bookkeeping (the arrivals it submitted and a
    plain drift test)."""
    import jax.numpy as jnp
    n_cells = log.chains.n_cells
    u = log.net["n_users"]
    q = np.full((n_cells, u), log.q0, np.float32)
    ref_pos = [0] * n_cells
    steps = []
    for r in log.rounds[:log.n_check]:
        touched = set()
        for _, c, user, qs in r.arrivals:
            q[c, user] = qs
            touched.add(c)
        for b in range(n_cells):
            if b in touched:
                continue
            a = log.chains.scenario(b, r.pos)
            ref = log.chains.scenario(b, ref_pos[b])
            num = jnp.sum(jnp.abs(a.h_up - ref.h_up)) \
                + jnp.sum(jnp.abs(a.h_dn - ref.h_dn))
            den = 0.5 * (jnp.sum(a.h_up + ref.h_up)
                         + jnp.sum(a.h_dn + ref.h_dn))
            if float(num / jnp.maximum(den, 1e-30)) > log.drift_threshold:
                touched.add(b)
        for b in touched:
            ref_pos[b] = r.pos
        steps.append((r.pos, q.copy(), sorted(touched)))
    return steps


def program_outcome(log: Log, i: int, b: int):
    """The program's outcome for lane ``b`` after round ``i`` (-1: the
    bootstrap) — the schedule it had installed then."""
    return log.boot_outcomes[b] if i < 0 else log.rounds[i].outcomes[b]


def precision_of(name: str):
    """A precision's name (``highest``, or a control's: ``high``, three
    bf16 passes; ``default``, one) as the reference computes it: the
    matmul precision context and the operand rounding.  On a TPU the
    context does it; elsewhere XLA contracts float32 in full whatever the
    context says, so the operands are rounded as the passes would."""
    import jax
    if name == "highest" or jax.default_backend() == "tpu":
        return name, None
    return "highest", {"high": 16, "default": 8}[name]


def replay(log: Log, cfg: dict, precision: str):
    """The reference's solve of the checked rounds (the window's first
    ``check_rounds``) of the checked lanes, at matmul ``precision``, each
    warm-started, as the program is, from the schedule the lane had
    installed before it.  Returns ``[(round index, lane, Outcome)]`` and
    the per-round inputs of every round up to the last checked one."""
    import jax
    import jax.numpy as jnp
    from bench.reference import era as ref

    net = cfg["network"]
    precision, bits = precision_of(precision)
    solver = ref.Solver(log.prof, net, cfg["weights"], cfg["solver"], bits)
    lanes = log.check_lanes
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
    out = []
    steps = _reference_inputs(log)
    last = {b: -1 for b in lanes}           # -1: the bootstrap
    with jax.default_matmul_precision(precision):
        for i, (pos, q, touched) in enumerate(steps):
            run_lanes = [b for b in lanes if b in touched]
            if i >= log.window.start and run_lanes:
                cells = stack([ref.make_cell(log.chains.scenario(b, pos))
                               for b in run_lanes])
                x0 = stack([ref.soften(ref.Alloc(*map(
                    jnp.asarray, program_outcome(log, last[b], b).alloc)),
                    net["n_subchannels"]) for b in run_lanes])
                res = solver.solve(cells, jnp.asarray(q[run_lanes]), x0)
                out += [(i, b, o) for b, o in zip(run_lanes, res)]
            for b in run_lanes:
                last[b] = i
    return out, steps


def _tainted(flip, pred) -> np.ndarray:
    """Split points whose GD, or that of any split point it was
    warm-started from, ran a different number of steps."""
    out = np.zeros_like(flip)
    for s in range(len(pred)):
        p = s
        while True:
            out[s] |= flip[p]
            if pred[p] == p:
                break
            p = pred[p]
    return out


def compare(got, want, assoc, cap: int, pred) -> Dict[str, float]:
    """One outcome against the reference's, from the same inputs:

    * ``split_users`` — users on another split point;
    * ``step_flips`` — split points whose GD ran another number of steps,
      leaving out those where the reference's stop test lay within
      round-off of its threshold (``era.NEAR_STOP``);
    * ``gamma_rel`` — the largest relative gap of Gamma by split point,
      over the split points whose GD, and every GD it was warm-started
      from, ran as many steps as the reference's;
    * ``alloc_rel`` — the largest gap of the power and compute leaves at
      the chosen split point, relative to the largest reference value of
      that leaf (left out where that split point's steps differ);
    * ``cap_excess`` — users without exactly one subchannel, and (AP,
      subchannel) pairs holding more users than the cap allows.

    Which of near-equal relaxed subchannel assignments rounding picks is
    decided by round-off, so the subchannels themselves are checked for
    validity only.
    """
    g = got.alloc
    flip = np.asarray(got.iters_by_layer) != want.iters_by_layer
    tainted = _tainted(flip, pred)
    gam = np.abs(np.asarray(got.gamma_by_layer, np.float64)
                 - want.gamma_by_layer) / np.abs(want.gamma_by_layer)
    s_star = int(np.argmin(want.gamma_by_layer))
    alloc = 0.0 if tainted[s_star] else max(
        float(np.max(np.abs(np.asarray(getattr(g, k), np.float64)
                            - getattr(want.alloc, k)))
              / np.max(np.abs(getattr(want.alloc, k))))
        for k in ("p", "p_ap", "r"))
    excess = 0
    assoc = np.asarray(assoc)
    for hard in (np.asarray(g.beta_up), np.asarray(g.beta_dn)):
        ch = np.argmax(hard, 1)
        counts = np.zeros((int(assoc.max()) + 1, hard.shape[1]), int)
        np.add.at(counts, (assoc, ch), 1)
        excess += int(np.sum(counts > cap))
        excess += int(np.sum(hard.sum(axis=1) != 1))
    return {"split_users": float(np.sum(np.asarray(got.s) != want.s)),
            "step_flips": float(np.sum(flip & ~want.near_stop)),
            "gamma_rel": float(np.max(gam[~tainted])) if (~tainted).any()
            else float("inf"),
            "alloc_rel": alloc,
            "cap_excess": float(excess)}


def _finite(tree):
    """Non-finite gradient entries set to 0, as the program's GD step and
    the reference's treat them."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda x: jnp.where(jnp.isfinite(x), x, 0.0), tree)


def _program_steps(scn, prof, q, alloc, w, n_s: int, block_m: int):
    """The program's fused GD step (value and gradient) at every split
    point, one after another, from the same allocation."""
    import jax
    import jax.numpy as jnp
    from repro.core.era import Allocation
    from repro.kernels.era_step import ops

    def one(s):
        s_vec = jnp.full(q.shape, s, jnp.int32)
        val, g = ops.era_step_value_and_grad(
            scn, prof, s_vec, q, Allocation(*alloc), w, block_m=block_m)
        return val, tuple(g)
    return jax.lax.map(one, jnp.arange(n_s, dtype=jnp.int32))


def step_numbers(log: Log, cfg: dict, program_prof, weights,
                 control: str = None) -> Dict[str, float]:
    """One GD step at the window's own inputs, as the fused ``era_step``
    kernel computes it, against the reference's autodiff at HIGHEST: for
    each checked lane, at the first checked round's channel snapshot and
    thresholds and at the warm start the round solved from, Gamma and its
    gradient at every split point.

    * ``step_gamma_rel`` — the largest relative gap of Gamma;
    * ``step_grad_rel`` — over split points and gradient leaves (both
      subchannel shares, the two powers, the compute units), the norm of
      the leaf's gap over the reference leaf's norm, or over the median
      leaf's norm where that is larger (a leaf can be all but zero).

    ``control``: the reference at that precision in the program's place."""
    import jax
    import jax.numpy as jnp
    from bench.reference import era as ref

    net, solver_cfg = cfg["network"], cfg["solver"]
    i0 = log.window.start
    pos, q_all, _ = _reference_inputs(log)[i0]
    n_s = len(log.prof["layer_flops"]) + 1
    ctx = [precision_of("highest")]
    if control is not None:
        ctx.append(precision_of(control))
    refs = [ref.Solver(log.prof, net, cfg["weights"], solver_cfg, bits)
            for _, bits in ctx]
    gam, grad = 0.0, 0.0
    for b in log.check_lanes:
        scn = log.chains.scenario(b, pos)
        q = jnp.asarray(q_all[b])
        alloc = ref.soften(ref.Alloc(*map(
            jnp.asarray, program_outcome(log, i0 - 1, b).alloc)),
            net["n_subchannels"])
        batch = lambda x: jnp.broadcast_to(x[None], (n_s,) + x.shape)
        cells = jax.tree.map(batch, ref.make_cell(scn))
        s_b = jnp.broadcast_to(jnp.arange(n_s, dtype=jnp.int32)[:, None],
                               (n_s, q.shape[0]))
        outs = []
        for (prec, _), solver in zip(ctx, refs):
            with jax.default_matmul_precision(prec):
                val, g = solver.value_and_grad(
                    cells, s_b, batch(q), jax.tree.map(batch, alloc))
            outs.append((np.asarray(val, np.float64),
                         [np.asarray(x, np.float64) for x in _finite(g)]))
        want = outs[0]
        if control is None:
            val, g = jax.jit(partial(
                _program_steps, w=weights, n_s=n_s,
                block_m=int(solver_cfg.get("step_block_m", 0))))(
                    scn, program_prof, q, tuple(alloc))
            got = (np.asarray(val, np.float64),
                   [np.asarray(x, np.float64) for x in _finite(g)])
        else:
            got = outs[1]
        gam = max(gam, float(np.max(np.abs(got[0] - want[0])
                                    / np.abs(want[0]))))
        for s in range(n_s):
            norms = [np.linalg.norm(w[s]) for w in want[1]]
            floor = float(np.median(norms))
            grad = max(grad, max(
                float(np.linalg.norm(g[s] - w[s])) / max(n, floor, 1e-30)
                for g, w, n in zip(got[1], want[1], norms)))
    return {"step_gamma_rel": gam, "step_grad_rel": grad}


def check(log: Log, cfg: dict, against=None, want=None) -> Dict[str, float]:
    """The largest of each compared number over every replayed outcome of
    the checked lanes, plus ``round_cells``: the replayed rounds whose
    solved cells differ from the lanes the benchmark's own bookkeeping
    says the round touched.  ``against``: ``replay`` outcomes to compare
    in place of the program's (the control's); ``want``: the reference's
    ``replay`` at HIGHEST, where already made."""
    from bench.reference import era as ref
    want, steps = want or replay(log, cfg, "highest")
    got_by = None if against is None else {(i, b): o for i, b, o in against}
    cap = int(cfg["network"]["max_users_per_channel"])
    pred = ref.predecessors(ref.tables(log.prof)[2])
    worst: Dict[str, float] = {}
    for i, b, o in want:
        got = program_outcome(log, i, b) if got_by is None \
            else got_by[(i, b)]
        assoc = log.chains.scenario(b, 0).assoc
        for k, v in compare(got, o, assoc, cap, pred).items():
            worst[k] = max(worst.get(k, 0.0), v)
    worst["round_cells"] = float(sum(
        tuple(r.cells) != tuple(t)
        for r, (_, _, t) in zip(log.rounds, steps)))
    return worst


# ------------------------------------------------------------------- run
def run(run: core.Run) -> core.Outcome:
    cfg = run.config
    log = simulate(run)
    rounds = log.rounds[log.window]
    t_end = rounds[-1].t_end
    n = len(rounds)
    lat = admit_latencies(log, run.t_window0, run.t_window0 + run.seconds)
    events = [e.fields for e in log.bus.snapshot("admission_round")
              if run.t_window0 <= e.t <= t_end]
    e2e = {"round_ms": 1e3 * (t_end - run.t_window0) / n,
           "admit_p95_ms": 1e3 * tr.percentile(lat, 95)}
    n_req, _, n_hit = run.compiles_in_window
    run.say(f"window: rounds={n} arrivals={len(lat)} "
            f"round_ms={e2e['round_ms']!r} "
            f"admit_p50_ms={1e3 * tr.percentile(lat, 50)!r} "
            f"admit_p95_ms={e2e['admit_p95_ms']!r} gd_steps/round "
            f"min={min(ev['iters'] for ev in events)} "
            f"max={max(ev['iters'] for ev in events)}")
    run.say(f"compiles in window: requests={n_req} persistent-cache hits="
            f"{n_hit}; set-up compile s={run.setup_compile_s!r}")
    say_operating_point(run, log.attainment, log.latency, log.splits)
    memory = core.device_info(run.cell["chips"])
    t0 = time.monotonic()
    numbers = check_numbers(run, log)
    run.say(f"check: {len(log.check_lanes)} lanes x "
            f"{log.n_check - log.window.start} rounds solved by the "
            f"reference in {time.monotonic() - t0:.1f} s")
    limits = cfg["limits"]
    checks = {k: (numbers[k], limits[k]) for k in limits}
    readings = {"rounds": rounds, "events": events, "log": log,
                "t_end": t_end, "u": cfg["network"]["n_users"],
                "m": cfg["network"]["n_subchannels"],
                "n_aps": cfg["network"]["n_aps"]}
    return core.Outcome(e2e=e2e, readings=readings, checks=checks,
                        attempted=len(lat),
                        failed=sum(not np.isfinite(x) for x in lat),
                        device=memory)
