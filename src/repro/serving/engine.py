"""Split-serving engine: executes scheduled requests end to end.

Pipeline per admission round:
  1. scheduler -> per-user (split, channel, power, r) assignments
  2. users are grouped by split point; each group's device-side prefix runs
     per user (their own tokens), the crossing activations are "transmitted"
     over the simulated NOMA link (latency = bits / scheduled rate), and the
     edge side runs as one batched forward per group
  3. decode continues on the edge with the shared KV/state caches

The radio and edge-compute times are simulated (CPU container — DESIGN.md);
the numerical path (device prefix -> crossing tensor -> edge suffix) is the
real model, so tests can assert split == fused logits exactly.

``SplitServeEngine`` serves one cell; ``MultiCellServeEngine`` serves B
cells whose schedules come from ONE batched solve (MultiCellScheduler) and
then reuses the same per-cell execution path (``execute_schedule``).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.era import lam
from repro.models import transformer as T
from repro.serving import split_runtime
from repro.serving.scheduler import (EraScheduler, MultiCellScheduler,
                                     Schedule)
from repro.telemetry import spans


@dataclass
class RequestResult:
    user: int
    tokens_out: np.ndarray
    latency_s: float
    t_device: float
    t_uplink: float
    t_edge: float
    t_downlink: float


def execute_schedule(params, cfg, netcfg, prof, sched: Schedule,
                     tokens_per_user, *, decode_steps=0
                     ) -> List[RequestResult]:
    """Run one cell's scheduled admission round (steps 2–3 above)."""
    results: Dict[int, RequestResult] = {}

    for split, users in sched.groups().items():
        # the group span ends where its first tokens reach the host
        with spans.span("era.serve.group", split=int(split),
                        users=len(users)):
            toks = tokens_per_user[users]
            x, positions = split_runtime.device_forward(params, cfg, toks,
                                                        split)
            logits = split_runtime.edge_forward(params, cfg, x, positions,
                                                split)
            next_tok = np.asarray(jnp.argmax(logits[:, -1], -1))
            spans.mark("first_token")
        crossing_bits = (float(x[0].size) * x.dtype.itemsize * 8)

        dev_fl = float(prof.device_flops[split])
        edge_fl = float(prof.edge_flops[split])
        for row, u in enumerate(users):
            r_up = max(float(sched.uplink_rate[u]), 1.0)
            r_dn = max(float(sched.downlink_rate[u]), 1.0)
            t_dev = dev_fl / netcfg.c_device_flops
            t_up = (crossing_bits / r_up) if split < prof.n_layers \
                else 0.0
            eff = lam(float(sched.compute_units[u]), netcfg) \
                * netcfg.c_min_flops
            t_edge = edge_fl / eff
            t_dn = (float(prof.result_bits) / r_dn) \
                if split < prof.n_layers else 0.0
            results[int(u)] = RequestResult(
                user=int(u),
                tokens_out=next_tok[row:row + 1],
                latency_s=t_dev + t_up + t_edge + t_dn,
                t_device=t_dev, t_uplink=t_up,
                t_edge=t_edge, t_downlink=t_dn,
            )

    if decode_steps:
        _continue_decode(params, cfg, tokens_per_user, results, decode_steps)
    return [results[u] for u in sorted(results)]


def _continue_decode(params, cfg, tokens, results, n_steps):
    """Greedy decode continuation on the edge (full model, cached)."""
    # sequence length is the LAST axis — multi-codebook models carry
    # (U, n_codebooks, S) tokens, where shape[1] would be n_codebooks
    s = tokens.shape[-1]
    # each span ends at the host read of its token that the loop needs
    with spans.span("era.serve.prefill"):
        logits, caches, _ = T.prefill(params, cfg, tokens,
                                      max_seq=s + n_steps + 1)
        cur = jnp.argmax(logits[:, -1], -1)
        outs = [np.asarray(cur)]
    for step in range(n_steps - 1):
        with spans.span("era.serve.decode_step"):
            logits, caches = T.decode_step(params, cfg, cur,
                                           jnp.int32(s + step), caches)
            cur = jnp.argmax(logits, -1)
            outs.append(np.asarray(cur))
    seq = np.stack(outs, 1)
    for u, r in results.items():
        r.tokens_out = seq[u]


class SplitServeEngine:
    def __init__(self, params, cfg, scn, prof, scheduler: EraScheduler):
        self.params = params
        self.cfg = cfg
        self.scn = scn
        self.prof = prof
        self.scheduler = scheduler

    def serve_round(self, tokens_per_user, q_thresholds, *,
                    decode_steps=0) -> List[RequestResult]:
        """tokens_per_user: (U, S) int32 (each user one request)."""
        sched = self.scheduler.schedule(q_thresholds)
        return execute_schedule(self.params, self.cfg, self.scn.cfg,
                                self.prof, sched, tokens_per_user,
                                decode_steps=decode_steps)


@dataclass(frozen=True)
class ScheduleSet:
    """Immutable installed-schedule snapshot.  Swapped as ONE reference
    under the engine lock, so a reader either sees the whole previous
    round's schedules or the whole new one — never a mix (the admission
    loop's swap-atomicity contract)."""
    version: int
    schedules: Tuple[Schedule, ...]        # one per cell


class MultiCellServeEngine:
    """Serves B cells per round: one batched schedule, per-cell execution.

    All cells serve the same model parameters (one edge deployment); the
    scheduler may still carry per-cell split profiles (e.g. different
    request lengths).

    Two serving modes:
      ``serve_round``            — lockstep: solve, install, execute (the
                                   pre-async behaviour, kept for
                                   benchmarking the synchronous baseline).
      ``serve_scheduled_round``  — event-driven: execute the currently
                                   installed ``ScheduleSet`` without
                                   touching the solver.  The admission
                                   loop (serving.admission) installs fresh
                                   schedules concurrently via
                                   ``install_schedules``/``swap_schedules``;
                                   in-flight rounds keep the snapshot they
                                   grabbed at round start."""

    def __init__(self, params, cfg, scns, scheduler: MultiCellScheduler,
                 *, bus=None, clock=time.monotonic):
        self.params = params
        self.cfg = cfg
        self.scns = list(scns)
        self.scheduler = scheduler          # profiles come from here too
        # telemetry (optional): every install/swap/resize records its
        # version's install time; the FIRST serving round to snapshot
        # that version emits `swap_to_serve` with the elapsed lag — the
        # freshness gap between solver output and serving pickup.  The
        # clock is injectable so the load harness measures lag in
        # deterministic fake-clock time.
        self.bus = bus
        self.clock = clock
        self._pending_serve: Dict[int, float] = {}   # version -> install t
        self._lock = threading.Lock()
        self._installed: Optional[ScheduleSet] = None

    @property
    def n_cells(self) -> int:
        return len(self.scns)

    # ---- schedule store ------------------------------------------------
    def install_schedules(self, scheds: Sequence[Schedule]) -> int:
        """Atomically replace every cell's schedule; returns new version."""
        scheds = tuple(scheds)
        if len(scheds) != self.n_cells:
            raise ValueError(f"need {self.n_cells} schedules, "
                             f"got {len(scheds)}")
        with self._lock:
            version = (self._installed.version + 1) if self._installed else 1
            self._installed = ScheduleSet(version, scheds)
            self._pending_serve[version] = self.clock()
        return version

    def swap_schedules(self, per_cell: Dict[int, Schedule]) -> int:
        """Atomically swap a subset of cells' schedules (admission rounds
        touch only drifted/arrival cells); untouched cells keep theirs."""
        bad = [b for b in per_cell if not 0 <= int(b) < self.n_cells]
        if bad:
            raise ValueError(f"cells {bad} out of range [0, {self.n_cells})")
        with self._lock:
            if self._installed is None:
                raise RuntimeError("no schedules installed yet "
                                   "(bootstrap with install_schedules)")
            scheds = list(self._installed.schedules)
            for b, sched in per_cell.items():
                scheds[b] = sched
            version = self._installed.version + 1
            self._installed = ScheduleSet(version, tuple(scheds))
            self._pending_serve[version] = self.clock()
        return version

    def resize(self, scns, schedules=None, keep: Dict[int, int] = None
               ) -> int:
        """Cell churn: atomically replace the cell list AND its schedules
        in ONE versioned swap.  In-flight rounds finish on the snapshot
        they grabbed; the next round sees the new cell set — zero-downtime
        handoff.

        Two calling conventions:
          * ``schedules`` = full per-cell sequence (the pre-facade path:
            resize the scheduler, re-solve everything, install here);
          * ``keep`` = {new_lane: old_lane} carrying surviving cells'
            INSTALLED schedules over unchanged (version continuity — no
            re-solve for survivors), with ``schedules`` = {new_lane:
            Schedule} covering only the lanes ``keep`` does not (joiners).
        Every new lane must end up with a schedule from one of the two.

        The coordinated join/leave path — admission-controller state
        following the remap — is ``AdmissionController.add_cell``/
        ``remove_cell``, which call this; the ``SplitInferenceCluster``
        facade keys it all by stable ``CellId``."""
        scns = list(scns)
        if schedules is None and keep is None:
            raise ValueError("resize needs schedules (full sequence or "
                             "{lane: Schedule}) and/or keep= "
                             "{new_lane: old_lane} — every new lane must "
                             "get a schedule from one of the two")
        with self._lock:
            cur = self._installed
            if keep is not None or isinstance(schedules, dict):
                scheds: List[Optional[Schedule]] = [None] * len(scns)
                for new_i, old_i in (keep or {}).items():
                    if cur is None:
                        raise RuntimeError("keep= carries installed "
                                           "schedules over, but none are "
                                           "installed yet")
                    if not (0 <= new_i < len(scns)
                            and 0 <= old_i < len(cur.schedules)):
                        raise ValueError(f"keep entry {new_i}->{old_i} out "
                                         "of range")
                    scheds[new_i] = cur.schedules[old_i]
                for new_i, sched in (schedules or {}).items():
                    if not 0 <= int(new_i) < len(scns):
                        raise ValueError(f"schedule for lane {new_i} out "
                                         f"of range [0, {len(scns)})")
                    scheds[int(new_i)] = sched
                missing = [i for i, s in enumerate(scheds) if s is None]
                if missing:
                    raise ValueError(f"lanes {missing} have neither a "
                                     "carried-over (keep=) nor a fresh "
                                     "schedule")
            else:
                scheds = list(schedules)
                if len(scheds) != len(scns):
                    raise ValueError(f"need one schedule per cell: "
                                     f"{len(scns)} cells, {len(scheds)} "
                                     "schedules")
            version = (cur.version + 1) if cur else 1
            self.scns = scns
            self._installed = ScheduleSet(version, tuple(scheds))
            self._pending_serve[version] = self.clock()
        return version

    def current_schedules(self) -> Optional[ScheduleSet]:
        """Consistent snapshot (single reference read under the lock)."""
        with self._lock:
            return self._installed

    def round_snapshot(self):
        """(ScheduleSet, scns, profiles) for one executing round.  The
        schedule/cell pair is captured under ONE lock acquisition (resize
        swaps both under it), and the per-lane profiles are resolved HERE
        rather than lane-by-lane during execution, so a concurrent churn
        shrinking the scheduler's profile list mid-round can neither shift
        a lane onto the wrong cell's profile nor index past the end.  The
        cluster facade calls this under its own lock (which churn also
        holds), making the whole triple churn-consistent."""
        lag = None
        with self._lock:
            ss, scns = self._installed, list(self.scns)
            if ss is not None and self._pending_serve:
                t_inst = self._pending_serve.pop(ss.version, None)
                if t_inst is not None:
                    lag = self.clock() - t_inst
                # versions superseded before ever serving have no
                # first-serve moment — drop them so the table stays
                # bounded by the number of in-flight versions
                for v in [v for v in self._pending_serve
                          if v < ss.version]:
                    del self._pending_serve[v]
        if lag is not None and self.bus is not None:
            # swap-to-serve lag: this is the FIRST round to serve this
            # schedule version since its install
            self.bus.emit("swap_to_serve", version=ss.version, lag_s=lag)
        profs = [self.scheduler.profile_for(b) for b in range(len(scns))]
        return ss, scns, profs

    def serve_snapshot(self, ss: ScheduleSet, scns, profs,
                       tokens_per_cell, *, decode_steps=0
                       ) -> List[List[RequestResult]]:
        """Execute one round on an explicit ``round_snapshot`` triple —
        callers that pair the snapshot with their own per-cell state (the
        facade's CellId keying) capture it atomically and execute here,
        immune to concurrent churn."""
        rounds = []
        for b, sched in enumerate(ss.schedules):
            rounds.append(execute_schedule(
                self.params, self.cfg, scns[b].cfg, profs[b], sched,
                tokens_per_cell[b], decode_steps=decode_steps))
        return rounds

    @property
    def schedule_version(self) -> int:
        ss = self.current_schedules()
        return ss.version if ss else 0

    def set_scenario(self, cell: int, scn) -> None:
        """Publish a drifted channel snapshot for one cell (the execute
        path reads only host-side config off it; schedules are re-solved
        by the admission loop, not here)."""
        with self._lock:
            self.scns[cell] = scn

    # ---- serving -------------------------------------------------------
    def serve_scheduled_round(self, tokens_per_cell, *, decode_steps=0
                              ) -> List[List[RequestResult]]:
        """Execute one round with the installed schedules — no solve."""
        ss, scns, profs = self.round_snapshot()
        if ss is None:
            raise RuntimeError("no schedules installed yet "
                               "(bootstrap with install_schedules)")
        return self.serve_snapshot(ss, scns, profs, tokens_per_cell,
                                   decode_steps=decode_steps)

    def serve_round(self, tokens_per_cell, q_per_cell, *,
                    decode_steps=0) -> List[List[RequestResult]]:
        """Lockstep solve -> install -> execute.
        tokens_per_cell: (B, U, S) int32; q_per_cell: (B, U) seconds."""
        scheds = self.scheduler.schedule(q_per_cell)
        self.install_schedules(scheds)
        return self.serve_scheduled_round(tokens_per_cell,
                                          decode_steps=decode_steps)
