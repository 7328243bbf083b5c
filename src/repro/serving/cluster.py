"""``SplitInferenceCluster`` — the unified serving facade with first-class
cell lifecycle.

Three PRs of scaling work scattered the ERA solver's knobs across
``ligd.solve_batch`` kwargs, two scheduler classes, the
``AdmissionController`` and a dozen launcher flags — and cells were still
addressed by fragile positional lane index, so any join/leave invalidated
every reference held above the scheduler.  This module closes that seam:

  * HOW solves run lives in ONE frozen ``SolverSpec`` (``core.ligd``);
  * WHO is being served lives behind stable ``CellId`` handles: the
    cluster owns scheduler + engine + admission controller and an
    id->lane remap table, so drift references, warm-start lanes, aged-QoE
    state and in-flight versioned schedules all survive churn.

Lifecycle::

    cluster = SplitInferenceCluster(params, cfg, prof, spec=SolverSpec())
    a = cluster.add_cell(scn_a, q0=0.4)        # before start: staged
    b = cluster.add_cell(scn_b, q0=0.4)
    cluster.start()                            # bootstrap solve + install
    cluster.submit(a, user=3, q_s=0.25)        # arrivals by CellId
    cluster.observe(b, drifted_scn)            # drift marks by CellId
    out = cluster.serve_round({a: toks_a, b: toks_b})
    c = cluster.add_cell(scn_c, q0=0.4)        # mid-run join: 1-lane solve,
    cluster.remove_cell(a)                     #   survivors' schedules
    cluster.stop()                             #   carried over verbatim

Zero-downtime churn contract (regression-tested in tests/test_cluster.py):
``add_cell`` solves ONLY the joiner (a 1-lane bucket) and ``remove_cell``
solves nothing; both swap the engine's cell list + schedules in one
versioned install where surviving cells keep their installed ``Schedule``
OBJECTS (version continuity), and every piece of admission state — drift
reference snapshots, posted/aged QoE thresholds, warm-start allocations,
queued arrivals — follows the lane remap keyed by ``CellId``.

Threading: ``start(threaded=True)`` runs admission rounds on the
controller's background solver thread; ``threaded=False`` is the
deterministic sync mode (drive rounds with ``step()``, inject a fake
``clock``) the tests use.  All public methods are safe to call from the
serving thread.  Churn serialises against admission rounds on the
controller's round lock and acquires it BEFORE the facade lock, so
waiting out an in-flight background solve never stalls producers;
``submit``/``observe``/``serve_round`` block only for the churn op
itself (a 1-lane solve on join, a remap on leave).

Multi-host (``SolverSpec(backend='multihost')``, >1 process): each
process runs its OWN cluster over its contiguous slice of the global
cell fleet (``multihost.lane_slice``) — per-host admission queues, per-
host engines.  ``start()``'s bootstrap is the one global SPMD solve
(every process reaches it); after that, incremental rounds solve host-
locally (``MultiCellScheduler.host_local_rounds``) and live churn
rendezvous at a named fence under the round lock (``_churn_fence``) so
all processes mutate their cell sets at the same inter-round point.
The facade API is unchanged — the backend stays opaque, as intended.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, NewType, Optional

import numpy as np

from repro.core import ligd
from repro.core.era import Weights
from repro.core.ligd import SolverSpec
from repro.serving.admission import AdmissionController, AdmissionRound
from repro.serving.engine import MultiCellServeEngine, RequestResult
from repro.serving.scheduler import MultiCellScheduler, Schedule
from repro.telemetry import spans

# Stable handle for one cell, valid across join/leave for the cluster
# lifetime.  NEVER a lane index: lanes shift on churn, CellIds do not.
CellId = NewType("CellId", int)


class SplitInferenceCluster:
    """One object owning the whole serving stack for a fleet of cells.

    Construction wires the model (``params``/``model_cfg``/``prof``), the
    solver policy (``spec``/``weights``) and the admission policy
    (drift threshold, batching window, QoE aging).  Cells are added with
    ``add_cell`` — before ``start()`` they are staged; after, they join
    live with a coordinated 1-lane solve.

    ``params``/``model_cfg`` may be None for solver-only use (scheduling
    without executing a model — benchmarks and solver tests do this);
    ``serve_round`` then must not be called.
    """

    def __init__(self, params, model_cfg, prof, *,
                 spec: SolverSpec = None,
                 weights: Weights = Weights(),
                 drift_threshold: float = 0.15,
                 min_interval_s: float = 0.0,
                 qoe_half_life_s: Optional[float] = None,
                 q_age_cap: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 default_q_s: float = 0.4,
                 bus=None, governor=None):
        self.params = params
        self.model_cfg = model_cfg
        self.prof = prof
        self.spec = spec if spec is not None else SolverSpec()
        self.weights = weights
        self.drift_threshold = float(drift_threshold)
        self.min_interval_s = float(min_interval_s)
        self.qoe_half_life_s = qoe_half_life_s
        self.q_age_cap = q_age_cap
        self.clock = clock
        self.default_q_s = float(default_q_s)
        # observability + governance (both optional): the telemetry bus
        # (telemetry.TelemetryBus) is threaded through the engine and
        # admission controller at start(); the QoS governor
        # (serving.governor.QoSGovernor) is consulted by every admission
        # round.  None = no events, ungoverned policy — bitwise the
        # pre-telemetry serving behaviour.
        self.bus = bus
        self.governor = governor

        # id->lane remap table; _ids is its inverse (lane -> id)
        self._lane_of: Dict[CellId, int] = {}
        self._ids: List[CellId] = []
        self._next_id = 0
        self._staged: List[tuple] = []          # (id, scn, q_row) pre-start
        self._lock = threading.RLock()          # serialises churn/lookup

        self.scheduler: Optional[MultiCellScheduler] = None
        self.engine: Optional[MultiCellServeEngine] = None
        self.controller: Optional[AdmissionController] = None

    # ---- introspection -------------------------------------------------
    @property
    def started(self) -> bool:
        return self.controller is not None

    @property
    def n_cells(self) -> int:
        with self._lock:
            return len(self._ids) if self.started else len(self._staged)

    def cell_ids(self) -> List[CellId]:
        """Live cell handles in lane order (stable snapshot)."""
        with self._lock:
            return list(self._ids) if self.started \
                else [cid for cid, _, _ in self._staged]

    def lane_of(self, cell_id: CellId) -> int:
        """Current lane of a cell — for interop with lane-indexed
        internals; do not store it, it moves on churn."""
        with self._lock:
            return self._lane(cell_id)

    @property
    def schedule_version(self) -> int:
        return self.engine.schedule_version if self.started else 0

    @property
    def rounds(self) -> List[AdmissionRound]:
        """Completed admission rounds (bootstrap excluded), churn included."""
        self._require_started()
        return self.controller.rounds

    @property
    def errors(self):
        """Bounded deque of exceptions from failed background admission
        rounds (newest last; admission.ERROR_BACKLOG entries retained,
        each failure also emitted as a ``round_error`` bus event) —
        non-empty means some cells may be serving on stale schedules."""
        self._require_started()
        return self.controller.errors

    def _lane(self, cell_id: CellId) -> int:
        lane = self._lane_of.get(cell_id)
        if lane is None:
            raise KeyError(f"unknown or removed cell id {cell_id}")
        return lane

    def _require_started(self) -> None:
        if not self.started:
            raise RuntimeError("cluster not started — call start() first")

    def _churn_fence(self, tag: str) -> None:
        """Multi-process ``multihost`` churn coordination: every process
        must mutate its local cell set at the same point between rounds,
        so live ``add_cell``/``remove_cell`` rendezvous at a named
        barrier INSIDE the round-lock hold (``controller.paused()``) —
        process 0's participation is what serialises the global churn
        order, reusing the same lock that already serialises churn
        against admission rounds locally.  The tag encodes the op and
        this process's churn sequence, so divergent churn across
        processes fails loudly in the barrier instead of desynchronising
        a later coordinated solve.  No-op single-process and for every
        other backend (the fence never touches ``jax.distributed``
        state unless the spec is multihost)."""
        if self.spec.backend != "multihost":
            return
        from repro.distributed import multihost
        multihost.churn_fence(tag)

    # ---- lifecycle -----------------------------------------------------
    def _q_row(self, q0) -> np.ndarray:
        u = self.prof_n_users()
        q0 = self.default_q_s if q0 is None else q0
        return np.broadcast_to(np.asarray(q0, np.float32), (u,)).copy()

    def prof_n_users(self) -> int:
        """User-axis size, from the first cell's scenario config."""
        with self._lock:
            if self.started:
                return self.engine.scns[0].cfg.n_users
            if self._staged:
                return self._staged[0][1].cfg.n_users
        raise RuntimeError("no cells yet — add_cell() first")

    def add_cell(self, scn, q0=None, prof=None) -> CellId:
        """Admit a cell (channel snapshot ``scn``, per-user QoE thresholds
        ``q0``: scalar or (U,), default ``default_q_s``) and return its
        stable ``CellId``.  Before ``start()`` the cell is staged; after,
        it joins live: only ITS lane is solved, surviving cells' installed
        schedules carry over object-identical in one versioned swap.
        ``prof``: the joiner's split profile, only for clusters built over
        a per-cell profile list (shared-profile clusters reject it)."""
        with self._lock:
            if not self.started:
                if prof is not None:
                    raise ValueError("per-cell prof= applies to live joins "
                                     "only; stage profiles via the "
                                     "cluster's prof list")
                cid = CellId(self._next_id)
                self._next_id += 1
                self._staged.append((cid, scn, None if q0 is None
                                     else np.asarray(q0, np.float32)))
                return cid
            cid = CellId(self._next_id)
            self._next_id += 1
            q_row = self._q_row(q0)
        # round lock FIRST, facade lock second: waiting out an in-flight
        # background solve must not hold the facade lock, or every
        # submit/observe/serve_round would stall behind it.  Producers
        # block only for the churn op itself (a 1-lane solve).
        with self.controller.paused():
            self._churn_fence(f"add_cell:{cid}")
            with self._lock:
                lane = self.controller.add_cell(scn, q_row, prof=prof)
                assert lane == len(self._ids)    # controller appends
                self._ids.append(cid)
                self._lane_of[cid] = lane
        return cid

    def remove_cell(self, cell_id: CellId) -> None:
        """Evict a cell.  Before ``start()``: unstage it.  After: drop its
        lane with NO solve — survivors' schedules, warm-start state, drift
        references, posted/aged thresholds and queued work all follow the
        lane remap; the handle becomes invalid."""
        with self._lock:
            if not self.started:
                n = len(self._staged)
                self._staged = [e for e in self._staged if e[0] != cell_id]
                if len(self._staged) == n:
                    raise KeyError(f"unknown or removed cell id {cell_id}")
                return
            self._lane(cell_id)                  # fail fast on bad ids
        # same lock order as add_cell: wait out any in-flight admission
        # round before taking the facade lock (lane resolved again inside
        # — churn between the check above and here may have moved it)
        with self.controller.paused():
            self._churn_fence(f"remove_cell:{cell_id}")
            with self._lock:
                lane = self._lane(cell_id)
                old_to_new = self.controller.remove_cell(lane)
                self._ids = [i for ln, i in enumerate(self._ids)
                             if ln != lane]
                self._lane_of = {i: old_to_new[ln]
                                 for i, ln in self._lane_of.items()
                                 if ln in old_to_new}

    def move_user(self, src: CellId, dst: CellId, user: int,
                  dst_user: Optional[int] = None) -> AdmissionRound:
        """Hand a user over between live cells: its posted QoE threshold
        (and age) and any queued arrivals move from slot ``user`` of
        ``src`` to slot ``dst_user`` (default: same index) of ``dst``,
        then ONLY the receiving cell re-solves — a 1-lane warm solve with
        the user's allocation row seeded from its source-cell outcome.
        The source cell is untouched (no solve, drift reference kept,
        like ``remove_cell``); every other cell keeps its installed
        schedule object-identical through the single version bump.
        Requires a started cluster (there is no staged-mobility notion —
        restage the user's threshold instead).  Returns the churn
        ``AdmissionRound`` (``cells == (dst lane,)``)."""
        self._require_started()
        with self._lock:
            # fail fast on bad ids before taking the round lock
            self._lane(src)
            self._lane(dst)
        # round lock FIRST, facade lock second — same churn discipline as
        # add_cell/remove_cell (lanes resolved again inside: churn between
        # the check above and here may have moved them)
        with self.controller.paused():
            self._churn_fence(
                f"move_user:{src}->{dst}:{user}->"
                f"{user if dst_user is None else dst_user}")
            with self._lock:
                return self.controller.move_user(
                    self._lane(src), self._lane(dst), user,
                    dst_user=dst_user)

    def start(self, threaded: bool = True) -> int:
        """Build scheduler/engine/controller over the staged cells, run
        the bootstrap solve, install schedules, and (``threaded=True``)
        start the background admission loop.  Returns the installed
        schedule version (1)."""
        with self._lock:
            if self.started:
                raise RuntimeError("cluster already started")
            if not self._staged:
                raise RuntimeError("no cells staged — add_cell() first")
            ids, scns, q_rows = zip(*self._staged)
            q0 = np.stack([self._q_row(r) for r in q_rows])
            self.scheduler = MultiCellScheduler(
                list(scns), self.prof, self.weights, spec=self.spec)
            self.engine = MultiCellServeEngine(
                self.params, self.model_cfg, list(scns), self.scheduler,
                bus=self.bus, clock=self.clock)
            self.controller = AdmissionController(
                self.engine,
                drift_threshold=self.drift_threshold,
                clock=self.clock,
                warm_start=self.spec.warm,
                min_interval_s=self.min_interval_s,
                partial_batch=self.spec.bucket != "full",
                qoe_half_life_s=self.qoe_half_life_s,
                q_age_cap=self.q_age_cap,
                bus=self.bus, governor=self.governor)
            self._ids = list(ids)
            self._lane_of = {cid: lane for lane, cid in enumerate(ids)}
            self._staged = []
            version = self.controller.bootstrap(q0)
            if threaded:
                self.controller.start()
            return version

    def stop(self, drain: bool = True) -> None:
        """Shut the admission loop down (``drain=True`` runs one final
        round over still-queued work).  The cluster stays inspectable but
        no longer serves."""
        if self.started:
            self.controller.stop(drain=drain)

    # ---- serving-side producers ---------------------------------------
    def submit(self, cell_id: CellId, user: int, q_s: float):
        """A user arrives (or renews its QoE deadline) in a cell."""
        self._require_started()
        with self._lock:
            lane = self._lane(cell_id)
            return self.controller.submit(lane, user, q_s)

    def observe(self, cell_id: CellId, scn) -> float:
        """Publish a cell's live channel snapshot; returns drift vs the
        snapshot its active schedule was solved on and marks it for
        re-scheduling past the threshold."""
        self._require_started()
        with self._lock:
            lane = self._lane(cell_id)
            return self.controller.observe_scenario(lane, scn)

    def step(self) -> Optional[AdmissionRound]:
        """Drive one admission round synchronously (sync mode / tests)."""
        self._require_started()
        return self.controller.step()

    def paused(self):
        """Context manager holding the admission round lock: no admission
        round or churn op runs inside the block (serving and producers
        stay live).  For atomic before/after reads around a churn op."""
        self._require_started()
        return self.controller.paused()

    # ---- serving -------------------------------------------------------
    def serve_round(self, tokens_by_cell, *, decode_steps: int = 0
                    ) -> Dict[CellId, List[RequestResult]]:
        """Execute one round on the INSTALLED schedules (no solve).

        ``tokens_by_cell``: {CellId: (U, S) int32} covering every live
        cell, or a (B, U, S) array in lane order.  Results come back keyed
        by CellId.

        The CellId list and the engine's (ScheduleSet, scns, profiles)
        snapshot are captured under ONE facade-lock acquisition — churn
        holds the same lock while it remaps them, so a concurrent
        add/remove can never pair this round's ids with a
        differently-shaped schedule/profile set (the round then executes
        outside the lock, on its own snapshot).

        With a bus, the ``serve_round`` event carries the round's phases
        (``telemetry.span``): ``serve_s``, ``first_token_s`` (one offset
        from the round's start per split group, cells in lane order),
        ``prefill_s``, ``decode_s`` and ``decode_steps`` (summed over
        cells), and the compile requests of prefill, decode and the whole
        round."""
        self._require_started()
        with spans.recording(self.bus is not None) as rec, \
                spans.span("era.serve"):
            with self._lock:
                ids = list(self._ids)
                ss, scns, profs = self.engine.round_snapshot()
            if ss is None:
                raise RuntimeError("no schedules installed yet")
            if isinstance(tokens_by_cell, dict):
                missing = [c for c in ids if c not in tokens_by_cell]
                if missing:
                    raise ValueError(f"missing tokens for cells {missing}")
                tokens = [tokens_by_cell[c] for c in ids]
            else:
                tokens = tokens_by_cell
                if len(tokens) != len(ids):
                    raise ValueError(f"need tokens for {len(ids)} cells, "
                                     f"got {len(tokens)}")
            rounds = self.engine.serve_snapshot(ss, scns, profs, tokens,
                                                decode_steps=decode_steps)
        if rec is not None:
            self.bus.emit("serve_round", version=ss.version,
                          n_cells=len(ids),
                          n_users=sum(len(r) for r in rounds),
                          serve_s=rec.seconds["era.serve"],
                          first_token_s=rec.marks.get("first_token", []),
                          **rec.fields(prefill="era.serve.prefill",
                                       decode="era.serve.decode_step"),
                          decode_steps=rec.counts.get(
                              "era.serve.decode_step", 0),
                          compiles=rec.total_compiles)
        return {cid: res for cid, res in zip(ids, rounds)}

    # ---- per-cell state, keyed by CellId (tests / observability) -------
    def posted_q(self, cell_id: CellId) -> np.ndarray:
        """The cell's posted (un-aged) QoE thresholds."""
        self._require_started()
        with self._lock:
            return self.controller.current_q()[self._lane(cell_id)]

    def effective_q(self, cell_id: CellId) -> np.ndarray:
        """The aged thresholds a round starting now would solve with."""
        self._require_started()
        with self._lock:
            return self.controller.effective_q()[self._lane(cell_id)]

    def qoe_attainment(self, cell_id: CellId) -> float:
        """The cell's last measured QoE attainment: fraction of its users
        whose predicted delay beat their effective aged threshold at the
        round that last solved it (admission.qoe_attainment)."""
        self._require_started()
        with self._lock:
            att = self.controller.attainment()
            return float(att[self._lane(cell_id)])

    def drift_reference(self, cell_id: CellId):
        """The scenario snapshot the cell's active schedule was solved on
        (what ``observe`` measures drift against)."""
        self._require_started()
        with self._lock:
            return self.controller.reference_scenario(self._lane(cell_id))

    def last_outcome(self, cell_id: CellId) -> Optional[ligd.LiGDOutcome]:
        """The cell's most recent solver outcome (its warm-start seed)."""
        self._require_started()
        with self._lock:
            return self.scheduler.last_outcomes[self._lane(cell_id)]

    def installed_schedule(self, cell_id: CellId) -> Schedule:
        """The cell's currently installed schedule."""
        self._require_started()
        with self._lock:
            # lane lookup and schedule read under one lock acquisition:
            # churn also holds this lock, so the pair stays consistent
            lane = self._lane(cell_id)
            ss = self.engine.current_schedules()
        if ss is None:
            raise RuntimeError("no schedules installed yet")
        return ss.schedules[lane]
