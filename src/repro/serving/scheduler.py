"""ERA-driven admission / placement scheduler.

Ties the paper's algorithm into the serving stack: given a scenario
(channel state), a split profile for the served model, and per-user QoE
thresholds, it runs Li-GD and emits a Schedule: per-user split point,
subchannel, tx power, edge compute share, plus predicted latency/energy/QoE
— the numbers the engine uses to simulate the radio and to group edge-side
batches.

Two schedulers share one outcome->Schedule lowering:
  EraScheduler       — one cell, the paper's setting, now on the
                       scan-compiled sweep by default (ligd.solve).
  MultiCellScheduler — B cells in ONE vmapped solve (ligd.solve_batch);
                       emits one Schedule per cell.  This is the serving
                       entry point the ROADMAP's fleet-scale work builds on:
                       cells share a compiled program, so admission cost
                       grows with device compute, not Python dispatch.

Partial rounds (``schedule(cells=...)``): an admission round that touched
k < B cells solves only those lanes, padded up a small ladder of batch
sizes (1/2/4/…/B — ``bucket_sizes``) so each bucket compiles exactly once
and a 2-dirty-cell drift round stops paying for a full-B sweep.  Padding
lanes repeat a real cell and are dropped from the result (lane
independence makes the real lanes' solutions identical to an exact-size
solve — regression-tested).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import era, ligd, network, noma, profiles
from repro.core.era import Weights
from repro.telemetry import spans


@jax.jit
def _scatter_lanes(leaves_b, lane_leaves, idx):
    """One compiled dispatch scattering k lanes' scenario leaves into the
    stacked batch: ``leaves_b[j][idx] = stack(lane_leaves[*][j])``.
    Replaces the per-leaf-per-lane ``.at[b].set`` chain (~27 leaves × k
    dispatches — the dominant host cost of a partial-round refresh and of
    ``move_user``'s drifted-receiver path).  ``idx`` is traced, so the
    compile caches on (leaf shapes, k) only — the same O(log B)-ish
    footprint as the bucket ladder."""
    return [xb.at[idx].set(jnp.stack([lv[j] for lv in lane_leaves]))
            for j, xb in enumerate(leaves_b)]


def bucket_sizes(n_cells: int) -> List[int]:
    """The padded-batch ladder for partial rounds: powers of two below
    n_cells, plus n_cells itself — at most O(log B) compiled variants."""
    if n_cells < 1:
        raise ValueError("need at least one cell")
    sizes, p = [], 1
    while p < n_cells:
        sizes.append(p)
        p *= 2
    sizes.append(n_cells)
    return sizes


def bucket_for(k: int, n_cells: int, policy: str = "pow2") -> int:
    """Padded lane count for k dirty cells under ``SolverSpec.bucket``:
    'pow2' = smallest ladder size that fits (the default — O(log B)
    compiled variants), 'exact' = k itself (no padding, one compiled
    program per subset size), 'full' = always all B lanes."""
    if not 1 <= k <= n_cells:
        raise ValueError(f"k must be in [1, {n_cells}], got {k}")
    if policy == "exact":
        return k
    if policy == "full":
        return n_cells
    # the ladder always ends with n_cells and k <= n_cells, so this returns
    for n in bucket_sizes(n_cells):
        if n >= k:
            return n


@dataclass
class Schedule:
    split: np.ndarray            # (U,) block index
    subchannel_up: np.ndarray    # (U,)
    subchannel_dn: np.ndarray    # (U,)
    power_up: np.ndarray         # (U,) W
    power_dn: np.ndarray         # (U,) W
    compute_units: np.ndarray    # (U,) r_i
    pred_latency: np.ndarray     # (U,) s
    pred_energy: np.ndarray      # (U,) J
    uplink_rate: np.ndarray      # (U,) bit/s
    downlink_rate: np.ndarray    # (U,) bit/s
    gamma: float
    iters: int

    def groups(self) -> Dict[int, np.ndarray]:
        """Users grouped by split point (edge batches share a split)."""
        return {int(s): np.nonzero(self.split == s)[0]
                for s in np.unique(self.split)}


@jax.jit
def _schedule_rates(scn, alloc):
    """Scheduled NOMA rates + hard channel picks, one compiled call."""
    r_up = noma.uplink_rates(scn, alloc.beta_up, alloc.p)
    r_dn = noma.downlink_rates(scn, alloc.beta_dn, alloc.p_ap)
    return (r_up, r_dn,
            jnp.argmax(alloc.beta_up, 1), jnp.argmax(alloc.beta_dn, 1))


def build_schedule(scn, out: ligd.LiGDOutcome) -> Schedule:
    """Lower a solver outcome to the engine-facing Schedule."""
    alloc = out.alloc
    r_up, r_dn, ch_up, ch_dn = _schedule_rates(scn, alloc)
    return Schedule(
        split=np.asarray(out.s),
        subchannel_up=np.asarray(ch_up),
        subchannel_dn=np.asarray(ch_dn),
        power_up=np.asarray(alloc.p),
        power_dn=np.asarray(alloc.p_ap),
        compute_units=np.asarray(alloc.r),
        pred_latency=np.asarray(out.terms.t),
        pred_energy=np.asarray(out.terms.e),
        uplink_rate=np.asarray(r_up),
        downlink_rate=np.asarray(r_dn),
        gamma=float(out.terms.gamma),
        iters=out.total_iters,
    )


def _ctor_spec(spec: Optional[ligd.SolverSpec], where: str, defaults: Dict,
               **legacy) -> ligd.SolverSpec:
    """Spec resolution for the scheduler constructors: exact ``spec=`` vs
    legacy-kwarg mix detection via ligd's unset sentinel (an explicitly
    passed kwarg always raises alongside ``spec=``, even at its default
    value), and the schedulers' own historical defaults — which
    intentionally differ from ``SolverSpec``'s (``per_user_split=True``
    here) — applied only when no spec is given."""
    passed = {k: v for k, v in legacy.items() if v is not ligd._UNSET}
    if spec is not None:
        if passed:
            raise ValueError(f"{where}: pass either spec= or the legacy "
                             f"kwargs {sorted(passed)}, not both")
        return spec
    kw = dict(defaults)
    kw.update(passed)
    return ligd.spec_from_kwargs(**kw)


class EraScheduler:
    def __init__(self, scn, prof: profiles.SplitProfile,
                 weights: Weights = Weights(),
                 spec: ligd.SolverSpec = None, *,
                 per_user_split=ligd._UNSET, max_steps=ligd._UNSET,
                 lr=ligd._UNSET, tol=ligd._UNSET,
                 compiled_sweep=ligd._UNSET):
        """One-cell ERA scheduler.  ``spec`` describes the solve
        (``SolverSpec``); the legacy kwargs are folded onto one when no
        spec is given (their historical defaults preserved).  Mixing
        ``spec=`` with a legacy kwarg raises, mirroring ``ligd.solve`` —
        a silently dropped kwarg is worse than an error."""
        spec = _ctor_spec(spec, "EraScheduler",
                          dict(per_user_split=True, max_steps=400, lr=0.05,
                               tol=1e-5, compiled_sweep=True),
                          per_user_split=per_user_split,
                          max_steps=max_steps, lr=lr, tol=tol,
                          compiled_sweep=compiled_sweep)
        self.scn = scn
        self.prof = prof
        self.weights = weights
        self.spec = spec

    def schedule(self, q_thresholds) -> Schedule:
        out = ligd.solve(self.scn, self.prof, jnp.asarray(q_thresholds),
                         self.weights, spec=self.spec)
        return build_schedule(self.scn, out)


class MultiCellScheduler:
    """Schedules B independent cells from ONE batched Li-GD solve.

    ``scns``: per-cell Scenarios sharing a NetworkConfig (stacked once at
    construction).  ``prof``: one shared SplitProfile, or a per-cell list
    with equal layer counts.  ``schedule`` takes (B, U) QoE thresholds and
    returns one Schedule per cell."""

    def __init__(self, scns: Sequence, prof,
                 weights: Weights = Weights(),
                 spec: ligd.SolverSpec = None, *,
                 per_user_split=ligd._UNSET, max_steps=ligd._UNSET,
                 lr=ligd._UNSET, tol=ligd._UNSET, gd_chunk=ligd._UNSET,
                 mesh=ligd._UNSET):
        """``spec`` (``SolverSpec``) describes every solve this scheduler
        runs — backend, GD knobs, bucket policy.  The legacy kwargs are
        folded onto one when no spec is given (historical defaults
        preserved; ``gd_chunk``/``mesh`` select the chunked/sharded
        backends exactly as ``ligd.spec_from_kwargs`` does).  Mixing
        ``spec=`` with a legacy kwarg raises, mirroring
        ``ligd.solve_batch``."""
        spec = _ctor_spec(spec, "MultiCellScheduler",
                          dict(per_user_split=True, max_steps=400, lr=0.05,
                               tol=1e-5, gd_chunk=0, mesh=None),
                          per_user_split=per_user_split,
                          max_steps=max_steps, lr=lr, tol=tol,
                          gd_chunk=gd_chunk, mesh=mesh)
        if spec.backend in ("sharded", "multihost") and spec.mesh is None:
            # resolve the all-devices default ONCE so every schedule()
            # call keys the sharded sweep's jit cache on the same Mesh
            spec = spec.replace(mesh=spec.run_mesh())
        self.spec = spec
        # multihost across >1 process: partial rounds and churn solves are
        # per-HOST events (arrivals/drift land on one host's queue), so
        # they run on a process-local sharded spec with identical GD
        # statics — per-lane numerics are bitwise the global program's,
        # and no cross-process rendezvous is needed per round.  Full-mesh
        # SPMD solves happen only at coordinated moments (bootstrap,
        # fenced churn) when every process calls schedule() in lockstep.
        self._host_spec = None
        if spec.backend == "multihost":
            from repro.distributed import multihost, solver_mesh
            if multihost.process_count() > 1:
                self._host_spec = spec.replace(
                    backend="sharded", mesh=solver_mesh.cells_mesh())
        self.scns = list(scns)
        # round-invariant solver inputs (stacked scenarios/profiles,
        # warm-start predecessors) are derived once, not per schedule()
        self.prep = ligd.prepare_batch(self.scns, prof, spec.warm_start)
        self.prof = prof
        self.weights = weights
        self.last_outcomes: List[Optional[ligd.LiGDOutcome]] = []

    @property
    def n_cells(self) -> int:
        return len(self.scns)

    @property
    def host_local_rounds(self) -> bool:
        """True when incremental (subset) rounds must stay on this
        process's devices — a multi-process ``multihost`` spec.  The
        admission loop reads this to route EVERY non-bootstrap round
        through the bucketed subset path (``admission._step_locked``),
        since per-host queues can never guarantee the all-process
        lockstep a global SPMD solve requires."""
        return self._host_spec is not None

    def profile_for(self, cell: int) -> profiles.SplitProfile:
        return self.prof[cell] if isinstance(self.prof, (list, tuple)) \
            else self.prof

    def update_scenarios(self, scns: Sequence,
                         cells: Sequence[int] = None) -> None:
        """Swap in drifted channel snapshots without re-deriving the
        round-invariant prep (profiles + warm-start predecessors): only the
        stacked scenario leaves change, same shapes, so the next
        ``schedule`` call hits the same compilation.

        ``cells``: update only these lanes, scatter-writing them into the
        stacked batch (``.at[b].set``) instead of re-stacking all B cells —
        keeps a k-dirty-cell partial round's host cost O(k), not O(B).
        Lanes outside ``cells`` keep the snapshot they were last solved
        on, which is exactly what their installed schedules reflect."""
        scns = list(scns)
        if len(scns) != self.n_cells:
            raise ValueError(f"need {self.n_cells} scenarios, "
                             f"got {len(scns)}")
        if cells is None:
            self.scns = scns
            self.prep = self.prep._replace(
                scn_b=network.stack_scenarios(scns), scn_list=tuple(scns),
                hetero=network.envs_differ(scns))
            return
        # flatten-level scatter: leaf order is fixed by the Scenario
        # pytree, so lanes with different (structurally compatible) cfg
        # aux still line up leaf-for-leaf
        leaves_b, treedef_b = jax.tree_util.tree_flatten(self.prep.scn_b)
        lane_leaves = []
        for b in cells:
            leaves_v = jax.tree_util.tree_leaves(scns[b])
            if len(leaves_v) != len(leaves_b):
                # a structurally incompatible scenario would silently land
                # in the wrong leaf slots
                raise ValueError(
                    f"scenario for cell {b} has {len(leaves_v)} pytree "
                    f"leaves, stacked batch has {len(leaves_b)}")
            self.scns[b] = scns[b]
            lane_leaves.append(leaves_v)
        if lane_leaves:
            leaves_b = _scatter_lanes(
                leaves_b, lane_leaves,
                jnp.asarray([int(b) for b in cells]))
        self.prep = self.prep._replace(
            scn_b=jax.tree_util.tree_unflatten(treedef_b, leaves_b),
            scn_list=tuple(self.scns),
            hetero=network.envs_differ(self.scns))

    def resize(self, scns: Sequence, prof=None, keep: Dict[int, int] = None
               ) -> None:
        """Cell churn: remap the stacked scenarios/profiles to a new cell
        list without dropping warm-start state for surviving cells.
        ``keep`` maps new lane -> old lane (default: identity over the
        overlapping prefix); unmapped new lanes start cold (uniform
        initial point on their first warm solve).

        When the profile set is unchanged (shared, ``prof=None``) and
        every surviving lane carries the scenario object it was last
        solved on, the stacked prep is REMAPPED rather than rebuilt:
        surviving lanes are gathered out of the old device-side batch
        (``network.take_cells``), joiners are stacked once and
        concatenated (``network.concat_cells``) — no O(B) host restack.
        Anything else (new profiles, per-cell profile lists, replaced
        survivor scenarios) falls back to a full ``prepare_batch``."""
        old_prep = self.prep
        old_outs = self.last_outcomes
        scns = list(scns)
        if keep is None:
            keep = {i: i for i in range(min(len(scns), len(old_outs)))}
        keep = {n: o for n, o in keep.items()
                if 0 <= n < len(scns) and 0 <= o < len(old_prep.scn_list)}
        new_prep = None
        if prof is None and not old_prep.prof_batched and scns:
            new_prep = self._remap_prep(scns, keep, old_prep)
        if new_prep is None:
            new_prep = ligd.prepare_batch(
                scns, self.prof if prof is None else prof,
                self.spec.warm_start)
        self.scns = scns
        if prof is not None:
            self.prof = prof
        self.prep = new_prep
        outs: List[Optional[ligd.LiGDOutcome]] = [None] * len(scns)
        for new_i, old_i in keep.items():
            if old_i < len(old_outs):
                outs[new_i] = old_outs[old_i]
        self.last_outcomes = outs

    def _remap_prep(self, scns, keep: Dict[int, int],
                    prep: ligd.BatchPrep) -> Optional[ligd.BatchPrep]:
        """Gather-survivors + concat-joiners prep for ``resize``'s fast
        path; None when the mapping needs a full rebuild.  Survivor lanes
        must carry the IDENTICAL scenario object they were last solved on
        — a different object for a kept lane means new channel state the
        gathered rows would silently miss, so it is treated as fresh."""
        ref_cfg = prep.scn_list[0].cfg
        lanes, fresh = [], []
        for i, scn in enumerate(scns):
            o = keep.get(i)
            if o is not None and scn is prep.scn_list[o]:
                lanes.append(("old", o))
            else:
                if not network.struct_compatible(scn.cfg, ref_cfg):
                    return None
                lanes.append(("new", len(fresh)))
                fresh.append(scn)
        old_idx = [o for kind, o in lanes if kind == "old"]
        parts, pred_parts = [], []
        if old_idx:
            parts.append(network.take_cells(prep.scn_b, old_idx))
            pred_parts.append(prep.pred_b[old_idx])
        if fresh:
            # normalise the joiners' static cfg aux to the old batch's
            # representative so the concatenated pytrees share a treedef
            # (per-cell numerics still travel via each env leaf)
            norm = [s if s.cfg == ref_cfg else
                    network.Scenario(ref_cfg, s.assoc, s.h_up, s.h_dn,
                                     s.up_order, s.up_group_end, s.dn_order,
                                     s.dn_group_end, env=s.env)
                    for s in fresh]
            parts.append(network.stack_scenarios(norm))
            pred_row = ligd.warm_start_predecessors(
                prep.prof_list[0].uplink_bits, self.spec.warm_start)
            pred_parts.append(np.stack([pred_row] * len(fresh)))
        scn_b = network.concat_cells(*parts)
        pred_b = np.concatenate(pred_parts, axis=0)
        # parts are ordered [survivors..., joiners...]; permute back to
        # lane order (identity for the common append-joiners case)
        n_old = len(old_idx)
        pos, n_seen_old = [], 0
        for kind, j in lanes:
            pos.append(n_seen_old if kind == "old" else n_old + j)
            if kind == "old":
                n_seen_old += 1
        if pos != list(range(len(lanes))):
            scn_b = network.take_cells(scn_b, pos)
            pred_b = pred_b[pos]
        return ligd.BatchPrep(
            scn_b=scn_b,
            scn_list=tuple(scns),
            prof_b=prep.prof_b,
            prof_list=(prep.prof_list[0],) * len(scns),
            prof_batched=False,
            pred_b=pred_b,
            hetero=network.envs_differ(scns),
        )

    def _warm_init(self, lanes: Sequence[int],
                   overrides: Dict[int, Dict] = None):
        """Warm-start Allocation for ``lanes`` from the previous outcomes;
        lanes without history (post-resize joiners) seed from the
        uninformed point.  None when no lane has history (and no
        overrides).

        ``overrides``: per-user row grafts for handover —
        ``{lane: {dst_user: (src_alloc, src_user)}}`` replaces the lane's
        warm-start row ``dst_user`` (every Allocation leaf's leading axis
        is U) with row ``src_user`` of ``src_alloc``, the moved user's
        solved allocation from its SOURCE cell.  With overrides present
        the init is built even without history (the grafted row is the
        whole point); padded duplicate lanes get the same graft, which is
        harmless — they are dropped from the result."""
        outs = self.last_outcomes
        has_hist = bool(outs) and any(outs[i] is not None for i in lanes)
        if not has_hist and not overrides:
            return None
        outs = outs if outs else [None] * self.n_cells
        allocs = [outs[i].alloc if outs[i] is not None
                  else era.uniform_alloc(self.scns[i]) for i in lanes]
        if overrides:
            # host-side graft: a handover is a latency-sensitive churn
            # op, and a per-leaf jax scatter costs ~ms of dispatch where
            # a numpy row copy is free (solve_batch converts the stacked
            # init to device arrays once anyway)
            def _graft(x, s, d, su):
                x = np.array(x)
                x[d] = np.asarray(s)[su]
                return x
            for j, lane in enumerate(lanes):
                for dst_u, (src_alloc, src_u) in \
                        (overrides.get(lane) or {}).items():
                    allocs[j] = jax.tree.map(
                        lambda x, s, d=int(dst_u), su=int(src_u):
                            _graft(x, s, d, su),
                        allocs[j], src_alloc)
        return ligd.stack_allocs(allocs)

    def _prep_subset(self, lanes: Sequence[int]) -> ligd.BatchPrep:
        """BatchPrep for a padded lane subset, sliced out of the full prep
        (device-side gathers — no host re-stacking, and the warm-start
        predecessor rows are reused, not recomputed)."""
        prep = self.prep
        scn_list = tuple(prep.scn_list[i] for i in lanes)
        prof_b = network.take_cells(prep.prof_b, lanes) \
            if prep.prof_batched else prep.prof_b
        return ligd.BatchPrep(
            scn_b=network.take_cells(prep.scn_b, lanes),
            scn_list=scn_list,
            prof_b=prof_b,
            prof_list=tuple(prep.prof_list[i] for i in lanes),
            prof_batched=prep.prof_batched,
            pred_b=prep.pred_b[list(lanes)],
            hetero=network.envs_differ(scn_list),
        )

    def schedule(self, q_per_cell, *, warm: bool = False,
                 init_alloc=None, cells: Sequence[int] = None,
                 bucket: str = None,
                 warm_overrides: Dict[int, Dict] = None) -> List[Schedule]:
        """One batched solve -> one Schedule per cell.

        ``warm=True`` seeds the solve from the previous ``schedule`` call's
        solved allocations (``ligd.warm_start_from``) — the admission
        loop's cross-round warm start; ``init_alloc`` overrides the seed
        explicitly.  ``warm_overrides`` grafts individual users' rows into
        the warm seed (see ``_warm_init``) — the handover path's
        carry-your-allocation-with-you mechanism; ignored when the solve
        is not warm (cold solves ignore history by definition).

        ``cells``: solve only this cell subset (a partial admission
        round), padded per the ``bucket`` policy (default: the spec's —
        the pow2 ladder hits jit's compile cache, so each bucket size
        compiles once; churn passes ``bucket='exact'`` so a join solves
        exactly its one lane regardless of policy).  Returns Schedules
        aligned with ``cells`` order; other cells' warm-start state is
        left untouched."""
        with spans.span("era.solve.warm"):
            q = jnp.asarray(q_per_cell)
            if cells is None:
                lanes = list(range(self.n_cells))
                prep, spec = self.prep, self.spec
            else:
                cells = list(cells)
                if not cells:
                    return []
                lanes = self._subset_lanes(q, cells, bucket)
                # identity lanes (k == B in order, or the 'full' policy
                # landing on an in-order full set) reuse the stored prep —
                # no gather needed
                prep = self.prep if lanes == list(range(self.n_cells)) \
                    else self._prep_subset(lanes)
                q = q[jnp.asarray(lanes)]
                # subset rounds run host-local under a multi-process
                # multihost spec (same GD statics => bitwise-identical
                # per-lane results)
                spec = self._host_spec or self.spec
            if init_alloc is None and warm:
                init_alloc = self._warm_init(lanes, overrides=warm_overrides)
        outs = ligd.solve_batch(None, None, q, self.weights, spec=spec,
                                prep=prep, init_alloc=init_alloc)
        with spans.span("era.solve.finalize"):
            if cells is None:
                cells = lanes
                self.last_outcomes = list(outs)
            else:
                if not self.last_outcomes:
                    self.last_outcomes = [None] * self.n_cells
                for j, c in enumerate(cells):          # real lanes only
                    self.last_outcomes[c] = outs[j]
            return [build_schedule(self.scns[c], outs[j])
                    for j, c in enumerate(cells)]

    def _subset_lanes(self, q, cells: List[int], bucket: str = None
                      ) -> List[int]:
        """The padded lane list for a partial round over ``cells``."""
        if sorted(set(cells)) != sorted(cells) or \
                not all(0 <= c < self.n_cells for c in cells):
            raise ValueError(f"cells must be distinct indices in "
                             f"[0, {self.n_cells}), got {cells}")
        # q is ALWAYS the full (B, U) matrix, indexed by `cells` here — a
        # subset-aligned q would gather the wrong rows silently (jax clamps
        # out-of-bounds gathers), so reject it loudly
        if q.ndim != 2 or q.shape[0] != self.n_cells:
            raise ValueError(f"q must be the full (B={self.n_cells}, U) "
                             f"threshold matrix, got {q.shape}")
        k = len(cells)
        n = bucket_for(k, self.n_cells, bucket or self.spec.bucket)
        return cells + [cells[-1]] * (n - k)      # pad: repeat last cell
