"""Li-GD — Loop-iteration Gradient Descent (paper §III, Table I) and the
cold-start GD baseline it is compared against (Corollary 4).

Structure per the paper:
  1. relax β ∈ {0,1} -> [0,1] (Corollary 1 makes Γ differentiable);
  2. for each candidate split point s: run projected GD on (β_up, β_dn, p,
     P, r) to minimise Γ_s (eq. 27);
  3. WARM START: layer j's GD starts from the solved layer whose
     intermediate data size w is closest to w_j (Table I lines 13–16) — the
     loop-iteration trick that shrinks ‖x⁰ − x*‖² and hence iterations
     (Corollary 4);
  4. pick s* = argmin_s Γ_s, round β to one-hot (≤3 users/channel) and the
     QoE indicator by the 1/2 rule; SIC-infeasible users fall back to
     device-only (paper §II.B).

GD details: plain descent with a fixed per-variable diagonal preconditioner
(each variable's step is scaled by its feasible range — the paper's step
size λ applied in normalised coordinates), projection = box clip + β row
renormalisation.  Stops when ‖g‖<ε, |ΔΓ|<ε, or k = max_steps (Table I
lines 6/9).

Compiled sweep (this module's batched API): the warm-start predecessor
graph depends only on the *static* ``uplink_bits`` profile, never on GD
iterates, so ``warm_start_predecessors`` precomputes the visit order
host-side and the whole F+1 sweep runs as ONE ``jax.lax.scan`` over a
stacked ``Allocation`` buffer (``_sweep_scan``) — no per-layer dispatch, no
host sync between layers.  ``solve(compiled_sweep=False)`` keeps the
original per-layer Python loop as the reference implementation.
``solve_batch`` vmaps the scanned sweep over a leading scenario axis so one
compiled call schedules B independent cells; ``solve_batch(mesh=...)``
additionally shards that cell axis across devices with ``shard_map``
(``distributed.solver_mesh``) — the sweep body has no cross-cell
reductions (noma.py/era.py batch-safety audits), so the SPMD program needs
no collectives until the final output gather.

Inner GD loop structure (``gd_chunk``): 0 runs the per-lane
``while_loop`` reference — under vmap every lane steps until the slowest
lane's layer converges (lockstep).  ``gd_chunk=k`` runs an outer
while-of-chunks of fixed ``k``-step partially-unrolled scans whose steps
freeze converged lanes by select, so iterates and ``iters_by_layer`` stay
the reference's (Corollary-4 plots unchanged) while wasted work is
bounded by ``k-1`` steps per lane, and under the cells mesh each device
exits on its own lanes instead of the global slowest cell.

How a solve runs is described by ONE object, the frozen ``SolverSpec``
(``solve``/``solve_batch`` take ``spec=``; the pre-spec kwarg sprawl —
``compiled_sweep``/``gd_chunk``/``mesh`` — still works through a
deprecation shim that maps onto the equivalent spec).  Its ``backend``
picks the sweep engine:
  ``reference`` — vmapped while_loop GD on one device (the bit-exact
                  baseline every other backend is regression-tested
                  against);
  ``chunked``   — ``gd_chunk``-step partially-unrolled scans with
                  per-lane carry freeze (lockstep-free, iterates
                  identical to reference);
  ``sharded``   — the chunked-or-while sweep under ``shard_map`` over a
                  ``cells`` device mesh (``spec.mesh``, default: all
                  visible devices);
  ``multihost`` — the SAME sharded sweep over a ``jax.distributed``
                  GLOBAL device mesh: every process passes its own
                  lanes, the compiled SPMD program spans all hosts with
                  ~0 cross-host bytes, and each process gets back only
                  its lanes' outcomes (``distributed.multihost``;
                  single-process it degenerates to ``sharded`` exactly).

Static vs traced argument split, in ``SolverSpec`` terms (applies to
``_sweep_scan``, the chunked sweep, the ``solver_mesh`` sharded sweep, and
everything above them):
  static  — ``spec.max_steps``, ``spec.adaptive``, ``spec.gd_chunk``
            (loop structure), ``spec.mesh`` (device set + axis name,
            ``sharded`` backend only), ``Weights`` (hashable frozen
            dataclass), the scenario's ``NetworkConfig`` (pytree aux),
            the profile's layer count F (leaf shapes), and the padded
            batch size B (``spec.bucket`` maps dirty-cell counts onto a
            small ladder of these so each bucket compiles once).
            Changing any of these recompiles — which is why they live in
            the frozen spec: one spec == one family of compiled programs.
  traced  — channel state (``Scenario`` leaves), the per-cell numeric
            network parameters (the ``CellEnv`` leaf — power/compute
            bounds, noise floor, bandwidth …, so heterogeneous-config
            batches vmap per lane), profile FLOP/bit tables
            (``SplitProfile`` leaves, incl. ``input_bits``/``result_bits``),
            QoE thresholds ``q``, ``spec.lr``/``spec.tol``, the warm-start
            predecessor index vector, and the initial allocation.  These
            can change every admission round without recompiling.
  host    — ``spec.warm_start`` (predecessor-graph precompute),
            ``spec.warm`` (cross-round warm seeding policy, consumed by
            the serving layer), ``spec.bucket``/``spec.per_user_split``/
            ``spec.compiled_sweep`` (host-side dispatch structure).

Beyond-paper extension (``per_user_split=True``, "ERA+"): the paper commits
one global s*; ERA+ reuses the F+1 solved GD problems to pick per-user
s_i = argmin_s of user i's utility contribution, then re-polishes the
allocation with the mixed split vector.  Recorded separately in benchmarks.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from dataclasses import replace as _dc_replace
from functools import partial
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import network, noma, profiles
from repro.core.era import (Allocation, Terms, Weights, clip_alloc,
                            round_beta, uniform_alloc, utility)
from repro.telemetry import spans

_BACKENDS = ("reference", "chunked", "sharded", "multihost")
_BUCKETS = ("pow2", "exact", "full")
_STEP_IMPLS = ("xla", "fused")
_PLACEMENTS = ("none", "sorted")

# gd_chunk a `backend="chunked"` spec defaults to when none is given —
# long enough that XLA fuses across GD steps, short enough that wasted
# selected-away work per lane stays small (benchmarks/sharded_solver.py)
DEFAULT_GD_CHUNK = 8


@dataclass(frozen=True)
class SolverSpec:
    """Frozen, validated description of HOW a Li-GD solve runs.

    One spec == one family of compiled programs: every field is either a
    jit-static of the sweep (backend/gd_chunk/mesh/max_steps/adaptive), a
    traced scalar threaded into it (lr/tol), or a host-side dispatch
    policy (warm_start/warm/bucket/per_user_split/compiled_sweep).  The
    serving stack (``MultiCellScheduler``, ``SplitInferenceCluster``)
    stores exactly one spec and threads it everywhere a solve happens —
    replacing the per-call kwarg sprawl the pre-spec API grew.

    Fields:
      backend         'reference' | 'chunked' | 'sharded' | 'multihost'
                      (module docs).
      gd_chunk        inner-GD scan segment length.  0 on 'reference'
                      (enforced); 'chunked' defaults it to
                      ``DEFAULT_GD_CHUNK`` when left at 0; 'sharded' and
                      'multihost' compose with either (0 = while_loop
                      per shard).
      lr / tol /
      max_steps       the GD knobs of Table I (step size, stop test,
                      iteration budget).
      warm_start      Table I's nearest-w predecessor warm start inside
                      one sweep (False = the cold-start GD baseline).
      warm            cross-ROUND warm start: serving re-solves seed from
                      the previous round's solved allocations
                      (``warm_start_from``).  Consumed by the serving
                      layer, not by a single ``solve_batch`` call.
      per_user_split  ERA+ per-user split pick + polish (beyond paper).
      adaptive        backtracking step-size control (beyond paper).
      compiled_sweep  False = the seed-structured per-layer Python loop
                      (single-cell reference path; 'reference' backend
                      only).
      bucket          partial-round padding policy for dirty-cell subsets:
                      'pow2' (1/2/4/…/B ladder, O(log B) compiled
                      variants), 'exact' (no padding, one compile per
                      subset size), 'full' (always solve all B lanes).
      mesh            explicit ``jax.Mesh`` for 'sharded'/'multihost'
                      (None = build a ``cells`` mesh at use: over every
                      visible device for 'sharded', over the GLOBAL
                      ``jax.distributed`` device set for 'multihost' —
                      ``multihost.global_cells_mesh``, which must span
                      every process's devices; single-process the two
                      defaults are the identical memoised Mesh object).
      step_impl       'xla' (autodiff value_and_grad — the reference) |
                      'fused' (the one-launch fused forward+backward GD
                      step, kernels/era_step: Pallas kernel on TPU, the
                      analytic jnp oracle elsewhere).  Composes with every
                      backend; jit-static of the sweep.
      lane_placement  'none' | 'sorted' — 'sorted' permutes lanes by the
                      previous same-size round's total iteration counts
                      before the sharded ``shard_map`` (hardest lanes
                      dealt round-robin across shards) and inverts the
                      permutation on output; outcomes are exactly the
                      'none' ordering's.  'sharded' backend only.
      step_block_m    channel-tile size of the fused step's Pallas grid
                      (``kernels/era_step``): 0 (default) auto-sizes from
                      the kernel's VMEM budget — untiled whenever the
                      whole problem fits, paper scale included; > 0 is
                      rounded to a tile Mosaic compiles (M, or a multiple
                      of 8) and that block is forced on both the kernel
                      and the jnp oracle (the oracle runs its tiled
                      mirror, reproducing the kernel's accumulation
                      order).  'fused' step_impl only; jit-static of the
                      sweep.
    """
    backend: str = "reference"
    gd_chunk: int = 0
    lr: float = 0.05
    tol: float = 1e-5
    max_steps: int = 400
    warm_start: bool = True
    warm: bool = True
    per_user_split: bool = False
    adaptive: bool = False
    compiled_sweep: bool = True
    bucket: str = "pow2"
    mesh: Optional[object] = None          # jax.sharding.Mesh (hashable)
    step_impl: str = "xla"
    lane_placement: str = "none"
    step_block_m: int = 0

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.bucket not in _BUCKETS:
            raise ValueError(f"bucket must be one of {_BUCKETS}, "
                             f"got {self.bucket!r}")
        if self.gd_chunk < 0:
            raise ValueError(f"gd_chunk must be >= 0, got {self.gd_chunk}")
        if self.backend == "chunked" and self.gd_chunk == 0:
            object.__setattr__(self, "gd_chunk", DEFAULT_GD_CHUNK)
        if self.backend == "reference" and self.gd_chunk:
            raise ValueError("backend='reference' runs the while_loop GD; "
                             "use backend='chunked' for gd_chunk>0")
        if self.mesh is not None and self.backend not in ("sharded",
                                                          "multihost"):
            raise ValueError("mesh= only applies to backend='sharded' "
                             "or 'multihost'")
        if not self.compiled_sweep and self.backend != "reference":
            raise ValueError("compiled_sweep=False (per-layer reference "
                             "loop) only composes with backend='reference'")
        if self.step_impl not in _STEP_IMPLS:
            raise ValueError(f"step_impl must be one of {_STEP_IMPLS}, "
                             f"got {self.step_impl!r}")
        if self.lane_placement not in _PLACEMENTS:
            raise ValueError(f"lane_placement must be one of {_PLACEMENTS},"
                             f" got {self.lane_placement!r}")
        if self.lane_placement == "sorted" and self.backend != "sharded":
            # multihost rejects it too: a global permutation would need
            # every host to see every lane's iteration history — exactly
            # the cross-host traffic the backend exists to avoid
            raise ValueError("lane_placement='sorted' permutes lanes "
                             "across mesh shards — it only applies to "
                             "backend='sharded'")
        if self.step_block_m < 0:
            raise ValueError(f"step_block_m must be >= 0, "
                             f"got {self.step_block_m}")
        if self.step_block_m and self.step_impl != "fused":
            raise ValueError("step_block_m tiles the fused step's kernel "
                             "grid — it only applies to step_impl='fused'")
        if not self.lr > 0:
            raise ValueError(f"lr must be > 0, got {self.lr}")
        if self.tol < 0:
            raise ValueError(f"tol must be >= 0, got {self.tol}")
        if self.max_steps < 1:
            raise ValueError(f"max_steps must be >= 1, got {self.max_steps}")

    def replace(self, **kw) -> "SolverSpec":
        """Functional update (re-validated)."""
        return _dc_replace(self, **kw)

    def run_mesh(self):
        """The mesh a ``sharded``/``multihost`` solve runs on (None for
        the single-device backends); an unset mesh resolves to a
        ``cells`` mesh over every visible device ('sharded') or the
        global ``jax.distributed`` device set ('multihost').  Both
        resolvers memoise, so repeated resolution returns the identical
        Mesh object and the sweep's jit cache keys stay stable."""
        if self.backend not in ("sharded", "multihost"):
            return None
        if self.mesh is not None:
            return self.mesh
        if self.backend == "multihost":
            from repro.distributed import multihost
            return multihost.global_cells_mesh()
        from repro.distributed import solver_mesh
        return solver_mesh.cells_mesh()


class _Unset:
    def __repr__(self):
        return "<unset>"


_UNSET = _Unset()

# legacy kwargs that warn (the ISSUE-era sprawl SolverSpec replaces);
# plain numeric knobs (lr/tol/max_steps/...) fold into the spec silently
_SPEC_DEPRECATED = ("compiled_sweep", "gd_chunk", "mesh")
# passing a deprecated kwarg at its no-op value is vacuous — fold it
# without warning (and without conflicting with an explicit spec=)
_VACUOUS = {"compiled_sweep": True, "gd_chunk": 0, "mesh": None}


def spec_from_kwargs(**kw) -> SolverSpec:
    """Map the legacy kwarg sprawl onto a ``SolverSpec``: ``mesh`` selects
    the sharded backend, else ``gd_chunk>0`` selects chunked, else
    reference.  Shared by the ``solve``/``solve_batch`` deprecation shims
    and the serving constructors' legacy signatures."""
    gd_chunk = int(kw.pop("gd_chunk", 0) or 0)
    mesh = kw.pop("mesh", None)
    if mesh is not None:
        kw.update(backend="sharded", mesh=mesh, gd_chunk=gd_chunk)
    elif gd_chunk:
        kw.update(backend="chunked", gd_chunk=gd_chunk)
    return SolverSpec(**kw)


def _resolve_spec(spec: Optional[SolverSpec], where: str,
                  **legacy) -> SolverSpec:
    """Either take the explicit ``spec=`` or build one from legacy kwargs.
    Mixing the two is rejected; deprecated structural kwargs
    (``compiled_sweep``/``gd_chunk``/``mesh``) warn."""
    passed = {k: v for k, v in legacy.items()
              if v is not _UNSET and _VACUOUS.get(k, _UNSET) != v}
    if spec is not None:
        if passed:
            raise ValueError(
                f"{where}: pass either spec= or the legacy kwargs "
                f"{sorted(passed)}, not both")
        return spec
    dep = sorted(k for k in passed if k in _SPEC_DEPRECATED)
    if dep:
        warnings.warn(
            f"{where}({', '.join(dep)}=...) is deprecated; build a "
            "SolverSpec and pass spec= (README.md has the migration "
            "table)", DeprecationWarning, stacklevel=3)
    return spec_from_kwargs(**passed)


class GDResult(NamedTuple):
    alloc: Allocation
    gamma: jnp.ndarray
    iters: jnp.ndarray


class LiGDOutcome(NamedTuple):
    s: np.ndarray                 # (U,) chosen split per user
    alloc: Allocation             # rounded allocation
    terms: Terms                  # evaluated at the rounded solution
    gamma_by_layer: np.ndarray    # (F+1,) Γ_s landscape
    iters_by_layer: np.ndarray    # (F+1,) GD iterations (Corollary 4 data)
    total_iters: int


def _scales(env):
    """Per-variable preconditioner ranges; ``env`` is the scenario's
    ``CellEnv`` leaf so ranges stay per-cell under the vmapped sweep."""
    return Allocation(
        beta_up=1.0,
        beta_dn=1.0,
        p=env.p_max_w - env.p_min_w,
        p_ap=env.ap_p_max_w - env.ap_p_min_w,
        r=env.r_max - env.r_min,
    )


def _gd_core(scn, s_vec, q, x0, lr, tol, max_steps, w, prof,
             adaptive=False, gd_chunk=0, step_impl="xla", step_block_m=0,
             step_aux=None):
    """Projected, preconditioned GD on Γ — pure traced function, shared by
    the per-layer jitted path and the scan-compiled sweep.

    ``adaptive=True`` (beyond paper — the paper's §III closing remark
    suggests self-adaptive step sizes): backtracking multiplicative step
    control — shrink 0.5× on a worsening step (and reject it), grow 1.1×
    on an improving one.

    ``gd_chunk=0`` (reference): a single ``while_loop`` runs until this
    lane's own stop test fires.  Under ``vmap``/``shard_map`` that loop is
    batched to run every lane until the SLOWEST lane stops — the lockstep
    tax the ROADMAP names.  ``gd_chunk=k`` replaces it with an outer
    while-of-chunks: each segment is a fixed ``k``-step ``lax.scan``
    (partially unrolled, so XLA fuses across GD steps) whose steps freeze
    an already-converged lane's carry via select — iterates and the
    per-lane iteration count ``iters`` stay exactly the reference's — and
    the outer loop exits as soon as EVERY lane in the (local) batch is
    done.  Wasted work per lane is bounded by ``k - 1`` selected-away
    steps, and under the cell-sharded mesh each device's outer loop exits
    on its own lanes, not the global slowest cell.

    ``step_impl='fused'`` swaps the autodiff ``value_and_grad`` body for
    the one-launch fused forward+backward step (kernels/era_step — Pallas
    kernel on TPU, analytic jnp oracle elsewhere); the final Γ evaluation
    and the adaptive path's extra forward stay on the XLA ``loss``, so
    reported gammas are computed identically under both impls.
    ``step_block_m``: the fused step's channel-tile size (0 = VMEM-budget
    auto-sizing; kernels/era_step/kernel.py).
    ``step_aux``: a precomputed ``era_step.ops.build_aux(scn)`` — the
    scanned sweep hoists it out of the layer loop; None builds it here."""

    def loss(alloc):
        return utility(scn, prof, s_vec, alloc, q, w).gamma

    if step_impl == "fused":
        from repro.kernels.era_step import ops as _era_step_ops
        aux = (step_aux if step_aux is not None
               else _era_step_ops.build_aux(scn))

        def grad_fn(alloc):
            return _era_step_ops.era_step_value_and_grad(
                scn, prof, s_vec, q, alloc, w, aux=aux,
                block_m=step_block_m)
    else:
        grad_fn = jax.value_and_grad(loss)
    scales = _scales(scn.env)

    def cond(carry):
        _, _, k, done, _ = carry
        return (~done) & (k < max_steps)

    def body(carry):
        alloc, prev_val, k, _, cur_lr = carry
        val, g = grad_fn(alloc)
        with jax.named_scope("era.grad_norm"):
            # guard against inf gradients from degenerate (near-zero-rate)
            # allocations: 1/R² terms in eq. (34) blow up as R -> 0
            g = jax.tree.map(lambda x: jnp.where(jnp.isfinite(x), x, 0.0),
                             g)
            gnorm = jnp.sqrt(sum(jnp.sum(x ** 2)
                                 for x in jax.tree_util.tree_leaves(g)))
            step = jax.tree.map(
                lambda gg, sc: cur_lr * sc * gg / (gnorm + 1e-12), g, scales)
        new = clip_alloc(scn, Allocation(*[a - d for a, d in
                                           zip(alloc, step)]))
        if adaptive:
            # backtracking needs Γ at the candidate point — pay the extra
            # forward pass only on this path
            new_val = loss(new)
            improved = new_val < val
            new = jax.tree.map(
                lambda n, o: jnp.where(improved, n, o), new, alloc)
            new_val = jnp.where(improved, new_val, val)
            cur_lr = jnp.where(improved, cur_lr * 1.1, cur_lr * 0.5)
            done = (jnp.abs(new_val - val) < tol * (1.0 + jnp.abs(val))) \
                | (gnorm < tol) | (cur_lr < lr * 1e-3)
            return (new, new_val, k + 1, done, cur_lr)
        # plain GD: value_and_grad already gives Γ(x_k), so the |ΔΓ| stop
        # compares against the previous iterate's value instead of paying a
        # third Γ evaluation per step (one extra lagged iteration at most)
        done = (jnp.abs(val - prev_val) < tol * (1.0 + jnp.abs(val))) \
            | (gnorm < tol)
        return (new, val, k + 1, done, cur_lr)

    init_val = jnp.float32(jnp.inf) if not adaptive else loss(x0)
    carry0 = (x0, init_val, jnp.int32(0), jnp.bool_(False), jnp.float32(lr))

    if gd_chunk:
        def frozen_step(carry, _):
            _, _, k, done, _ = carry
            # freeze converged (or budget-exhausted) lanes: the step still
            # computes (SIMD lanes can't branch) but its result is selected
            # away, so the carry — iterates AND iteration count — is
            # bit-identical to the while_loop reference's
            keep = done | (k >= max_steps)
            new = body(carry)
            return jax.tree.map(
                lambda n, o: jnp.where(keep, o, n), new, carry), None

        def chunk_body(carry):
            carry, _ = jax.lax.scan(frozen_step, carry, None,
                                    length=gd_chunk,
                                    unroll=min(gd_chunk, 4))
            return carry

        alloc, _, iters, _, _ = jax.lax.while_loop(cond, chunk_body, carry0)
    else:
        alloc, _, iters, _, _ = jax.lax.while_loop(cond, body, carry0)
    with jax.named_scope("era.loss"):
        gamma = loss(alloc)
    return GDResult(alloc, gamma, iters)


# per-layer entry point (sequential reference path + ERA+ polish step):
# Scenario/SplitProfile are registered pytrees, Weights is static, so one
# compilation serves every layer's solve.
_gd_solve = partial(jax.jit, static_argnames=("max_steps", "w", "adaptive",
                                              "gd_chunk", "step_impl",
                                              "step_block_m"))(
    _gd_core)


def warm_start_predecessors(uplink_bits, warm_start: bool = True
                            ) -> np.ndarray:
    """Host-side precompute of Table I's nearest-w warm-start rule.

    Returns ``pred`` (F+1,) int32 such that the GD for split point s starts
    from the solved allocation of split ``pred[s]`` — the already-visited
    split whose intermediate data size is nearest ``w_s`` (first index wins
    ties, matching the sequential reference).  The solution buffer is
    initialised with the uninformed start, so ``pred[s] == s`` (slot not yet
    written) means "start cold"; that encodes both s = 0 and the
    ``warm_start=False`` baseline without any branching in the scan body.
    """
    wbits = np.asarray(uplink_bits)
    n = wbits.shape[0]
    pred = np.arange(n, dtype=np.int32)
    if warm_start:
        for s in range(1, n):
            pred[s] = np.argmin(np.abs(wbits[s] - wbits[:s]))
    return pred


def _sweep_core(scn, q, x_init, pred, lr, tol, max_steps, w, prof,
                adaptive=False, gd_chunk=0, step_impl="xla",
                step_block_m=0):
    """The whole F+1 split sweep as one ``lax.scan`` (tentpole path).

    Carry = a stacked Allocation buffer with leading axis F+1, initialised
    to ``x_init`` in every slot; step s reads slot ``pred[s]`` (dynamic
    gather — always an already-written slot or the uninformed start, see
    ``warm_start_predecessors``), runs GD, and writes slot s.  F is static
    (``pred``'s shape), so XLA sees a single fused program with no host
    round-trips between layers.

    ``step_impl='fused'``: the fused step's allocation-independent operand
    pack (SIC permutations, transposed gains — ``era_step.ops.build_aux``)
    is hoisted here, outside the layer scan AND the GD loop, so it is
    assembled once per sweep rather than once per step."""
    n_s = pred.shape[0]                    # F+1 (static)
    u = q.shape[0]
    buf0 = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_s,) + x.shape), x_init)
    step_aux = None
    if step_impl == "fused":
        from repro.kernels.era_step import ops as _era_step_ops
        step_aux = _era_step_ops.build_aux(scn)

    def body(buf, xs):
        s, p_idx = xs
        x0 = jax.tree.map(lambda b: b[p_idx], buf)
        s_vec = jnp.full((u,), s, jnp.int32)
        res = _gd_core(scn, s_vec, q, x0, lr, tol, max_steps, w, prof,
                       adaptive=adaptive, gd_chunk=gd_chunk,
                       step_impl=step_impl, step_block_m=step_block_m,
                       step_aux=step_aux)
        buf = jax.tree.map(lambda b, a: b.at[s].set(a), buf, res.alloc)
        return buf, res

    _, swept = jax.lax.scan(body, buf0,
                            (jnp.arange(n_s, dtype=jnp.int32), pred))
    return swept                           # GDResult stacked along s


_sweep_scan = partial(jax.jit, static_argnames=("max_steps", "w",
                                                "adaptive", "gd_chunk",
                                                "step_impl",
                                                "step_block_m"))(
    _sweep_core)


def _vmapped_sweep(scn_b, q_b, x_init, pred_b, lr, tol, max_steps, w, prof,
                   adaptive=False, gd_chunk=0, step_impl="xla",
                   step_block_m=0, prof_batched=False,
                   x_init_batched=False):
    """Unjitted vmap of the scanned sweep over a leading cell axis — the
    single shared definition of the batched sweep body.  Jitted directly
    as ``_sweep_batch`` (one device) and wrapped in ``shard_map`` by
    ``distributed.solver_mesh`` (each mesh shard vmaps its local lanes) —
    one place to change when the sweep grows a new operand.

    ``scn_b``/``q_b``/``pred_b`` carry the batch axis; ``prof`` is batched
    only when cells serve different split profiles.  ``x_init`` is shared
    by default (uninformed start from shared box bounds) and batched
    (``x_init_batched=True``) when cells warm-start from per-cell previous
    solutions or have heterogeneous configs."""
    return jax.vmap(
        lambda scn, q, x0, pred, prf: _sweep_core(
            scn, q, x0, pred, lr, tol, max_steps, w, prf,
            adaptive=adaptive, gd_chunk=gd_chunk, step_impl=step_impl,
            step_block_m=step_block_m),
        in_axes=(0, 0, 0 if x_init_batched else None, 0,
                 0 if prof_batched else None),
    )(scn_b, q_b, x_init, pred_b, prof)


_sweep_batch = partial(jax.jit, static_argnames=(
    "max_steps", "w", "adaptive", "gd_chunk", "step_impl", "step_block_m",
    "prof_batched", "x_init_batched"))(_vmapped_sweep)


def _per_user_cost(scn, prof, s_vec, alloc, q, w: Weights):
    """User i's summand of Γ (for the ERA+ per-user split pick)."""
    from repro.core import qoe as qoe_mod
    from repro.core.era import delay_terms, energy, lam
    t_dev, t_srv, t_up, t_dn, r_up, r_dn = delay_terms(scn, prof, s_vec, alloc)
    t = t_dev + t_srv + t_up + t_dn
    e = energy(scn, prof, s_vec, alloc, r_up, r_dn)
    r_ind = qoe_mod.indicator(t, q, w.qoe_a)
    c_i = (t - q) * r_ind
    return (w.w_t * t * w.t_scale + w.w_q * (c_i * w.t_scale + r_ind)
            + w.w_r * (e * w.e_scale + lam(alloc.r, scn.env) * w.r_cost_scale))


def stack_allocs(allocs) -> Allocation:
    """Stack per-cell Allocations along a new leading cell axis B — e.g.
    previous-round ``LiGDOutcome.alloc``s into a warm-start initial point
    for the next ``solve_batch(init_alloc=...)``."""
    allocs = list(allocs)
    if not allocs:
        raise ValueError("need at least one allocation")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *allocs)


def warm_start_from(outcomes) -> Allocation:
    """Batched warm-start point from the previous round's outcomes (the
    loop-iteration idea extended across admission rounds: seed round t+1's
    GD from round t's solved allocations)."""
    return stack_allocs([o.alloc for o in outcomes])


def soften_beta(scn, alloc: Allocation, eps: float = 0.1) -> Allocation:
    """Blend a hard one-hot β back into the simplex interior so a previous
    outcome can seed a new GD run (gradients at exact vertices are brittle)."""
    m = scn.cfg.n_subchannels

    def mix(b):
        return (1.0 - eps) * b + eps / m

    return alloc._replace(beta_up=mix(alloc.beta_up),
                          beta_dn=mix(alloc.beta_dn))


def _cost_table(scn, prof, stacked, q, w):
    """(F+1, U) table of each user's Γ summand at every solved split — one
    vmapped dispatch instead of the seed's F+1 eager evaluations."""
    n_s = stacked.p.shape[0]
    u = q.shape[0]
    return jax.vmap(
        lambda s, a: _per_user_cost(
            scn, prof, jnp.full((u,), s, jnp.int32), a, q, w)
    )(jnp.arange(n_s, dtype=jnp.int32), stacked)


_per_user_cost_table = partial(jax.jit,
                               static_argnames=("w",))(_cost_table)


def _discretize(scn, prof, s_user, hard, q, w, f):
    """SIC feasibility fallback + final Γ at the rounded allocation, as one
    compiled call (the seed evaluated both eagerly, op by op)."""
    feasible = noma.sic_feasible(scn, hard.beta_up, hard.p)
    s_final = jnp.where(feasible, s_user, f)
    return s_final, utility(scn, prof, s_final, hard, q, w)


_discretize_eval = partial(jax.jit,
                           static_argnames=("w", "f"))(_discretize)


def _cells_in(prof_batched):
    """in_axes for (scn, per-cell arrays..., prof) vmaps."""
    return 0 if prof_batched else None


@partial(jax.jit, static_argnames=("w", "prof_batched"))
def _cost_table_batch(scn_b, q_b, stacked_b, w, prof, prof_batched=False):
    return jax.vmap(
        lambda scn, q, st, prf: _cost_table(scn, prf, st, q, w),
        in_axes=(0, 0, 0, _cells_in(prof_batched)),
    )(scn_b, q_b, stacked_b, prof)


@partial(jax.jit, static_argnames=("w", "f", "prof_batched"))
def _discretize_eval_batch(scn_b, s_user_b, hard_b, q_b, w, prof, f,
                           prof_batched=False):
    return jax.vmap(
        lambda scn, s, h, q, prf: _discretize(scn, prf, s, h, q, w, f),
        in_axes=(0, 0, 0, 0, _cells_in(prof_batched)),
    )(scn_b, s_user_b, hard_b, q_b, prof)


def _finalize(scn, prof, q, w, stacked, gammas_np, iters_np, *, lr, tol,
              max_steps, adaptive, per_user_split,
              step_impl="xla", step_block_m=0) -> LiGDOutcome:
    """Shared post-sweep discretisation: s* pick (+ optional ERA+ per-user
    split & polish), β rounding, SIC fallback, final Γ evaluation.

    ``stacked``: Allocation pytree with leading axis F+1 (slot s = the GD
    solution for split point s)."""
    u = scn.cfg.n_users
    f = prof.n_layers
    s_star = int(np.argmin(gammas_np))

    def alloc_at(s):
        return jax.tree.map(lambda b: b[s], stacked)

    if per_user_split:
        costs = _per_user_cost_table(scn, prof, stacked, q, w)   # (F+1, U)
        s_user = jnp.argmin(costs, axis=0).astype(jnp.int32)
        # polish the allocation for the mixed split vector
        res = _gd_solve(scn, s_user, q, alloc_at(s_star), lr, tol,
                        max_steps, w, prof, adaptive=adaptive,
                        step_impl=step_impl, step_block_m=step_block_m)
        alloc = res.alloc
    else:
        s_user = jnp.full((u,), s_star, jnp.int32)
        alloc = alloc_at(s_star)

    # discretise + SIC feasibility fallback (device-only s=F)
    hard = round_beta(scn, alloc)
    s_final, terms = _discretize_eval(scn, prof, s_user, hard, q, w, f)

    return LiGDOutcome(
        s=np.asarray(s_final),
        alloc=hard,
        terms=terms,
        gamma_by_layer=gammas_np,
        iters_by_layer=iters_np,
        total_iters=int(np.sum(iters_np)),
    )


def solve(scn, prof, q, w: Weights = Weights(), *, spec: SolverSpec = None,
          lr=_UNSET, tol=_UNSET, max_steps=_UNSET, warm_start=_UNSET,
          per_user_split=_UNSET, init_alloc: Allocation = None,
          adaptive=_UNSET, key=None, compiled_sweep=_UNSET,
          gd_chunk=_UNSET) -> LiGDOutcome:
    """Run Li-GD (``spec.warm_start=True``) or the paper's cold-start GD
    baseline over every candidate split point, as described by ``spec``
    (``SolverSpec``; the default spec is the scanned-sweep reference
    backend).

    Legacy kwargs (``lr``/``tol``/… and the deprecated structural trio
    ``compiled_sweep``/``gd_chunk``) still work and are folded onto the
    equivalent spec — bitwise-identical results, since both routes run the
    same compiled programs.  Mixing ``spec=`` with legacy kwargs raises.

    ``init_alloc`` (beyond paper, "online ERA"): seed layer 1's GD from a
    previous time step's solution instead of the uninformed start — the
    loop-iteration warm-start idea extended across time, for re-scheduling
    under channel drift (network.evolve_scenario)."""
    spec = _resolve_spec(spec, "ligd.solve", lr=lr, tol=tol,
                         max_steps=max_steps, warm_start=warm_start,
                         per_user_split=per_user_split, adaptive=adaptive,
                         compiled_sweep=compiled_sweep, gd_chunk=gd_chunk)
    if spec.backend in ("sharded", "multihost"):
        raise ValueError(f"backend={spec.backend!r} shards a CELL axis — "
                         "use solve_batch (single-cell solve has no cell "
                         "axis)")
    x_init = (soften_beta(scn, init_alloc) if init_alloc is not None
              else uniform_alloc(scn, rng=key))

    if not spec.compiled_sweep:
        return _solve_sequential(scn, prof, q, w, lr=spec.lr, tol=spec.tol,
                                 max_steps=spec.max_steps,
                                 warm_start=spec.warm_start,
                                 per_user_split=spec.per_user_split,
                                 adaptive=spec.adaptive, x_init=x_init,
                                 step_impl=spec.step_impl,
                                 step_block_m=spec.step_block_m)

    pred = warm_start_predecessors(prof.uplink_bits, spec.warm_start)
    swept = _sweep_scan(scn, q, x_init, jnp.asarray(pred), spec.lr, spec.tol,
                        spec.max_steps, w, prof, adaptive=spec.adaptive,
                        gd_chunk=spec.gd_chunk, step_impl=spec.step_impl,
                        step_block_m=spec.step_block_m)
    return _finalize(scn, prof, q, w, swept.alloc,
                     np.asarray(swept.gamma), np.asarray(swept.iters),
                     lr=spec.lr, tol=spec.tol, max_steps=spec.max_steps,
                     adaptive=spec.adaptive,
                     per_user_split=spec.per_user_split,
                     step_impl=spec.step_impl,
                     step_block_m=spec.step_block_m)


def _solve_sequential(scn, prof, q, w, *, lr, tol, max_steps, warm_start,
                      per_user_split, adaptive, x_init,
                      step_impl="xla", step_block_m=0) -> LiGDOutcome:
    """The seed-structured reference the compiled sweep is validated and
    benchmarked against: one jitted GD per layer with a NumPy round-trip in
    between, an eager per-user cost stack for ERA+, and eager
    discretisation.  (The GD step itself is the shared ``_gd_core``, whose
    non-adaptive stop check was restructured in the same PR — so this path
    preserves the seed's dispatch/sync *structure*, not its bit-exact
    iterates.)"""
    u = scn.cfg.n_users
    f = prof.n_layers
    pred = warm_start_predecessors(prof.uplink_bits, warm_start)

    solved_alloc, gammas, iters = [], [], []
    for s in range(f + 1):
        x0 = solved_alloc[pred[s]] if pred[s] < s else x_init
        s_vec = jnp.full((u,), s, jnp.int32)
        res = _gd_solve(scn, s_vec, q, x0, lr, tol, max_steps, w, prof,
                        adaptive=adaptive, step_impl=step_impl,
                        step_block_m=step_block_m)
        solved_alloc.append(res.alloc)
        gammas.append(float(res.gamma))      # host sync per layer
        iters.append(int(res.iters))

    gammas_np = np.asarray(gammas)
    s_star = int(np.argmin(gammas_np))

    if per_user_split:
        costs = np.stack([
            np.asarray(_per_user_cost(scn, prof,
                                      jnp.full((u,), s, jnp.int32),
                                      solved_alloc[s], q, w))
            for s in range(f + 1)
        ])                                   # (F+1, U) — eager, per layer
        s_user = jnp.asarray(np.argmin(costs, axis=0), jnp.int32)
        # polish the allocation for the mixed split vector
        res = _gd_solve(scn, s_user, q, solved_alloc[s_star], lr, tol,
                        max_steps, w, prof, adaptive=adaptive,
                        step_impl=step_impl, step_block_m=step_block_m)
        alloc = res.alloc
    else:
        s_user = jnp.full((u,), s_star, jnp.int32)
        alloc = solved_alloc[s_star]

    # discretise + SIC feasibility fallback (device-only s=F)
    hard = round_beta(scn, alloc)
    feasible = noma.sic_feasible(scn, hard.beta_up, hard.p)
    s_final = jnp.where(feasible, s_user, f)
    terms = utility(scn, prof, s_final, hard, q, w)

    return LiGDOutcome(
        s=np.asarray(s_final),
        alloc=hard,
        terms=terms,
        gamma_by_layer=gammas_np,
        iters_by_layer=np.asarray(iters),
        total_iters=int(np.sum(iters)),
    )


# lane_placement='sorted' history: padded-batch-size -> (B,) per-lane total
# GD iteration counts of the most recent sharded solve at that size.
# Host-side and advisory only — the permutation it induces is inverted on
# every output, so placement never changes WHAT a solve returns, only which
# shard works hardest.  Keyed by lane count so bucketed partial rounds
# (1/2/4/… ladders) never mix histories across batch shapes.
_LANE_ITERS: dict = {}


def reset_lane_history():
    """Drop the lane_placement='sorted' iteration history (call on cell
    churn — lane indices change meaning — or between unrelated tests)."""
    _LANE_ITERS.clear()


def _lane_permutation(n_lanes: int, n_shards: int):
    """Slot->lane permutation for ``lane_placement='sorted'``, or None when
    there is nothing to sort (no history at this size, or a 1-shard mesh).

    Lanes are ranked by the previous same-size round's total iteration
    count and dealt round-robin across the mesh's contiguous shard blocks —
    hardest lane to shard 0, next to shard 1, … — so no shard ends up with
    all the slow cells while others idle at the lockstep barrier.  Returns
    ``perm`` with ``permuted[k] = original[perm[k]]``; callers invert with
    ``np.argsort(perm)``."""
    hist = _LANE_ITERS.get(n_lanes)
    if hist is None or n_shards <= 1 or n_lanes <= 1:
        return None
    order = np.argsort(-np.asarray(hist), kind="stable")
    block = -(-n_lanes // n_shards)              # shard block length (ceil)
    slots = [s * block + t
             for t in range(block) for s in range(n_shards)
             if s * block + t < n_lanes]         # round-robin slot order
    perm = np.empty(n_lanes, dtype=np.int64)
    perm[np.asarray(slots)] = order
    return perm


class BatchPrep(NamedTuple):
    """Round-invariant inputs of ``solve_batch`` (stacked scenarios,
    stacked/per-cell profiles, warm-start predecessor matrix).  Build once
    via ``prepare_batch`` when solving the same cells every admission round
    (MultiCellScheduler does) instead of re-deriving them per call."""
    scn_b: object                 # batched Scenario (leading cell axis)
    scn_list: tuple               # per-cell Scenarios
    prof_b: object                # shared or stacked SplitProfile
    prof_list: tuple              # per-cell SplitProfiles
    prof_batched: bool
    pred_b: np.ndarray            # (B, F+1) warm-start predecessors
    hetero: bool = False          # cells carry different numeric params


def prepare_batch(scns, prof, warm_start: bool = True) -> BatchPrep:
    """Precompute everything about (cells, profiles) that does not change
    between solves.  ``scns``: list of Scenarios or an already-stacked
    batched Scenario; ``prof``: shared profile or per-cell list."""
    if isinstance(scns, (list, tuple)):
        scn_list = tuple(scns)
        scn_b = network.stack_scenarios(scn_list)
    else:
        scn_b = scns
        scn_list = tuple(jax.tree.map(lambda x, b=b: x[b], scn_b)
                         for b in range(scn_b.assoc.shape[0]))
    n_cells = len(scn_list)

    if isinstance(prof, (list, tuple)):
        prof_list = tuple(prof)
        if len(prof_list) != n_cells:
            raise ValueError("need one profile per cell")
        prof_b = profiles.stack_profiles(prof_list)
        prof_batched = True
    else:
        prof_list = (prof,) * n_cells
        prof_b = prof
        prof_batched = False

    pred_b = np.stack([warm_start_predecessors(p.uplink_bits, warm_start)
                       for p in prof_list])
    # env-leaf comparison, not cfg equality: a pre-stacked batched Scenario
    # slices back with the representative cfg on every cell, but the env
    # leaves always keep each cell's true numbers
    hetero = network.envs_differ(scn_list)
    return BatchPrep(scn_b, scn_list, prof_b, prof_list, prof_batched,
                     pred_b, hetero)


def solve_batch(scns, prof, q, w: Weights = Weights(), *,
                spec: SolverSpec = None, lr=_UNSET, tol=_UNSET,
                max_steps=_UNSET, warm_start=_UNSET, per_user_split=_UNSET,
                adaptive=_UNSET, prep: BatchPrep = None,
                init_alloc: Allocation = None, gd_chunk=_UNSET,
                mesh=_UNSET, compiled_sweep=_UNSET) -> List[LiGDOutcome]:
    """Schedule B independent cells with ONE compiled, vmapped sweep, as
    described by ``spec`` (``SolverSpec``):

      backend='reference'  one device, vmapped while_loop GD;
      backend='chunked'    one device, lockstep-free chunked GD;
      backend='sharded'    the sweep under ``shard_map`` over
                           ``spec.run_mesh()``'s ``cells`` axis — one SPMD
                           program, no cross-lane collectives until the
                           final output gather; lanes are padded
                           (repeat-last) to a multiple of the mesh size
                           and padding outcomes dropped.
      backend='multihost'  the same sharded sweep over the GLOBAL
                           ``jax.distributed`` device mesh.  ``scns``/
                           ``q``/``init_alloc`` are THIS process's lanes;
                           every process must call with the same local
                           lane count and the same statics at the same
                           point (one SPMD program spans all hosts), and
                           each gets back outcomes for its own lanes
                           only.  Lane padding is per host, the compiled
                           program moves ~0 bytes across hosts, and
                           single-process the path is bitwise
                           ``backend='sharded'`` (``distributed.
                           multihost`` module docs).

    Legacy kwargs (``gd_chunk=``/``mesh=``/``compiled_sweep=`` plus the
    numeric knobs) still work through a deprecation shim that folds them
    onto the equivalent spec — bitwise-identical results, same compiled
    programs.  Mixing ``spec=`` with legacy kwargs raises.

    Arguments:
      scns: a list/tuple of ``Scenario``s with structurally compatible
        NetworkConfigs (numeric fields may differ per cell — they travel
        via the ``CellEnv`` leaf), or an already-stacked batched Scenario
        (``network.stack_scenarios``).
      prof: one shared ``SplitProfile``, or a list of per-cell profiles
        with equal layer counts (``profiles.stack_profiles`` semantics —
        e.g. the same architecture profiled at different request lengths).
      q: (B, U) per-cell QoE thresholds.

    The GD sweep for all B cells runs in a single compiled call; only the
    cheap discretisation (β rounding, SIC fallback) happens per-cell on
    the host.  Returns one ``LiGDOutcome`` per cell.

    ``prep``: pass a ``prepare_batch`` result to skip re-deriving the
    round-invariant stacked inputs on every call (``scns``/``prof``/
    ``spec.warm_start`` are then ignored in its favour).

    ``init_alloc`` (warm-start entry point, online ERA across rounds): a
    batched Allocation with leading axis B — typically
    ``warm_start_from(previous_outcomes)`` — or a list of per-cell
    Allocations.  Hard one-hot β rows are softened back into the simplex
    interior (``soften_beta``) before seeding layer 0's GD, exactly as the
    single-cell ``solve(init_alloc=...)`` path does.
    """
    # warm: spec, prep and the start point; sweep: from the dispatch to
    # the Γ landscape on the host; finalize: the rest (telemetry.span)
    with spans.span("era.solve.warm"):
        spec = _resolve_spec(spec, "ligd.solve_batch", lr=lr, tol=tol,
                             max_steps=max_steps, warm_start=warm_start,
                             per_user_split=per_user_split, adaptive=adaptive,
                             gd_chunk=gd_chunk, mesh=mesh,
                             compiled_sweep=compiled_sweep)
        if not spec.compiled_sweep:
            raise ValueError(
                "compiled_sweep=False is the per-layer sequential reference "
                "loop, a single-cell path — use ligd.solve; solve_batch "
                "always runs the scanned sweep")
        if prep is None:
            prep = prepare_batch(scns, prof, spec.warm_start)
        scn_b, scn_list = prep.scn_b, prep.scn_list
        prof_b, prof_list = prep.prof_b, prep.prof_list
        prof_batched, pred_b = prep.prof_batched, prep.pred_b
        n_cells = len(scn_list)
        q = jnp.asarray(q)
        if q.ndim != 2 or q.shape[0] != n_cells:
            raise ValueError(f"q must be (B, U) with B={n_cells}, "
                             f"got {q.shape}")

        hetero = prep.hetero
        if init_alloc is not None:
            if not isinstance(init_alloc, Allocation) \
                    and isinstance(init_alloc, (list, tuple)):
                init_alloc = stack_allocs(init_alloc)
            if init_alloc.p.shape[0] != n_cells:
                raise ValueError(f"init_alloc must carry a leading "
                                 f"B={n_cells} axis, got "
                                 f"{init_alloc.p.shape}")
            # soften_beta only needs n_subchannels (structural) —
            # batched-safe
            x_init = soften_beta(scn_list[0], init_alloc)
            x_init_batched = True
        elif hetero:
            # per-cell box bounds => per-cell uninformed starts
            x_init = stack_allocs([uniform_alloc(s) for s in scn_list])
            x_init_batched = True
        else:
            x_init = uniform_alloc(scn_list[0])    # identical across cells
            x_init_batched = False
        f = prof_list[0].n_layers
        u = q.shape[1]

        run_mesh = spec.run_mesh()
    with spans.span("era.solve.sweep"):
        if spec.backend == "multihost":
            from repro.distributed import multihost
            # host-local lanes in, host-local lanes out: the finalize tail
            # below sees exactly this process's B lanes either way, so it is
            # shared verbatim with the single-process backends.  No
            # _LANE_ITERS recording — lane_placement='sorted' is rejected
            # for multihost (cross-host history would defeat the point).
            swept = multihost.multihost_sweep(
                run_mesh, scn_b, q, x_init, jnp.asarray(pred_b),
                spec.lr, spec.tol, spec.max_steps, w, prof_b,
                adaptive=spec.adaptive, gd_chunk=spec.gd_chunk,
                step_impl=spec.step_impl, step_block_m=spec.step_block_m,
                prof_batched=prof_batched, x_init_batched=x_init_batched)
        elif run_mesh is not None:
            from repro.distributed import solver_mesh
            lane_perm = None
            if spec.lane_placement == "sorted":
                lane_perm = _lane_permutation(n_cells, run_mesh.devices.size)
            if lane_perm is not None:
                perm_ix = jnp.asarray(lane_perm)
                scn_sw = network.take_cells(scn_b, perm_ix)
                q_sw = jnp.take(q, perm_ix, axis=0)
                pred_sw = pred_b[lane_perm]
                x_init_sw = (network.take_cells(x_init, perm_ix)
                             if x_init_batched else x_init)
                prof_sw = (network.take_cells(prof_b, perm_ix)
                           if prof_batched else prof_b)
            else:
                scn_sw, q_sw, pred_sw = scn_b, q, pred_b
                x_init_sw, prof_sw = x_init, prof_b
            swept = solver_mesh.sharded_sweep(
                run_mesh, scn_sw, q_sw, x_init_sw, jnp.asarray(pred_sw),
                spec.lr, spec.tol, spec.max_steps, w, prof_sw,
                adaptive=spec.adaptive, gd_chunk=spec.gd_chunk,
                step_impl=spec.step_impl, step_block_m=spec.step_block_m,
                prof_batched=prof_batched, x_init_batched=x_init_batched)
            if lane_perm is not None:
                # per-lane GD is frozen-by-select under vmap, so a lane's
                # result is independent of its co-resident lanes — inverting
                # the permutation restores the 'none' ordering's outputs
                # exactly (tests/test_sharded_solver.py asserts equality)
                inv_ix = jnp.asarray(np.argsort(lane_perm))
                swept = network.take_cells(swept, inv_ix)
            # record this round's per-lane effort for the next same-size round
            _LANE_ITERS[n_cells] = np.asarray(swept.iters).sum(axis=1)
        else:
            swept = _sweep_batch(scn_b, q, x_init, jnp.asarray(pred_b),
                                 spec.lr, spec.tol, spec.max_steps, w, prof_b,
                                 adaptive=spec.adaptive,
                                 gd_chunk=spec.gd_chunk,
                                 step_impl=spec.step_impl,
                                 step_block_m=spec.step_block_m,
                                 prof_batched=prof_batched,
                                 x_init_batched=x_init_batched)
        gammas = np.asarray(swept.gamma)                       # (B, F+1)

    # ---- batched finalize: every compiled stage is ONE dispatch for all
    # cells; only the greedy β rounding runs per cell (host-side) ----------
    with spans.span("era.solve.finalize"):
        iters = np.asarray(swept.iters)
        s_star = jnp.asarray(np.argmin(gammas, axis=1), jnp.int32)   # (B,)
        # select layer s* per cell by a one-hot sum, not a gather: under a
        # mesh with Explicit axes the gather's output sharding is ambiguous
        # and raises, while select-and-sum keeps x's cells sharding (and is
        # exact: every other term is 0.0)
        pick = jax.nn.one_hot(s_star, gammas.shape[1], dtype=bool)   # (B, F+1)

        def at_star(x):
            mask = pick.reshape(pick.shape + (1,) * (x.ndim - 2))
            return jnp.sum(jnp.where(mask, x, 0), axis=1)

        if spec.per_user_split:
            costs = _cost_table_batch(scn_b, q, swept.alloc, w, prof_b,
                                      prof_batched=prof_batched)  # (B, F+1, U)
            s_user = jnp.argmin(costs, axis=1).astype(jnp.int32)  # (B, U)
            # polish per cell: polish iteration counts vary wildly across
            # cells, so a vmapped (lockstep) polish would run every lane to the
            # slowest cell's count — B small dispatches are cheaper here
            x_star = jax.tree.map(at_star, swept.alloc)
            polished = [
                _gd_solve(scn_list[b], s_user[b], q[b],
                          jax.tree.map(lambda x, b=b: x[b], x_star),
                          spec.lr, spec.tol, spec.max_steps, w, prof_list[b],
                          adaptive=spec.adaptive, step_impl=spec.step_impl,
                          step_block_m=spec.step_block_m)
                for b in range(n_cells)
            ]
            alloc_b = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[p.alloc for p in polished])
        else:
            s_user = jnp.broadcast_to(s_star[:, None], (n_cells, u))
            alloc_b = jax.tree.map(at_star, swept.alloc)

        # discretise per cell (host greedy), then one batched SIC+Γ evaluation
        hard_list = [round_beta(scn_list[b],
                                jax.tree.map(lambda x, b=b: x[b], alloc_b))
                     for b in range(n_cells)]
        hard_b = jax.tree.map(lambda *xs: jnp.stack(xs), *hard_list)
        s_final_b, terms_b = _discretize_eval_batch(
            scn_b, s_user, hard_b, q, w, prof_b, f, prof_batched=prof_batched)

        s_final_np = np.asarray(s_final_b)
        terms_np = jax.tree.map(np.asarray, terms_b)
        return [
            LiGDOutcome(
                s=s_final_np[b],
                alloc=hard_list[b],
                terms=Terms(*(leaf[b] for leaf in terms_np)),
                gamma_by_layer=gammas[b],
                iters_by_layer=iters[b],
                total_iters=int(iters[b].sum()),
            )
            for b in range(n_cells)
        ]
