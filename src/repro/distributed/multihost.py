"""Multi-host solver mesh: ``SolverSpec(backend='multihost')``.

``solver_mesh`` shards the cells axis over ONE process's devices; fleet
scale (ROADMAP north star) wants it over a ``jax.distributed`` device set
— N hosts × M devices sweeping N·M shards of cells as one SPMD program.
This module is that backend.  The key property carries over unchanged:
the sweep body is collective-free by construction (every reduction in
noma.py/era.py is over per-cell axes), and with ``out_specs=P('cells')``
each host materialises ONLY its own lanes' results — the compiled
program moves ~0 bytes across hosts (``sweep_collective_cost`` audits
the optimized HLO via ``launch/hlo_cost``; asserted in
tests/test_multihost_solver.py and recorded in BENCH_multihost.json).

SPMD contract (what every caller must uphold):
  * every process calls ``ligd.solve_batch(backend='multihost')`` with
    ITS OWN lanes — the same local cell count, the same static config
    (max_steps / gd_chunk / step_impl / profile layer count / padded B)
    on every process, at the same point in its execution;
  * process p's lanes occupy the contiguous global slice
    ``[p·B_pad, (p+1)·B_pad)`` (``jax.devices()`` orders devices grouped
    by process, so a 1-D mesh over them is host-contiguous — runtime-
    asserted in ``_localize``);
  * lane padding is PER HOST: each process pads its local batch to a
    multiple of its local shard count by repeating its own last lane
    (``solver_mesh.pad_lanes``), so every host's slice is self-contained
    and no host ever needs another host's scenario data;
  * outputs come back as the local ``B`` lanes only (padding trimmed) —
    ``solve_batch`` returns exactly as many ``LiGDOutcome``s as the
    local lanes passed in, same as every other backend.

Single-process degeneration: with one process the global mesh IS
``solver_mesh.cells_mesh()`` (same memoised Mesh object, same jit cache)
and ``multihost_sweep`` delegates to ``sharded_sweep`` — so
``backend='multihost'`` on a laptop is bitwise ``backend='sharded'``.

Process bring-up (``initialize_from_env``): the emulation recipe on the
pinned CPU toolchain is N worker subprocesses, each with
``XLA_FLAGS=--xla_force_host_platform_device_count=M`` and::

    REPRO_MH_COORDINATOR=localhost:<port>   # process 0 hosts it
    REPRO_MH_NUM_PROCESSES=N
    REPRO_MH_PROCESS_ID=<0..N-1>

CPU multi-process collectives need the gloo backend
(``jax_cpu_collectives_implementation``) configured BEFORE
``jax.distributed.initialize`` — without it the runtime refuses
multiprocess computations outright; ``initialize_from_env`` handles the
ordering.  The solve itself compiles to zero collectives; gloo is only
exercised by the named barrier ``churn_fence`` (coordinated cell
join/leave — ``serving/cluster.py``) and distributed-runtime bring-up.

Mesh style follows launch/mesh.py: functions, not module constants —
importing this module never touches jax device state.
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import network
from repro.distributed import solver_mesh
from repro.launch.mesh import _make_mesh

CELL_AXIS = solver_mesh.CELL_AXIS

ENV_COORDINATOR = "REPRO_MH_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_MH_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_MH_PROCESS_ID"

_INITIALIZED = False


class HostInfo(NamedTuple):
    process_id: int
    n_processes: int
    n_local_devices: int
    n_global_devices: int


def host_info() -> HostInfo:
    return HostInfo(jax.process_index(), jax.process_count(),
                    len(jax.local_devices()), len(jax.devices()))


def initialize_from_env() -> HostInfo:
    """Join (or host) the distributed runtime described by the
    ``REPRO_MH_*`` env vars; a no-op single-process ``HostInfo`` when the
    coordinator var is unset.  Idempotent.  Must run before anything
    touches jax device state (platform presets excepted — they only set
    env vars)."""
    global _INITIALIZED
    coord = os.environ.get(ENV_COORDINATOR)
    if coord is None or _INITIALIZED:
        return host_info()
    n_procs = int(os.environ[ENV_NUM_PROCESSES])
    pid = int(os.environ[ENV_PROCESS_ID])
    if not 0 <= pid < n_procs:
        raise ValueError(f"{ENV_PROCESS_ID}={pid} outside "
                         f"[0, {ENV_NUM_PROCESSES}={n_procs})")
    if n_procs > 1:
        # gloo must be selected before the CPU client exists; on other
        # platforms the option is inert (it only steers CPU collectives)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=n_procs, process_id=pid)
    _INITIALIZED = True
    return host_info()


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def lane_slice(n_local: int):
    """Global lane interval ``[lo, hi)`` this process's ``n_local`` cells
    occupy, given the SPMD contract that every process holds ``n_local``
    lanes — the contiguous per-host CellId slice the admission layer
    shards over."""
    pid = jax.process_index()
    return pid * n_local, (pid + 1) * n_local


_MESH_CACHE = {}


def global_cells_mesh(n_devices: int = None):
    """1-D ``cells`` mesh over the GLOBAL (all-process) device set.

    Single-process this IS ``solver_mesh.cells_mesh`` — the identical
    memoised Mesh object, so the sharded and multihost jit caches unify.
    Multi-process it spans every process's devices (``jax.devices()``
    orders them grouped by process, giving each host a contiguous lane
    slice); a partial ``n_devices`` is rejected there, because a prefix
    mesh would leave some processes with no addressable shard of the
    SPMD program.  Memoised like ``cells_mesh``, built with Auto axes
    (``launch.mesh._make_mesh``)."""
    if jax.process_count() == 1:
        return solver_mesh.cells_mesh(n_devices)
    n_avail = len(jax.devices())
    if n_devices is not None and n_devices != n_avail:
        raise ValueError(
            f"multihost mesh must span all {n_avail} global devices "
            f"(every process needs addressable shards), got "
            f"n_devices={n_devices}")
    mesh = _MESH_CACHE.get(n_avail)
    if mesh is None:
        mesh = _MESH_CACHE[n_avail] = _make_mesh((n_avail,), (CELL_AXIS,))
    return mesh


def churn_fence(tag: str) -> None:
    """Named cross-process barrier for coordinated SPMD moments (cell
    join/leave, bootstrap ordering).  Every process must reach the fence
    with the SAME tag — a divergent churn sequence fails loudly in the
    barrier instead of deadlocking a later global solve.  No-op
    single-process."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(tag)


def _global_args(mesh, scn_b, q_b, x_init, pred_b, lr, tol, prof, *,
                 prof_batched, x_init_batched):
    """Per-host pad + lift this process's local inputs into global
    ``jax.Array``s on ``mesh``.

    Cell-sharded inputs use ``make_array_from_callback`` with
    ``P('cells')``: the callback is only invoked for ADDRESSABLE device
    indices, so each host supplies exactly its own slice (shifted by
    ``lo``) and no host ever materialises another host's lanes.
    Replicated inputs (shared x_init/profile, the lr/tol scalars) lift
    the same local value everywhere — the SPMD contract makes them equal
    across processes by construction.

    Returns ``(sweep_args, n_local, b_pad, lo)`` with ``sweep_args``
    ordered exactly as ``solver_mesh._sharded_sweep_fn`` expects."""
    n_local = int(q_b.shape[0])
    n_procs = jax.process_count()
    n_shards = mesh.shape[CELL_AXIS]
    if n_shards % n_procs:
        raise ValueError(f"{n_shards}-shard mesh not divisible by "
                         f"{n_procs} processes")
    per_host = n_shards // n_procs
    idx = solver_mesh.pad_lanes(n_local, per_host)
    if idx is not None:
        take = partial(network.take_cells, idx=idx)
        scn_b, q_b, pred_b = take(scn_b), take(q_b), take(pred_b)
        if x_init_batched:
            x_init = take(x_init)
        if prof_batched:
            prof = take(prof)
    b_pad = n_local if idx is None else len(idx)
    lo = jax.process_index() * b_pad

    cells_sh = NamedSharding(mesh, P(CELL_AXIS))
    repl_sh = NamedSharding(mesh, P())

    def lift_cells(x):
        x = np.asarray(x)
        gshape = (n_procs * b_pad,) + x.shape[1:]

        def cb(gidx, x=x):
            s0 = gidx[0]
            return x[(slice(s0.start - lo, s0.stop - lo),)
                     + tuple(gidx[1:])]

        return jax.make_array_from_callback(gshape, cells_sh, cb)

    def lift_repl(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, repl_sh, lambda gidx, x=x: x[gidx])

    args = (
        jax.tree.map(lift_cells, scn_b),
        lift_cells(q_b),
        jax.tree.map(lift_cells if x_init_batched else lift_repl, x_init),
        lift_cells(pred_b),
        lift_repl(np.float32(lr)),
        lift_repl(np.float32(tol)),
        jax.tree.map(lift_cells if prof_batched else lift_repl, prof),
    )
    return args, n_local, b_pad, lo


def _localize(leaf, lo, b_pad, n_local):
    """This host's lanes of a cell-sharded global output: concatenate the
    addressable shards in lane order, runtime-assert they cover exactly
    the expected contiguous slice ``[lo, lo+b_pad)`` (the device-order
    assumption the whole host-local contract rests on), trim the per-host
    padding."""
    shards = sorted(leaf.addressable_shards,
                    key=lambda s: int(s.index[0].start or 0))
    start = int(shards[0].index[0].start or 0)
    stop = shards[-1].index[0].stop
    stop = int(leaf.shape[0] if stop is None else stop)
    out = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
    if start != lo or stop != lo + b_pad or out.shape[0] != b_pad:
        raise RuntimeError(
            f"process {jax.process_index()}'s output shards cover lanes "
            f"[{start}, {stop}) ({out.shape[0]} rows), expected the "
            f"contiguous per-host slice [{lo}, {lo + b_pad}) — global "
            f"device order is not grouped by process")
    return jnp.asarray(out[:n_local])


def multihost_sweep(mesh, scn_b, q_b, x_init, pred_b, lr, tol, max_steps,
                    w, prof, *, adaptive=False, gd_chunk=0, step_impl="xla",
                    step_block_m=0, prof_batched=False,
                    x_init_batched=False):
    """``solver_mesh.sharded_sweep`` over a GLOBAL device mesh, with
    host-local inputs and host-local outputs.

    Takes THIS process's lanes (leading axis = local B), runs the one
    global SPMD sweep — the exact jitted shard_map program the sharded
    backend caches in ``_sharded_sweep_fn``, so per-lane numerics are
    bitwise the sharded backend's — and returns a ``GDResult`` holding
    only the local lanes (padding trimmed).  Single-process: delegates
    to ``sharded_sweep`` outright."""
    if jax.process_count() == 1:
        return solver_mesh.sharded_sweep(
            mesh, scn_b, q_b, x_init, pred_b, lr, tol, max_steps, w, prof,
            adaptive=adaptive, gd_chunk=gd_chunk, step_impl=step_impl,
            step_block_m=step_block_m, prof_batched=prof_batched,
            x_init_batched=x_init_batched)
    args, n_local, b_pad, lo = _global_args(
        mesh, scn_b, q_b, x_init, pred_b, lr, tol, prof,
        prof_batched=prof_batched, x_init_batched=x_init_batched)
    fn = solver_mesh._sharded_sweep_fn(mesh, max_steps, w, adaptive,
                                       gd_chunk, step_impl, step_block_m,
                                       prof_batched, x_init_batched)
    swept = fn(*args)
    return jax.tree.map(lambda x: _localize(x, lo, b_pad, n_local), swept)


def sweep_collective_cost(mesh, scn_b, q_b, x_init, pred_b, lr, tol,
                          max_steps, w, prof, *, adaptive=False, gd_chunk=0,
                          step_impl="xla", step_block_m=0,
                          prof_batched=False, x_init_batched=False):
    """The cross-host byte audit: ``hlo_cost.analyze`` over the optimized
    HLO of the compiled multihost sweep.  ``Cost.total_coll_bytes`` is
    the bytes the program moves through collectives — the sweep body is
    collective-free and outputs stay on ``P('cells')``, so this must be
    ~0 (the host-local materialisation in ``_localize`` copies only
    already-local shards).  Every process must call it together in the
    multi-process case (it lowers the same SPMD program everywhere)."""
    from repro.launch import hlo_cost
    if jax.process_count() == 1:
        n_shards = mesh.shape[CELL_AXIS]
        idx = solver_mesh.pad_lanes(int(q_b.shape[0]), n_shards)
        if idx is not None:
            take = partial(network.take_cells, idx=idx)
            scn_b, q_b, pred_b = take(scn_b), take(q_b), take(pred_b)
            if x_init_batched:
                x_init = take(x_init)
            if prof_batched:
                prof = take(prof)
        args = (scn_b, q_b, x_init, pred_b, jnp.float32(lr),
                jnp.float32(tol), prof)
    else:
        args, _, _, _ = _global_args(
            mesh, scn_b, q_b, x_init, pred_b, lr, tol, prof,
            prof_batched=prof_batched, x_init_batched=x_init_batched)
    fn = solver_mesh._sharded_sweep_fn(mesh, max_steps, w, adaptive,
                                       gd_chunk, step_impl, step_block_m,
                                       prof_batched, x_init_batched)
    return hlo_cost.analyze(fn.lower(*args).compile().as_text())
