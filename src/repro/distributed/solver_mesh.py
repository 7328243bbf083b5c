"""SPMD cell-sharded Li-GD solves: one admission round = one sharded
program across pods (ROADMAP north star).

``solve_batch`` vmaps the F+1 split sweep over a leading cell axis; this
module shards that axis over a 1-D device mesh (axis name ``cells``) with
``shard_map``, so B cells split across the available devices as ONE
compiled SPMD program.  The sweep body is collective-free by construction
— every reduction in noma.py/era.py is over per-cell user/channel axes
(see their batch-safety audits), so shards never communicate until the
final output gather that ``out_specs=P('cells')`` implies.

Two consequences worth naming:
  * throughput: B cells' GD sweeps run concurrently, one program launch,
    device count × lanes-per-device parallelism;
  * lockstep relief: each device's (chunked or while) GD loop exits when
    ITS lanes converge — a slow-converging cell only holds back the
    shard it lives on, not the whole fleet (``ligd._gd_core`` docs).

Mesh style follows launch/mesh.py: functions, not module constants —
importing this module never touches jax device state.  Multi-device CPU
runs (tests/benchmarks) force device count via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
initialises (Makefile ``test-solver`` does).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import ligd, network
from repro.launch.mesh import _make_mesh

CELL_AXIS = "cells"


_MESH_CACHE = {}


def cells_mesh(n_devices: int = None):
    """1-D mesh over the solver's cell axis — THIS process's devices
    (``distributed.multihost.global_cells_mesh`` is the all-process
    variant).  ``n_devices=None`` uses every visible device; a smaller
    request uses a prefix of them.  Memoised per device count, so
    ``SolverSpec.run_mesh()``'s lazy all-devices default resolves to the
    identical Mesh object on every call and the sharded sweep's jit cache
    never splinters.  Built with Auto axes (``launch.mesh._make_mesh``)."""
    n_avail = len(jax.devices())
    n = n_avail if n_devices is None else max(1, min(n_devices, n_avail))
    mesh = _MESH_CACHE.get(n)
    if mesh is None:
        mesh = _MESH_CACHE[n] = _make_mesh((n,), (CELL_AXIS,))
    return mesh


def pad_lanes(n_lanes: int, n_shards: int):
    """Gather indices that pad a B-lane batch up to a multiple of the shard
    count by repeating the last lane (None when no padding is needed).
    Padding lanes re-solve a real cell and are dropped from the output —
    solutions stay exact; only the padded tail is wasted work."""
    rem = n_lanes % n_shards
    if rem == 0:
        return None
    import numpy as np
    pad = n_shards - rem
    return np.concatenate([np.arange(n_lanes), np.full(pad, n_lanes - 1)])


_SWEEP_CACHE = {}


def _sharded_sweep_fn(mesh, max_steps, w, adaptive, gd_chunk, step_impl,
                      step_block_m, prof_batched, x_init_batched):
    """Build (and cache) the jitted shard_map'd sweep for one static
    configuration.  The cache key is exactly the static argument set —
    the same split the unsharded ``_sweep_batch`` jits over, plus the
    mesh (device set + axis name).  ``step_impl='fused'`` keeps the body
    collective-free: the fused step (kernels/era_step) is pure per-cell
    jnp/Pallas with no cross-lane reductions, so it drops inside the
    shard_map exactly like the autodiff body."""
    key = (mesh, max_steps, w, adaptive, gd_chunk, step_impl, step_block_m,
           prof_batched, x_init_batched)
    fn = _SWEEP_CACHE.get(key)
    if fn is not None:
        return fn

    cells = P(CELL_AXIS)
    repl = P()

    def local_sweep(scn_b, q_b, x_init, pred_b, lr, tol, prof):
        # one shard's lanes: the SAME vmapped sweep body _sweep_batch
        # jits, applied to the local slice — the sharded path can never
        # diverge from the single-device reference, and carries the same
        # named scopes (era.beta_t, era.grad_norm, era.clip_alloc,
        # era.loss) on its ops
        return ligd._vmapped_sweep(
            scn_b, q_b, x_init, pred_b, lr, tol, max_steps, w, prof,
            adaptive=adaptive, gd_chunk=gd_chunk, step_impl=step_impl,
            step_block_m=step_block_m, prof_batched=prof_batched,
            x_init_batched=x_init_batched)

    # check_vma=False: the GD loops seed their carries with unsharded
    # constants (Γ=inf, done=False) that the body makes cell-varying; every
    # output is cell-sharded anyway, so tracking replication buys nothing
    sharded = jax.shard_map(
        local_sweep, mesh=mesh,
        in_specs=(cells, cells, cells if x_init_batched else repl, cells,
                  repl, repl, cells if prof_batched else repl),
        out_specs=cells, check_vma=False)
    fn = jax.jit(sharded)
    _SWEEP_CACHE[key] = fn
    return fn


def sharded_sweep(mesh, scn_b, q_b, x_init, pred_b, lr, tol, max_steps, w,
                  prof, *, adaptive=False, gd_chunk=0, step_impl="xla",
                  step_block_m=0, prof_batched=False, x_init_batched=False):
    """Drop-in replacement for ``ligd._sweep_batch`` that runs the vmapped
    sweep under ``shard_map`` over ``mesh``'s ``cells`` axis.  Pads the
    lane count to a multiple of the shard count (repeat-last, exact per
    lane) and slices the padding back off the stacked ``GDResult``."""
    n_lanes = int(q_b.shape[0])
    n_shards = mesh.shape[CELL_AXIS]
    idx = pad_lanes(n_lanes, n_shards)
    if idx is not None:
        take = partial(network.take_cells, idx=idx)
        scn_b, q_b, pred_b = take(scn_b), take(q_b), take(pred_b)
        if x_init_batched:
            x_init = take(x_init)
        if prof_batched:
            prof = take(prof)

    fn = _sharded_sweep_fn(mesh, max_steps, w, adaptive, gd_chunk,
                           step_impl, step_block_m, prof_batched,
                           x_init_batched)
    swept = fn(scn_b, q_b, x_init, pred_b, jnp.float32(lr),
               jnp.float32(tol), prof)
    if idx is not None:
        swept = jax.tree.map(lambda x: x[:n_lanes], swept)
    return swept


def solve_batch_sharded(scns, prof, q, *args, mesh=None, spec=None, **kw):
    """``ligd.solve_batch`` on a cells mesh (built over every visible
    device when ``mesh`` is None).  The sharded backend's convenience
    entry: with ``spec=`` the spec is re-pinned to ``backend='sharded'``
    on this mesh; otherwise legacy kwargs flow through ``solve_batch``'s
    deprecation shim.  The ``SolverSpec.backend`` seam is the fleet-scale
    extension point — ``backend='multihost'`` (distributed/multihost.py)
    runs this same sweep over a ``jax.distributed`` global mesh without
    touching the serving layer."""
    mesh = cells_mesh() if mesh is None else mesh
    if spec is not None:
        spec = spec.replace(backend="sharded", mesh=mesh)
        return ligd.solve_batch(scns, prof, q, *args, spec=spec, **kw)
    return ligd.solve_batch(scns, prof, q, *args, mesh=mesh, **kw)
