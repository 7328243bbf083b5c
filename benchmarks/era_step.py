"""Fused ERA GD-step kernel vs the XLA autodiff step (kernels/era_step).

Three claim families, landing in BENCH_era_step.json:

  1. per-step latency: one jitted evaluation of (Γ, ∂Γ/∂Allocation) — the
     autodiff body ``jax.value_and_grad(utility(...).gamma)`` against the
     fused pipeline ``era_step_value_and_grad`` — across problem sizes;
  2. roofline position of that step before/after fusion: FLOPs and the
     HBM-write proxy from the trip-count-aware HLO parser
     (launch/hlo_cost.cost_of_callable), placed against the running
     device's peaks (launch/roofline.step_roofline) and skipped on a device
     ``launch/platform.PEAKS`` has none for.  The fused step's claim is
     fewer materialised intermediates — write_bytes is the number to watch;
  3. full-solve latency across the 1/2/4/8 cell bucket ladder under the
     sharded backend, ``step_impl='xla'`` vs ``'fused'``, plus the final-Γ
     relative agreement between the two paths (the regression bound
     tests/test_era_step.py pins at rtol=1e-5);
  4. the paper-scale record (U=1250, M=250, N=5): the channel-tiled fused
     step's latency and roofline position vs the XLA autodiff step's
     write-bytes proxy.  The XLA step is costed (compile + HLO analysis)
     but NOT executed — its O(M·U²) SIC masks alone are ~1.5 TB, which is
     exactly the latent OOM the tiled grid removes.  The tile columns
     (``roofline.tiled_step_roofline``) land the chosen TPU block size and
     its per-block VMEM footprint against the budget.

Platform comparability: benchmarks/run.py embeds
``launch.platform.describe()`` (effective XLA_FLAGS, preset, device count)
in this file's config block — numbers from different ambient environments
are visibly different runs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, emit_skip
from repro.core import era, ligd, network, profiles
from repro.core.era import Weights
from repro.kernels.era_step import ops as eops
from repro.kernels.era_step.kernel import (DEFAULT_VMEM_BUDGET,
                                           block_vmem_bytes, choose_block_m)
from repro.launch.hlo_cost import cost_of_callable
from repro.launch.platform import roofline_peaks
from repro.launch.roofline import step_roofline, tiled_step_roofline

PER_STEP_SIZES = [(8, 4), (16, 8), (32, 8), (64, 16)]  # (users, subchannels)
BUCKETS = (1, 2, 4, 8)
GD_CHUNK = 8
PAPER_U, PAPER_M = 1250, 250
# CPU lane of the paper-scale record: a small block would unroll into a
# many-block XLA loop here — use a divisor that keeps per-block host
# buffers small (~bm·U²·4 B ≈ 312 MB of masks) without exploding compile
# time
PAPER_BLOCK_M_CPU = 50
NO_PEAKS = "no published peaks for this device kind (launch/platform.PEAKS)"


def _device_peaks():
    """Peaks of the running device, or None where ``PEAKS`` publishes none
    (a CPU): the roofline rows are then skipped, since HLO counts of the
    interpret-mode kernel placed against a chip's peaks describe no run."""
    try:
        return roofline_peaks()
    except ValueError:
        return None


def _median_time(fn, n=5):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6          # µs


def _step_setup(u, m, seed=0):
    cfg = network.small_config(n_users=u, n_subchannels=m)
    scn = network.make_scenario(jax.random.PRNGKey(seed), cfg)
    prof = profiles.get_profile("nin")
    q = jnp.full((u,), 0.4)
    w = Weights()
    s_vec = jnp.full((u,), min(3, len(prof.device_flops) - 1),
                     dtype=jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 5)
    alloc = era.Allocation(
        beta_up=jax.nn.softmax(jax.random.normal(ks[0], (u, m)), axis=1),
        beta_dn=jax.nn.softmax(jax.random.normal(ks[1], (u, m)), axis=1),
        p=jnp.exp(jax.random.normal(ks[2], (u,)) * 0.3) * 0.1,
        p_ap=jnp.exp(jax.random.normal(ks[3], (u,)) * 0.3),
        r=1.0 + jnp.exp(jax.random.normal(ks[4], (u,)) * 0.2))
    return scn, prof, q, w, s_vec, alloc


def _block(out):
    return jax.block_until_ready(jax.tree.leaves(out)[0])


def _per_step(sizes, reps):
    peaks = _device_peaks()
    for u, m in sizes:
        scn, prof, q, w, s_vec, alloc = _step_setup(u, m)
        aux = eops.build_aux(scn)

        def loss(a):
            return era.utility(scn, prof, s_vec, a, q, w).gamma

        xla_fn = jax.jit(jax.value_and_grad(loss))
        fused_fn = jax.jit(lambda a: eops.era_step_value_and_grad(
            scn, prof, s_vec, q, a, w, aux=aux))
        gx, _ = xla_fn(alloc)
        gf, _ = fused_fn(alloc)                                   # warm
        us_x = _median_time(lambda: _block(xla_fn(alloc)), reps)
        us_f = _median_time(lambda: _block(fused_fn(alloc)), reps)
        tag = f"u{u}m{m}"
        emit(f"era_step.step_xla_us.{tag}", us_x, "")
        emit(f"era_step.step_fused_us.{tag}", us_f, "")
        emit(f"era_step.step_speedup.{tag}", 0.0, f"{us_x / us_f:.3f}x")
        rel = abs(float(gx) - float(gf)) / (abs(float(gx)) + 1e-30)
        emit(f"era_step.step_gamma_rel.{tag}", 0.0, f"{rel:.3e}")

        # roofline: cost the compiled step bodies, place on the platform
        # roofline — the fused claim is the write_bytes (fusion) column
        if peaks is None:
            emit_skip(f"era_step.roofline.{tag}", NO_PEAKS)
            continue
        rx = step_roofline(cost_of_callable(jax.value_and_grad(loss), alloc),
                           peaks=peaks)
        rf = step_roofline(cost_of_callable(
            lambda a: eops.era_step_value_and_grad(
                scn, prof, s_vec, q, a, w, aux=aux), alloc), peaks=peaks)
        for impl, r in (("xla", rx), ("fused", rf)):
            emit(f"era_step.roofline_{impl}.{tag}", 0.0,
                 f"flops={r['flops']:.3e} write_bytes={r['write_bytes']:.3e} "
                 f"intensity={r['intensity']:.2f} bound={r['bound']}")
        if rf["write_bytes"]:
            emit(f"era_step.roofline_bytes_reduction.{tag}", 0.0,
                 f"{rx['write_bytes'] / rf['write_bytes']:.2f}x")


def _full_solve(buckets, reps, quick):
    cfg = network.small_config(n_users=8, n_subchannels=4)
    prof = profiles.get_profile("nin")
    w = Weights()
    steps = 60 if quick else 150
    base = ligd.SolverSpec(backend="sharded", gd_chunk=GD_CHUNK, tol=0.0,
                           max_steps=steps, per_user_split=False)
    for b in buckets:
        scns = [network.make_scenario(jax.random.PRNGKey(i), cfg)
                for i in range(b)]
        qb = jnp.full((b, cfg.n_users), 0.4)
        sx, sf = base, base.replace(step_impl="fused")
        ox = ligd.solve_batch(scns, prof, qb, w, spec=sx)          # warm
        of = ligd.solve_batch(scns, prof, qb, w, spec=sf)
        us_x = _median_time(
            lambda: ligd.solve_batch(scns, prof, qb, w, spec=sx), reps)
        us_f = _median_time(
            lambda: ligd.solve_batch(scns, prof, qb, w, spec=sf), reps)
        emit(f"era_step.solve_xla_us.b{b}", us_x, "")
        emit(f"era_step.solve_fused_us.b{b}", us_f, "")
        emit(f"era_step.solve_speedup.b{b}", 0.0, f"{us_x / us_f:.3f}x")
        g_rel = max(
            float(np.max(np.abs(ox[i].gamma_by_layer - of[i].gamma_by_layer)
                         / (np.abs(ox[i].gamma_by_layer) + 1e-12)))
            for i in range(b))
        emit(f"era_step.solve_gamma_rel.b{b}", 0.0, f"{g_rel:.3e}")


def _paper_scale(reps):
    u, m = PAPER_U, PAPER_M
    scn, prof, q, w, s_vec, alloc = _step_setup(u, m)
    aux = eops.build_aux(scn)
    n_aps = scn.h_up.shape[1]
    tag = f"u{u}m{m}"

    # what a TPU launch would pick, and what it costs per block
    bm = choose_block_m(m, u, n_aps)
    vmem = block_vmem_bytes(bm, u, n_aps)
    vmem_untiled = block_vmem_bytes(m, u, n_aps)
    emit(f"era_step.paper.block_m.{tag}", 0.0,
         f"bm={bm} nb={-(-m // bm)} block_vmem={vmem / 2**20:.2f}MiB "
         f"budget={DEFAULT_VMEM_BUDGET / 2**20:.0f}MiB "
         f"untiled={vmem_untiled / 2**20:.0f}MiB")

    # tiled fused step: the only paper-scale lane that can EXECUTE here
    bm_cpu = PAPER_BLOCK_M_CPU
    fused_fn = jax.jit(lambda a: eops.era_step_value_and_grad(
        scn, prof, s_vec, q, a, w, aux=aux, block_m=bm_cpu))
    _block(fused_fn(alloc))                                       # warm
    us_f = _median_time(lambda: _block(fused_fn(alloc)), reps)
    emit(f"era_step.paper.step_fused_us.{tag}", us_f, f"bm={bm_cpu}")

    peaks = _device_peaks()
    if peaks is None:
        emit_skip(f"era_step.paper.roofline.{tag}", NO_PEAKS)
        return
    rf = tiled_step_roofline(
        cost_of_callable(lambda a: eops.era_step_value_and_grad(
            scn, prof, s_vec, q, a, w, aux=aux, block_m=bm_cpu), alloc),
        n_blocks=-(-m // bm), block_vmem_bytes=vmem,
        vmem_budget=DEFAULT_VMEM_BUDGET, peaks=peaks)
    emit(f"era_step.paper.roofline_fused.{tag}", 0.0,
         f"flops={rf['flops']:.3e} write_bytes={rf['write_bytes']:.3e} "
         f"intensity={rf['intensity']:.2f} bound={rf['bound']} "
         f"n_blocks={rf['n_blocks']} vmem_fits={rf['block_vmem_fits']}")

    # XLA autodiff step: compile + HLO cost only — running it would
    # materialise the (M, U, U) SIC masks (~1.5 TB f32), the latent OOM
    # the tiled grid exists to remove.  memory_s is the roofline-model
    # lower bound on its step time at this platform's bandwidth.
    def loss(a):
        return era.utility(scn, prof, s_vec, a, q, w).gamma

    rx = step_roofline(cost_of_callable(jax.value_and_grad(loss), alloc),
                       peaks=peaks)
    emit(f"era_step.paper.roofline_xla.{tag}", 0.0,
         f"flops={rx['flops']:.3e} write_bytes={rx['write_bytes']:.3e} "
         f"intensity={rx['intensity']:.2f} bound={rx['bound']} "
         f"NOT-RUN mem_lower_bound_us={rx['memory_s'] * 1e6:.0f}")
    if rf["write_bytes"]:
        emit(f"era_step.paper.roofline_bytes_reduction.{tag}", 0.0,
             f"{rx['write_bytes'] / rf['write_bytes']:.2f}x")


def run(quick=False):
    reps = 3 if quick else 5
    sizes = PER_STEP_SIZES[:2] if quick else PER_STEP_SIZES
    buckets = (1, 4) if quick else BUCKETS
    _per_step(sizes, reps)
    _full_solve(buckets, reps, quick)
    if not quick:
        _paper_scale(reps)


if __name__ == "__main__":
    import sys
    run("--quick" in sys.argv)
