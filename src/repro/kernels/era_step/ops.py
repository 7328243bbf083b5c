"""Scenario-level wrapper for the fused ERA GD step: assemble channel-major
operands + static SIC permutation aux from a ``Scenario``, dispatch to the
Pallas kernel (TPU) or the analytic jnp oracle (everywhere else), and map
the results back onto ``Allocation`` layouts.

``era_step_value_and_grad`` is a drop-in for
``jax.value_and_grad(lambda a: utility(scn, prof, s, a, q, w).gamma)`` —
``ligd._gd_core(step_impl='fused')`` swaps its grad_fn for this under all
three solver backends.  Everything here is pure traced jnp (vmappable over
a leading cell axis, shard_map-safe: no collectives, no host sync), so the
fused step composes with the batched sweep and the cells mesh unchanged.

``build_aux`` precomputes what is allocation-INdependent — per-user SIC
decode ranks and group ids (the two rows ``ref._sic_mask`` expands into
the masked-matvec interference operator), the AP one-hot, transposed gain
tensors — once per scenario (``_sweep_core`` hoists it outside the layer
scan), so the per-step work is exactly the fused pipeline.  The rank/gid
rows are themselves derived by one-hot einsum rather than gather/argsort,
keeping the whole fused path free of data-dependent indexing (see ref.py
on the XLA:CPU shard_map+while gather miscompile this sidesteps).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.era import Allocation
from repro.kernels.era_step import ref as _ref


class StepAux(NamedTuple):
    """Allocation-independent operands of the fused step (all jnp leaves —
    vmappable / shard_map-safe alongside the Scenario they derive from)."""
    own_up_t: jnp.ndarray     # (M, U) own-AP uplink gain, channel-major
    own_dn_t: jnp.ndarray     # (M, U)
    h_up_r: jnp.ndarray       # (N, M, U) uplink gain to AP n, transposed
    h_dn_r: jnp.ndarray       # (N, M, U) downlink gain from AP n
    onehot: jnp.ndarray       # (N, U) AP-association one-hot
    up_rank: jnp.ndarray      # (M, U) f32 SIC decode rank per user
    up_gid: jnp.ndarray       # (M, U) f32 SIC group id per user
    dn_rank: jnp.ndarray
    dn_gid: jnp.ndarray


def _group_starts(group_end):
    """Per sorted position, the first index of its SIC group — derived from
    the ``group_end`` tensor Scenario stores: position k starts a group iff
    k == 0 or the previous position's group ended at k-1; a running max of
    start indices then labels every member."""
    u = group_end.shape[-1]
    idx = jnp.arange(u, dtype=jnp.int32)
    prev_end = jnp.concatenate(
        [jnp.full(group_end.shape[:-1] + (1,), -1, group_end.dtype),
         group_end[..., :-1]], axis=-1)
    is_start = prev_end == (idx - 1)
    return jax.lax.cummax(jnp.where(is_start, idx, 0),
                          axis=group_end.ndim - 1)


def _rank_gid(order, group_end):
    """User-order decode rank + group id from the Scenario's sorted-order
    SIC tensors, via one-hot einsum (no argsort/gather — the tensors stay
    f32 and the derivation composes under vmap + shard_map untouched).

    ``oh[m, k, i] = 1`` iff sorted position k decodes user i, so a k-sum
    against any per-sorted-position row relabels it per user."""
    u = order.shape[-1]
    oh = jax.nn.one_hot(order.astype(jnp.int32), u, dtype=jnp.float32)
    gs = _group_starts(group_end.astype(jnp.int32)).astype(jnp.float32)
    # HIGHEST keeps the relabelled integers exact: TPU's default one-pass
    # bf16 contraction would round every rank and group id above 256
    rank = jnp.einsum("k,mki->mi", jnp.arange(u, dtype=jnp.float32), oh,
                      precision=_ref.HIGHEST)
    gid = jnp.einsum("mki,mk->mi", oh, gs, precision=_ref.HIGHEST)
    return rank, gid


def build_aux(scn) -> StepAux:
    """Static (per-scenario) operand pack for the fused step."""
    n_aps = scn.cfg.n_aps
    onehot = jax.nn.one_hot(scn.assoc, n_aps, dtype=jnp.float32).T  # (N,U)
    up_rank, up_gid = _rank_gid(scn.up_order, scn.up_group_end)
    dn_rank, dn_gid = _rank_gid(scn.dn_order, scn.dn_group_end)
    return StepAux(
        own_up_t=scn.own_gain_up().T,
        own_dn_t=scn.own_gain_dn().T,
        h_up_r=jnp.transpose(scn.h_up, (1, 2, 0)),    # (U,N,M) -> (N,M,U)
        h_dn_r=jnp.transpose(scn.h_dn, (0, 2, 1)),    # (N,U,M) -> (N,M,U)
        onehot=onehot,
        up_rank=up_rank, up_gid=up_gid,
        dn_rank=dn_rank, dn_gid=dn_gid,
    )


def _operands(scn, prof, s_vec, q, alloc, aux, w):
    """The 20 positional operands of ``ref.fused_step_math``, in order.

    The env row packs the ``CellEnv`` scalars AND the ``Weights`` fields
    (``ref.ENV_LANES`` lanes) — weights are traced DATA, so weight sweeps
    share one kernel compile (the lowering-cache probe in
    tests/test_era_step.py pins this)."""
    env = scn.env
    row = lambda x: jnp.asarray(x, jnp.float32)[None, :]          # (1, U)
    envp = jnp.stack([
        jnp.asarray(env.noise_w, jnp.float32),
        jnp.asarray(env.subchannel_bw, jnp.float32),
        jnp.asarray(env.c_device_flops, jnp.float32),
        jnp.asarray(env.c_min_flops, jnp.float32),
        jnp.asarray(env.lambda_exponent, jnp.float32),
        jnp.asarray(env.xi_device, jnp.float32),
        jnp.asarray(env.xi_edge, jnp.float32),
        jnp.asarray(w.w_t, jnp.float32),
        jnp.asarray(w.w_q, jnp.float32),
        jnp.asarray(w.w_r, jnp.float32),
        jnp.asarray(w.qoe_a, jnp.float32),
        jnp.asarray(w.t_scale, jnp.float32),
        jnp.asarray(w.e_scale, jnp.float32),
        jnp.asarray(w.r_cost_scale, jnp.float32),
        jnp.float32(0.0),
        jnp.float32(0.0),
    ])[None, :]                                       # (1, ref.ENV_LANES)
    with jax.named_scope("era.beta_t"):
        bu_t = alloc.beta_up.T.astype(jnp.float32)
        bd_t = alloc.beta_dn.T.astype(jnp.float32)
    return (
        bu_t, bd_t,
        row(alloc.p), row(alloc.p_ap), row(alloc.r), row(q),
        row(prof.device_flops[s_vec]), row(prof.edge_flops[s_vec]),
        row(prof.uplink_bits[s_vec]), row(prof.downlink_bits[s_vec]),
        envp,
        aux.own_up_t, aux.own_dn_t, aux.h_up_r, aux.h_dn_r, aux.onehot,
        aux.up_rank, aux.up_gid, aux.dn_rank, aux.dn_gid,
    )


def era_step_value_and_grad(scn, prof, s_vec, q, alloc, w, *, aux=None,
                            impl=None, interpret=None, block_m=0):
    """Fused ``(Γ, ∂Γ/∂Allocation)`` for one GD step.

    ``impl``: 'kernel' (Pallas launch), 'ref' (analytic jnp pipeline), or
    None = 'kernel' on TPU else 'ref' — the kernel in interpret mode is an
    emulator, far too slow for a solve's inner loop, so CPU/GPU runs get
    the same fused arithmetic via the oracle.  ``interpret`` defaults to
    True off-TPU (kernel impl only).  ``block_m``: channel-tile size —
    0 (default) lets the kernel auto-size from its VMEM budget
    (``kernel.choose_block_m``; the ref oracle stays untiled), > 0 is
    first rounded to the tile Mosaic can compile (``kernel.legal_block_m``:
    M, or a multiple of 8) and that block is forced on both impls (the ref
    runs its tiled mirror, so CPU backends reproduce the compiled kernel's
    accumulation order).  Pass a precomputed ``aux`` (``build_aux``) when
    calling repeatedly on one scenario."""
    from repro.kernels.era_step.kernel import era_step_fused, legal_block_m
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "ref"
    if block_m > 0:
        block_m = legal_block_m(block_m, alloc.beta_up.shape[1])
    if aux is None:
        aux = build_aux(scn)
    operands = _operands(scn, prof, s_vec, q, alloc, aux, w)
    if impl == "ref":
        gamma, grads = _ref.era_step_ref(
            *operands, block_m=block_m if block_m > 0 else None)
    elif impl == "kernel":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        gamma, *grads = era_step_fused(*operands, block_m=block_m,
                                       interpret=interpret)
    else:
        raise ValueError(f"impl must be 'kernel' or 'ref', got {impl!r}")
    d_bu, d_bd, d_p, d_pap, d_r = grads
    with jax.named_scope("era.beta_t"):
        d_bu, d_bd = d_bu.T, d_bd.T
    grad = Allocation(beta_up=d_bu, beta_dn=d_bd,
                      p=d_p[0], p_ap=d_pap[0], r=d_r[0])
    return jnp.reshape(gamma, ()), grad
