"""Oracle for the fused ERA GD-step kernel — analytic forward + backward,
written as a CHANNEL-BLOCK decomposition.

One call evaluates the whole per-step body of ``ligd._gd_core``: NOMA
uplink/downlink SIC rates (eqs. 5–11), delay/energy terms (eqs. 12, 22),
the QoE penalty (eqs. 13–17), the scalar loss Γ (eq. 24) AND its gradient
w.r.t. every ``Allocation`` leaf — i.e. exactly what
``jax.value_and_grad(utility(...).gamma)`` produces, but written as a
single fused pipeline over pre-assembled channel-major operands so the
Pallas kernel (kernel.py) can mirror it line for line in VMEM.

Layout: channel-major ``(M, U)`` for β/gain/ordering tensors, ``(1, U)``
rows for per-user scalars, ``(N, M, U)`` for the cross-cell gain tensors
(N = number of APs, static), ``(1, ENV_LANES)`` for the packed ``CellEnv``
scalars AND the ``Weights`` triple+scales (lanes ``_W_T``..``_R_COST`` —
weights are DATA, not jit statics, so sweeping tradeoff weights never
recompiles the kernel).  ``ops.build_aux``/``ops._operands`` assemble
these from a ``Scenario``.

Block decomposition (the tiled-grid contract)
---------------------------------------------
Everything per-CHANNEL in the math is local to an M-block; only three
reductions cross blocks, and all three are plain sums:

  pass 1   ``up_rate_rows`` / ``dn_rate_rows``: each (bm, U) channel block
           contributes a partial ``(1, U)`` per-user rate row
           (Σ_m β·rate); blocks accumulate.
  tail     ``tail_grads``: the delay/energy/QoE/Γ pipeline and the
           cotangents of the rate rows (``g_rup``/``g_rdn``), plus the
           rate-independent gradient rows (``d_r`` and the energy terms of
           ``d_p``/``d_pap``) — all ``(1, U)`` work, no M axis at all.
  pass 2   ``up_block_grad`` / ``dn_block_grad``: given the tail's
           cotangents, each block's ``(bm, U)`` β-gradient rows are
           block-local, and its contributions to ``d_p``/``d_pap`` are
           partial ``(1, U)`` sums; blocks accumulate.

The grad helpers recompute their block's forward internally: under the
untiled oracle XLA CSEs the duplicate against pass 1, and in the tiled
kernel the recompute IS the design — (bm, U) operand slabs are re-streamed
rather than an O(M·U) forward cache held in VMEM across the grid.
``fused_step_math`` (the untiled oracle, ``bm = M``) and the tiled
``era_step_ref(block_m=...)`` mirror compose the SAME four helpers, so
kernel-vs-ref can only diverge in plumbing, never in arithmetic, and
tiled-vs-untiled differs only by f32 accumulation order.

SIC suffix interference as a masked matvec: user i's intra-cell
interference is the sum over same-SIC-group users decoded after i —
``mask[i, j] = [gid_i == gid_j] · [rank_j > rank_i]`` applied to the
per-user contributions (one contraction per link direction).  The mask is
built from two (bm, U) aux rows (decode rank + group id) — never an HBM
operand; its adjoint is the SAME mask contracted over the other index, so
the backward is gather-free by construction.  The block helpers take the
operator as a ``sic`` argument: the oracle passes ``MaskSIC`` (the whole
(bm, U, U) mask, one einsum), the kernel its one-channel-at-a-time form,
so at paper scale the kernel never holds more than one (U, U) mask.  Both
contract at ``Precision.HIGHEST``.  This deliberately avoids the
sorted-cumsum-difference form noma.py used to use:
  * no in-loop ``take_along_axis`` — XLA:CPU's SPMD partitioner has
    miscompiled per-lane dynamic gathers inside a ``while_loop`` under
    fully-partitioned ``shard_map`` (wrong permutation on non-zero
    shards), and the solver's sharded backend runs exactly that
    composition;
  * no large-prefix cancellation — the mask sums only in-group terms,
    where the global cumsum difference loses ~3 decimal digits in f32
    across the path-loss dynamic range;
  * an MXU/VPU-friendly inner product instead of a data-dependent
    permutation network, which is what a TPU kernel wants anyway.

Gradient-convention notes (must match JAX autodiff bit-for-semantics):
  * ``jnp.maximum(x, y)`` propagates a 0.5 factor to each side at an exact
    tie (``lax``'s balanced_eq rule) — the masked suffix sum is *exactly*
    0.0 for the last-decoded user of every SIC group (empty mask row sums
    no terms), so the relu on intra-cell interference hits that tie on
    every call; ``_tie`` reproduces it.
  * ``sigmoid'(x) = s(1-s)``, ``log2'(x) = 1/((1+x)·ln 2)``,
    ``(r^a)' = a·r^(a-1)``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# f32 contractions everywhere: TPU's default f32 matmul precision is one
# bf16 pass, which would round every interference term to 8 mantissa bits
HIGHEST = jax.lax.Precision.HIGHEST

_LN2 = 0.6931471805599453

# envp row layout (ops._operands packs it): CellEnv scalars in lanes 0-6,
# the Weights fields in lanes 7-13, lanes 14-15 reserved.  Weights ride in
# the env row precisely so era_step_fused needs NO static w argument — two
# weight triples share one compiled kernel (tests/test_era_step.py probes
# the lowering cache).
ENV_LANES = 16
(_NOISE, _BW, _C_DEV, _C_MIN, _LAM_EXP, _XI_D, _XI_E,
 _W_T, _W_Q, _W_R, _QOE_A, _T_SCALE, _E_SCALE, _R_COST) = range(14)


def _tie(x):
    """d/dx max(x, 0) with JAX's balanced tie rule (0.5 at x == 0)."""
    return jnp.where(x > 0, 1.0, jnp.where(x < 0, 0.0, 0.5))


def _sic_mask(rank, gid):
    """(bm, U, U) decode-order mask: ``mask[m, i, j] = 1`` iff users i and j
    share channel m's SIC group and j is decoded after i (j's signal is
    still un-cancelled interference at i's decode step)."""
    same = gid[:, :, None] == gid[:, None, :]
    later = rank[:, None, :] > rank[:, :, None]
    return (same & later).astype(jnp.float32)


def _suffix_apply(mask, x):
    """``out[m, i] = Σ_j mask[m, i, j] · x[m, j]`` — the in-group
    decoded-after suffix sum in user order."""
    return jnp.einsum("mij,mj->mi", mask, x, precision=HIGHEST)


def _suffix_transpose(mask, d):
    """Adjoint of ``_suffix_apply`` w.r.t. ``x``: the same mask einsum
    summed over the OTHER index — ``out[m, j] = Σ_i mask[m, i, j]·d[m, i]``
    (each user j's contribution interferes with every same-group user
    decoded before j)."""
    return jnp.einsum("mij,mi->mj", mask, d, precision=HIGHEST)


class MaskSIC:
    """The SIC suffix operator of one channel block, as the oracle forms
    it: the whole (bm, U, U) mask at once.  The block helpers below take
    any object with this ``apply``/``transpose`` pair, so the kernel can
    substitute its one-channel-at-a-time form (kernel.py) while every
    other line of arithmetic stays shared."""

    def __init__(self, rank, gid):
        self.mask = _sic_mask(rank, gid)

    def apply(self, x):
        return _suffix_apply(self.mask, x)

    def transpose(self, d):
        return _suffix_transpose(self.mask, d)


class _UpFwd(NamedTuple):
    """Block-local uplink forward cache (everything pass 2 reuses)."""
    intra_u: jnp.ndarray      # (bm, U) masked in-group interference
    raw_up: tuple             # per-AP (bm, 1) raw inter-cell residual
    d_up: jnp.ndarray         # (bm, U) SINR denominator
    sinr_up: jnp.ndarray      # (bm, U)
    rate_up: jnp.ndarray      # (bm, U)


class _DnFwd(NamedTuple):
    """Block-local downlink forward cache."""
    intra_d: jnp.ndarray
    raw_dn: jnp.ndarray       # (bm, U) other-AP power residual
    d_dn: jnp.ndarray
    sinr_dn: jnp.ndarray
    rate_dn: jnp.ndarray


def _up_forward(beta_up_t, p, own_up_t, h_up_r, onehot, sic, noise, bw):
    """One channel block's uplink SIC pipeline (noma.uplink_sinr)."""
    n_aps = onehot.shape[0]
    bp_u = beta_up_t * p                          # (bm, U) β·p
    contrib_u = bp_u * own_up_t                   # β·p·|h|²
    sig_u = p * own_up_t
    intra_u = sic.apply(contrib_u)
    # inter-cell residual at AP n summed cancellation-free over OTHER-cell
    # users (1 - onehot), not as t_all - own_cell: when no cross terms
    # exist the sum is exactly 0.0, hitting the same relu tie the autodiff
    # path's exact self-cancellation hits — a subtraction would land at
    # ±ulp and flip ``_tie`` to 0/1 where autodiff propagates 0.5
    raw_up = []
    inter_u = jnp.zeros_like(bp_u)
    for n in range(n_aps):
        other = bp_u * h_up_r[n] * (1.0 - onehot[n][None, :])
        raw = jnp.sum(other, axis=1, keepdims=True)             # (bm, 1)
        raw_up.append(raw)
        inter_u = inter_u + jnp.maximum(raw, 0.0) * onehot[n][None, :]
    d_up = jnp.maximum(intra_u, 0.0) + inter_u + noise
    sinr_up = sig_u / d_up
    rate_up = bw * jnp.log2(1.0 + sinr_up)
    return _UpFwd(intra_u, tuple(raw_up), d_up, sinr_up, rate_up)


def _dn_forward(beta_dn_t, p_ap, own_dn_t, h_dn_r, onehot, sic, noise, bw):
    """One channel block's downlink SIC pipeline (noma.downlink_sinr)."""
    n_aps = onehot.shape[0]
    comp_u = beta_dn_t * p_ap
    sig_d = p_ap * own_dn_t
    intra_pwr_u = sic.apply(comp_u)
    intra_d = intra_pwr_u * own_dn_t
    # same cancellation-free shape downlink: other-AP power only, never
    # cross_total - own_ap (see the uplink note above)
    raw_dn = jnp.zeros_like(comp_u)
    for n in range(n_aps):
        ap_n = jnp.sum(comp_u * onehot[n][None, :], axis=1,
                       keepdims=True)             # (bm, 1)
        raw_dn = raw_dn + ap_n * h_dn_r[n] * (1.0 - onehot[n][None, :])
    inter_d = jnp.maximum(raw_dn, 0.0)
    d_dn = jnp.maximum(intra_d, 0.0) + inter_d + noise
    sinr_dn = sig_d / d_dn
    rate_dn = bw * jnp.log2(1.0 + sinr_dn)
    return _DnFwd(intra_d, raw_dn, d_dn, sinr_dn, rate_dn)


def up_rate_rows(beta_up_t, p, own_up_t, h_up_r, onehot, sic, noise, bw):
    """Pass 1, uplink: this block's partial ``(1, U)`` rate row Σ_m β·rate
    — the ONLY uplink quantity that crosses blocks.  ``sic``: the block's
    SIC suffix operator (``MaskSIC`` or the kernel's per-channel form)."""
    fwd = _up_forward(beta_up_t, p, own_up_t, h_up_r, onehot, sic, noise,
                      bw)
    return jnp.sum(beta_up_t * fwd.rate_up, axis=0, keepdims=True)


def dn_rate_rows(beta_dn_t, p_ap, own_dn_t, h_dn_r, onehot, sic, noise,
                 bw):
    """Pass 1, downlink partial rate row."""
    fwd = _dn_forward(beta_dn_t, p_ap, own_dn_t, h_dn_r, onehot, sic, noise,
                      bw)
    return jnp.sum(beta_dn_t * fwd.rate_dn, axis=0, keepdims=True)


def tail_grads(r_up, r_dn, p, p_ap, r, q, dev_fl, edge_fl, wup, wdn, envp):
    """The M-free tail: delay / energy / QoE / Γ (era, qoe) forward, plus
    the backward chain down to per-user cotangents.  Returns
    ``(gamma, g_rup, g_rdn, d_p0, d_pap0, d_r)`` — the rate-row cotangents
    pass 2 consumes and the rate-independent gradient rows."""
    c_dev = envp[0, _C_DEV]
    c_min = envp[0, _C_MIN]
    lam_exp = envp[0, _LAM_EXP]
    xi_d = envp[0, _XI_D]
    xi_e = envp[0, _XI_E]
    w_t = envp[0, _W_T]
    w_q = envp[0, _W_Q]
    w_r = envp[0, _W_R]
    qoe_a = envp[0, _QOE_A]
    t_scale = envp[0, _T_SCALE]
    e_scale = envp[0, _E_SCALE]
    r_cost_scale = envp[0, _R_COST]

    lam = r ** lam_exp
    lam_p = lam_exp * r ** (lam_exp - 1.0)
    edge_c = lam * c_min
    t_dev = dev_fl / c_dev
    t_srv = edge_fl / edge_c
    mup = jnp.maximum(r_up, 1.0)
    mdn = jnp.maximum(r_dn, 1.0)
    t = t_dev + t_srv + wup / mup + wdn / mdn
    e = (xi_d * c_dev ** 2 * dev_fl
         + xi_e * edge_c ** 2 * edge_fl
         + p * wup / mup + p_ap * wdn / mdn)
    rq = jax.nn.sigmoid(qoe_a * (t / q - 1.0))
    gamma = (w_t * jnp.sum(t) * t_scale
             + w_q * (jnp.sum((t - q) * rq) * t_scale + jnp.sum(rq))
             + w_r * (jnp.sum(e) * e_scale
                      + jnp.sum(lam) * r_cost_scale))

    # backward: Γ -> per-user t/e/r cotangents
    rp = qoe_a * rq * (1.0 - rq) / q              # dR/dt
    g_t = (w_t * t_scale
           + w_q * (t_scale * (rq + (t - q) * rp) + rp))         # (1, U)
    g_e = w_r * e_scale
    d_r = (g_t * (-edge_fl * c_min * lam_p / (edge_c ** 2))
           + g_e * (2.0 * xi_e * c_min ** 2 * lam * lam_p * edge_fl)
           + w_r * r_cost_scale * lam_p)
    g_rup = -_tie(r_up - 1.0) * (wup / mup ** 2) * (g_t + g_e * p)
    g_rdn = -_tie(r_dn - 1.0) * (wdn / mdn ** 2) * (g_t + g_e * p_ap)
    d_p0 = g_e * wup / mup                        # e_up = p·w/max(r,1)
    d_pap0 = g_e * wdn / mdn
    return gamma, g_rup, g_rdn, d_p0, d_pap0, d_r


def up_block_grad(beta_up_t, p, own_up_t, h_up_r, onehot, sic, noise, bw,
                  g_rup):
    """Pass 2, uplink: this block's ``(bm, U)`` β gradient rows and its
    partial ``(1, U)`` contribution to ``d_p``, given the tail's rate-row
    cotangent.  Recomputes the block forward (see module docstring)."""
    n_aps = onehot.shape[0]
    fwd = _up_forward(beta_up_t, p, own_up_t, h_up_r, onehot, sic, noise,
                      bw)
    d_sinr = (g_rup * beta_up_t) * bw / ((1.0 + fwd.sinr_up) * _LN2)
    d_bu = g_rup * fwd.rate_up                    # direct Σ_m β·rate term
    psi = -d_sinr * fwd.sinr_up / fwd.d_up        # cotangent of D
    d_contrib = sic.transpose(psi * _tie(fwd.intra_u))
    d_bp = jnp.zeros_like(beta_up_t)
    for n in range(n_aps):
        g_n = jnp.sum(psi * onehot[n][None, :], axis=1,
                      keepdims=True) * _tie(fwd.raw_up[n])        # (bm, 1)
        d_bp = d_bp + g_n * h_up_r[n] * (1.0 - onehot[n][None, :])
    d_bp = d_bp + d_contrib * own_up_t
    d_bu = d_bu + d_bp * p
    d_p_part = jnp.sum(d_bp * beta_up_t + (d_sinr / fwd.d_up) * own_up_t,
                       axis=0, keepdims=True)
    return d_bu, d_p_part


def dn_block_grad(beta_dn_t, p_ap, own_dn_t, h_dn_r, onehot, sic, noise, bw,
                  g_rdn):
    """Pass 2, downlink block gradient + partial ``d_pap`` row."""
    n_aps = onehot.shape[0]
    fwd = _dn_forward(beta_dn_t, p_ap, own_dn_t, h_dn_r, onehot, sic, noise,
                      bw)
    d_sinr_d = (g_rdn * beta_dn_t) * bw / ((1.0 + fwd.sinr_dn) * _LN2)
    d_bd = g_rdn * fwd.rate_dn
    psi_d = -d_sinr_d * fwd.sinr_dn / fwd.d_dn
    d_inter = psi_d * _tie(fwd.raw_dn)
    d_comp = sic.transpose(psi_d * _tie(fwd.intra_d) * own_dn_t)
    for n in range(n_aps):
        d_ap_n = jnp.sum(d_inter * h_dn_r[n]
                         * (1.0 - onehot[n][None, :]),
                         axis=1, keepdims=True)                   # (bm, 1)
        d_comp = d_comp + d_ap_n * onehot[n][None, :]
    d_bd = d_bd + d_comp * p_ap
    d_pap_part = jnp.sum(d_comp * beta_dn_t + (d_sinr_d / fwd.d_dn)
                         * own_dn_t, axis=0, keepdims=True)
    return d_bd, d_pap_part


def fused_step_math(beta_up_t, beta_dn_t, p, p_ap, r, q,
                    dev_fl, edge_fl, wup, wdn, envp,
                    own_up_t, own_dn_t, h_up_r, h_dn_r, onehot,
                    up_rank, up_gid, dn_rank, dn_gid):
    """The untiled fused forward+backward — the four block helpers composed
    on one whole-M block.  This is both the numerical oracle and the
    ``bm = M`` special case of the tiled grid.

    Returns ``(gamma, (d_beta_up_t, d_beta_dn_t, d_p, d_pap, d_r))`` with
    gradients in the same layouts as their primal operands."""
    noise = envp[0, _NOISE]
    bw = envp[0, _BW]
    up_sic = MaskSIC(up_rank, up_gid)
    dn_sic = MaskSIC(dn_rank, dn_gid)
    r_up = up_rate_rows(beta_up_t, p, own_up_t, h_up_r, onehot, up_sic,
                        noise, bw)
    r_dn = dn_rate_rows(beta_dn_t, p_ap, own_dn_t, h_dn_r, onehot, dn_sic,
                        noise, bw)
    gamma, g_rup, g_rdn, d_p, d_pap, d_r = tail_grads(
        r_up, r_dn, p, p_ap, r, q, dev_fl, edge_fl, wup, wdn, envp)
    d_bu, d_p_part = up_block_grad(beta_up_t, p, own_up_t, h_up_r, onehot,
                                   up_sic, noise, bw, g_rup)
    d_bd, d_pap_part = dn_block_grad(beta_dn_t, p_ap, own_dn_t, h_dn_r,
                                     onehot, dn_sic, noise, bw, g_rdn)
    return gamma, (d_bu, d_bd, d_p + d_p_part, d_pap + d_pap_part, d_r)


# operand axis map for the M-blocked layout: index into the 20-operand
# tuple -> the axis carrying M (kernel.py's BlockSpecs and the tiled ref
# mirror share it)
N_OPERANDS = 20
BLOCKED_AXIS = {0: 0, 1: 0, 11: 0, 12: 0, 13: 1, 14: 1,
                16: 0, 17: 0, 18: 0, 19: 0}


def _slice_block(operands, lo, hi):
    """The 20-operand tuple restricted to channel rows [lo, hi)."""
    out = []
    for i, x in enumerate(operands):
        ax = BLOCKED_AXIS.get(i)
        if ax is None:
            out.append(x)
        elif ax == 0:
            out.append(x[lo:hi])
        else:
            out.append(x[:, lo:hi])
    return tuple(out)


def era_step_ref(*operands, block_m=None):
    """The pure-jnp oracle: dispatched by
    ``ops.era_step_value_and_grad(impl='ref')`` — the fused GD step on
    non-TPU backends, and the reference the Pallas kernel is
    regression-tested against.

    ``block_m=None`` (default) runs the untiled single-block pipeline.  An
    explicit ``block_m`` runs the tiled mirror of the kernel's grid — the
    same two passes over [lo, hi) channel blocks with the same plain-sum
    cross-block reductions, in plain jnp — so tests can pin
    tiled-vs-untiled agreement (f32 accumulation order is the ONLY
    difference) without a Pallas launch.  The remainder block is simply
    shorter here; the kernel zero-pads instead (exactly neutral — padded
    channels have zero gain/β, so every partial sum they touch is 0.0)."""
    if len(operands) != N_OPERANDS:
        raise ValueError(f"expected {N_OPERANDS} operands, "
                         f"got {len(operands)}")
    m = operands[0].shape[0]
    if block_m is None or block_m <= 0 or block_m >= m:
        return fused_step_math(*operands)
    envp = operands[10]
    noise = envp[0, _NOISE]
    bw = envp[0, _BW]
    spans = [(lo, min(lo + block_m, m)) for lo in range(0, m, block_m)]
    blocks = [_slice_block(operands, lo, hi) for lo, hi in spans]

    def up_args(blk):
        return (blk[0], blk[2], blk[11], blk[13], blk[15],
                MaskSIC(blk[16], blk[17]))

    def dn_args(blk):
        return (blk[1], blk[3], blk[12], blk[14], blk[15],
                MaskSIC(blk[18], blk[19]))

    # pass 1: accumulate the (1, U) rate rows block by block, in grid order
    u = operands[2].shape[1]
    r_up = jnp.zeros((1, u), jnp.float32)
    r_dn = jnp.zeros((1, u), jnp.float32)
    for blk in blocks:
        r_up = r_up + up_rate_rows(*up_args(blk), noise, bw)
        r_dn = r_dn + dn_rate_rows(*dn_args(blk), noise, bw)

    # tail: Γ + cotangents, no M axis
    _, _, p, p_ap, r, q, dev_fl, edge_fl, wup, wdn = operands[:10]
    gamma, g_rup, g_rdn, d_p, d_pap, d_r = tail_grads(
        r_up, r_dn, p, p_ap, r, q, dev_fl, edge_fl, wup, wdn, envp)

    # pass 2: block-local β rows, cross-block-reduced (1, U) power rows
    d_bu_blocks, d_bd_blocks = [], []
    for blk in blocks:
        d_bu, d_p_part = up_block_grad(*up_args(blk), noise, bw, g_rup)
        d_bd, d_pap_part = dn_block_grad(*dn_args(blk), noise, bw, g_rdn)
        d_bu_blocks.append(d_bu)
        d_bd_blocks.append(d_bd)
        d_p = d_p + d_p_part
        d_pap = d_pap + d_pap_part
    return gamma, (jnp.concatenate(d_bu_blocks, axis=0),
                   jnp.concatenate(d_bd_blocks, axis=0),
                   d_p, d_pap, d_r)
