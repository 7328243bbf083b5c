"""Fused ERA GD step as a single channel-tiled Pallas TPU launch.

The innermost body of every Li-GD solve — NOMA SIC rates, QoE penalty, the
scalar loss Γ and its gradient w.r.t. all five ``Allocation`` leaves —
runs F+1 × ``max_steps`` × B times per admission round as ~30 separate XLA
ops (plus their autodiff transposes).  This kernel evaluates the whole
forward+backward in ONE launch with zero intermediate HBM traffic — a
custom-VJP-style fusion over the user axis.  SIC suffix interference is a
masked matvec (``ref._sic_mask``, the same cancellation-free formulation
noma_rate and core.noma use), so the hot ops are MXU dots over 0/1
masks built in VMEM; the backward contracts the same mask over its other
index (scatter- and gather-free, see ref.py).

The kernel body calls ref.py's four block helpers on its loaded slabs —
the oracle and the kernel share one definition of the arithmetic, all
but the SIC contraction itself (see "SIC masks" below), so the
kernel sweep (tests/test_era_step.py) validates Pallas plumbing and Mosaic
lowering, while ref-vs-autodiff validates the math itself.

Tiled grid
----------
Γ and every gradient leaf depend *nonlinearly* (sigmoid, max) on the
per-user rate rows ``r_up``/``r_dn``, which are full-M reductions — so the
M axis cannot be tiled in one sweep.  The grid is ``(2, nb)`` with
``dimension_semantics=('arbitrary', 'arbitrary')`` (strictly sequential,
lexicographic), i.e. two passes over the same ``nb = M/bm`` channel
blocks:

  pass 0   each block streams its (bm, U) / (N, bm, U) operand slabs and
           accumulates partial (1, U) rate rows into VMEM scratch
           (``ref.up_rate_rows`` / ``dn_rate_rows``);
  tail     at grid step (1, 0) the accumulated rows are complete: the
           O(U) delay/energy/QoE/Γ tail runs once, emitting Γ, d_r, the
           rate-independent d_p/d_pap rows, and the rate-row cotangents
           ``g_rup``/``g_rdn`` into scratch;
  pass 1   each block re-streams its slabs, recomputes its forward, and
           writes its (bm, U) β-gradient block (``ref.up_block_grad`` /
           ``dn_block_grad``) while accumulating (1, U) d_p/d_pap
           partials into revisited output blocks (constant index map →
           the row lives in VMEM across the whole grid, accumulated
           in-place, copied out once at grid end).

SIC masks: inside a grid step the suffix operator runs one channel at a
time (``_ChannelSIC``): each channel's (U, U) mask is built in VMEM from
its two rank/gid rows and contracted on the MXU, so the O(M·U²) mask is
never materialised in HBM, nor more than one channel of it in VMEM.

Sizing: ``block_vmem_bytes`` estimates one grid step's scoped VMEM —
one (U, U) mask plus ~40 f32 (bm, U) rows per channel row, fit to
Mosaic's own accounting.  ``choose_block_m`` picks ``bm = M`` (the
untiled single-block launch) whenever that fits the 64 MiB the kernel
requests (``DEFAULT_VMEM_BUDGET``) — every test scale and the paper's
U=1250/M=250 (~58 MiB estimated, 25 MiB needed) — else the largest
multiple of 8 dividing M rounded up to 8.  A block that does not divide M
zero-pads the M axis to the next multiple — padded channels carry zero
gain/β/rank rows, which contribute exactly 0.0 to every cross-block sum
(rates and gradients), so padding is bitwise-neutral; the padded
β-gradient rows are sliced off.  Compiled, a (bm, U) block must have
``bm`` a multiple of 8 or equal to M (``legal_block_m``); interpret mode
runs any block.

Operands and gradients are all f32 with no data-dependent indexing at
all, precisely so this lowers to Mosaic as dots + elementwise ops — the
one Pallas-hostile primitive family (dynamic lane gathers) was designed
out at the ref.py level.  Weights ride in the ``envp`` row (ref.ENV_LANES
lanes), NOT as jit statics: sweeping tradeoff weights re-uses one
compiled kernel (only ``block_m``/``interpret`` — true shape/lowering
parameters — are static).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.era_step import ref as _ref
from repro.kernels.era_step.ref import (
    BLOCKED_AXIS, N_OPERANDS, _BW, _NOISE)

# Scoped VMEM the kernel requests from Mosaic (``vmem_limit_bytes``) and
# that ``choose_block_m`` sizes a grid step against: half of the 128 MiB
# a TPU v5e core has, the rest left to XLA, which may place the
# kernel's operands in VMEM too.  (Unrequested, Mosaic scopes a kernel
# to 16 MiB.)
DEFAULT_VMEM_BUDGET = 64 * 1024 * 1024

# Fit to Mosaic's own accounting at U=1250, N=5 (the smallest scoped
# limit each block compiles under for a described v5e: 5 MiB at bm=8,
# 15 at 64, 27 at 128): ~37.5 f32 (bm, U) rows per channel row — the
# blocked operands and outputs with their second pipeline buffers, the
# SIC row buffers and the helpers' live temporaries — over a fixed part
# under one channel's (U, U) mask.  Both are rounded up, so the estimate
# stays above the real need.
_LIVE_ROWS = 40


def _round_up(x, k):
    return -(-x // k) * k


def block_vmem_bytes(bm, u, n_aps):
    """Estimated scoped VMEM of ONE grid step at block size ``bm``, in
    padded (8, 128) f32 tiles: one channel's (U, U) SIC mask (the channel
    loop never holds two), ``_LIVE_ROWS`` (bm, U) rows, and the
    double-buffered (1, U)/(N, U) rows, outputs and scratch."""
    lanes = _round_up(u, 128)
    mask = _round_up(u, 8) * lanes
    live = _LIVE_ROWS * _round_up(bm, 8) * lanes
    small = (2 * (10 + n_aps + 4) + 4) * 8 * lanes
    return 4 * (mask + live + small)


def legal_block_m(bm, m):
    """The channel block Mosaic can tile: the last two dims of a (bm, U)
    block must be M itself or divisible by 8 (the f32 sublane tile), so
    round ``bm`` up to a multiple of 8, or to M when that reaches it."""
    if bm >= m:
        return m
    bm = _round_up(bm, 8)
    return m if bm >= m else bm


def choose_block_m(m, u, n_aps, budget_bytes=DEFAULT_VMEM_BUDGET):
    """Largest legal channel block whose grid step fits ``budget_bytes``:
    ``m`` itself (the untiled single-block launch) when the whole problem
    fits, else the largest multiple of 8 that divides ``m`` rounded up to
    8 — so the zero-padded remainder is at most 7 channels.  8 is the
    floor even if over budget: past that, U itself is the problem and the
    caller should shard users, not channels."""
    if block_vmem_bytes(m, u, n_aps) <= budget_bytes:
        return m
    m8 = _round_up(m, 8)
    best = 8
    for bm in range(16, m8, 8):
        if m8 % bm == 0 and block_vmem_bytes(bm, u, n_aps) <= budget_bytes:
            best = bm
    return best


class _ChannelSIC:
    """The SIC suffix operator of one channel block, one channel at a
    time: per channel, the (U, U) mask is built from that channel's rank
    and group-id rows and contracted with its (1, U) row on the MXU.  The
    same ``apply``/``transpose`` pair as ``ref.MaskSIC``, which builds the
    whole (bm, U, U) mask — a block Mosaic neither lowers (its batched
    matvec contracts the mask's middle axis in the adjoint) nor fits in
    VMEM at paper scale (~50 MB per mask at bm=8, U=1250).  Both
    contractions take the mask as the plain 2-D rhs: NT for ``apply``
    (Σ_j mask[i, j]·x[j]), NN for ``transpose`` (Σ_i mask[i, j]·d[i]).

    ``x_buf``/``out_buf`` are (bm, U) VMEM scratch rows: the operand is
    parked there so the loop can read it one row at a time."""

    def __init__(self, rank_ref, gid_ref, x_buf, out_buf):
        self.rank_ref = rank_ref
        self.gid_ref = gid_ref
        self.x_buf = x_buf
        self.out_buf = out_buf

    def _contract(self, x, rhs_dim):
        self.x_buf[...] = x

        def body(c, carry):
            rank = self.rank_ref[pl.ds(c, 1), :]           # (1, U)
            gid = self.gid_ref[pl.ds(c, 1), :]
            mask = ((gid.T == gid) & (rank > rank.T)).astype(jnp.float32)
            self.out_buf[pl.ds(c, 1), :] = jax.lax.dot_general(
                self.x_buf[pl.ds(c, 1), :], mask,
                (((1,), (rhs_dim,)), ((), ())),
                precision=_ref.HIGHEST, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, self.x_buf.shape[0], body, 0)
        return self.out_buf[...]

    def apply(self, x):
        return self._contract(x, 1)

    def transpose(self, d):
        return self._contract(d, 0)


def _kernel(*refs):
    ins = refs[:N_OPERANDS]
    (gamma_ref, dbu_ref, dbd_ref, dp_ref, dpap_ref,
     dr_ref) = refs[N_OPERANDS:N_OPERANDS + 6]
    rup_acc, rdn_acc, grup, grdn, x_buf, out_buf = refs[N_OPERANDS + 6:]
    phase = pl.program_id(0)
    b = pl.program_id(1)
    envp = ins[10][...]
    noise = envp[0, _NOISE]
    bw = envp[0, _BW]
    up_sic = _ChannelSIC(ins[16], ins[17], x_buf, out_buf)
    dn_sic = _ChannelSIC(ins[18], ins[19], x_buf, out_buf)

    def up_args():
        # (beta_up_t, p, own_up_t, h_up_r, onehot, sic)
        return (ins[0][...], ins[2][...], ins[11][...], ins[13][...],
                ins[15][...], up_sic)

    def dn_args():
        return (ins[1][...], ins[3][...], ins[12][...], ins[14][...],
                ins[15][...], dn_sic)

    @pl.when((phase == 0) & (b == 0))
    def _init():
        rup_acc[...] = jnp.zeros_like(rup_acc)
        rdn_acc[...] = jnp.zeros_like(rdn_acc)
        gamma_ref[...] = jnp.zeros_like(gamma_ref)
        dp_ref[...] = jnp.zeros_like(dp_ref)
        dpap_ref[...] = jnp.zeros_like(dpap_ref)
        dr_ref[...] = jnp.zeros_like(dr_ref)

    @pl.when(phase == 0)
    def _pass0():
        rup_acc[...] += _ref.up_rate_rows(*up_args(), noise, bw)
        rdn_acc[...] += _ref.dn_rate_rows(*dn_args(), noise, bw)
        # every output block gets defined bytes on its pass-0 visit, so
        # copy-out never publishes garbage in either execution mode
        dbu_ref[...] = jnp.zeros_like(dbu_ref)
        dbd_ref[...] = jnp.zeros_like(dbd_ref)

    @pl.when((phase == 1) & (b == 0))
    def _tail():
        gamma, g_rup, g_rdn, d_p0, d_pap0, d_r = _ref.tail_grads(
            rup_acc[...], rdn_acc[...], ins[2][...], ins[3][...],
            ins[4][...], ins[5][...], ins[6][...], ins[7][...],
            ins[8][...], ins[9][...], envp)
        # a full-block store: Mosaic stores no scalar to VMEM
        gamma_ref[...] = jnp.full((1, 1), gamma, jnp.float32)
        dr_ref[...] = d_r
        dp_ref[...] += d_p0
        dpap_ref[...] += d_pap0
        grup[...] = g_rup
        grdn[...] = g_rdn

    @pl.when(phase == 1)
    def _pass1():
        d_bu, d_p_part = _ref.up_block_grad(*up_args(), noise, bw,
                                            grup[...])
        d_bd, d_pap_part = _ref.dn_block_grad(*dn_args(), noise, bw,
                                              grdn[...])
        dbu_ref[...] = d_bu
        dbd_ref[...] = d_bd
        dp_ref[...] += d_p_part
        dpap_ref[...] += d_pap_part


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def era_step_fused(*operands, block_m=0, interpret=False):
    """One fused forward+backward launch over a ``(2, nb)`` channel-tiled
    grid.  ``operands``: the 20 assembled tensors of
    ``ref.fused_step_math`` (``ops._operands`` builds them — weights
    included, in the env row).  ``block_m``: channel rows per grid step;
    0 auto-selects via ``choose_block_m`` (untiled whenever the problem
    fits VMEM).  Returns
    ``(gamma (1,1), d_beta_up_t, d_beta_dn_t, d_p, d_pap, d_r)``."""
    if len(operands) != N_OPERANDS:
        raise ValueError(f"expected {N_OPERANDS} operands, "
                         f"got {len(operands)}")
    m, u = operands[0].shape
    n_aps = operands[15].shape[0]
    bm = block_m if block_m > 0 else choose_block_m(m, u, n_aps)
    # interpret mode runs any block (the tests sweep odd ones); Mosaic
    # tiles only legal ones
    bm = min(bm, m) if interpret else legal_block_m(bm, m)
    nb = -(-m // bm)
    m_pad = nb * bm
    if m_pad != m:
        padded = []
        for i, x in enumerate(operands):
            ax = BLOCKED_AXIS.get(i)
            if ax is None:
                padded.append(x)
            else:
                widths = [(0, 0)] * x.ndim
                widths[ax] = (0, m_pad - m)
                padded.append(jnp.pad(x, widths))
        operands = tuple(padded)

    def in_spec(i, x):
        ax = BLOCKED_AXIS.get(i)
        if ax is None:
            zeros = (0,) * x.ndim
            return pl.BlockSpec(x.shape, lambda p, b, _z=zeros: _z)
        if ax == 0:
            return pl.BlockSpec((bm, u), lambda p, b: (b, 0))
        return pl.BlockSpec((n_aps, bm, u), lambda p, b: (0, b, 0))

    out_shapes = [
        jax.ShapeDtypeStruct((1, 1), jnp.float32),       # gamma
        jax.ShapeDtypeStruct((m_pad, u), jnp.float32),   # d beta_up_t
        jax.ShapeDtypeStruct((m_pad, u), jnp.float32),   # d beta_dn_t
        jax.ShapeDtypeStruct((1, u), jnp.float32),       # d p
        jax.ShapeDtypeStruct((1, u), jnp.float32),       # d p_ap
        jax.ShapeDtypeStruct((1, u), jnp.float32),       # d r
    ]
    out_specs = [
        pl.BlockSpec((1, 1), lambda p, b: (0, 0)),
        pl.BlockSpec((bm, u), lambda p, b: (b, 0)),
        pl.BlockSpec((bm, u), lambda p, b: (b, 0)),
        pl.BlockSpec((1, u), lambda p, b: (0, 0)),
        pl.BlockSpec((1, u), lambda p, b: (0, 0)),
        pl.BlockSpec((1, u), lambda p, b: (0, 0)),
    ]
    gamma, d_bu, d_bd, d_p, d_pap, d_r = pl.pallas_call(
        _kernel,
        grid=(2, nb),
        in_specs=[in_spec(i, x) for i, x in enumerate(operands)],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=([pltpu.VMEM((1, u), jnp.float32)] * 4
                        + [pltpu.VMEM((bm, u), jnp.float32)] * 2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=DEFAULT_VMEM_BUDGET),
        interpret=interpret,
    )(*operands)
    if m_pad != m:
        d_bu = d_bu[:m]
        d_bd = d_bd[:m]
    return gamma, d_bu, d_bd, d_p, d_pap, d_r
