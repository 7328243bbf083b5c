"""NOMA uplink/downlink SINR and achievable rates (paper eqs. 5–11).

SIC semantics:
  uplink (eq. 5): the AP decodes stronger users first, so user i sees
    intra-cell interference from same-cell users with LOWER gain on the same
    subchannel, plus inter-cell interference from every user on that channel
    in other cells.
  downlink (eq. 8): weaker users decode first, so user i sees interference
    from the power components of same-cell users with HIGHER gain, plus other
    APs' total transmit power on the channel.

Subchannel assignment is the relaxed β ∈ [0,1]^{U×M} of the paper
(Corollary 1); rates are Σ_m β_im · (B/M)·log2(1+SINR_im).

SIC orderings depend only on channel gains, which are static per scenario,
so ``Scenario`` precomputes per-channel user orderings grouped by AP;
interference is then a decoded-after suffix sum over the sorted
contributions.  The suffix is evaluated as a masked matvec rather than a
cumsum difference (``end_cs - cs``): the subtraction cancels
catastrophically whenever the in-group suffix is small against the running
global cumsum, and its ±ulp residue lands on the ``max(·, 0)`` tie
nondeterministically — the mask sums only the in-group terms, so an empty
suffix is EXACTLY 0.0 and autodiff's balanced relu tie (0.5) fires
deterministically.  The fused GD-step kernel (kernels/era_step) evaluates
the same masked form; numerical consistency between the two is what lets
its solver regression tests pin rtol=1e-5.  Cost is O(U²·M) against the
cumsum's O(U·M) — at test scale it is noise, and at paper scale the hot
path is the fused kernel, where the mask matvec is an MXU dot.

Every contraction here pins ``Precision.HIGHEST``: TPU's default f32
matmul is one bf16 pass, which would round each interference term to 8
mantissa bits and make the solver's answer depend on the platform.

Batch-safety audit (ligd.solve_batch vmaps this module over a leading cell
axis): every reduction here is over an explicit named axis (cumsum axis=1,
rate sum axis=1, einsum subscripts, segment_sum over the per-cell ``assoc``)
and every gather/scatter indexes with per-cell static orderings, so vmap
lifts all of it cleanly — there are no full-array reductions that would
leak across cells.  The same audit is what makes the cell axis SHARDABLE
(distributed.solver_mesh): under ``shard_map`` nothing here needs a
``psum``/``all_gather`` over the ``cells`` mesh axis — each shard's lanes
are whole cells, so the sharded sweep body is collective-free and devices
never synchronise until the final output gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _suffix_interference(contrib_sorted, group_end):
    """contrib_sorted: (M, U) sorted per SIC order. Returns, per position i,
    the sum of contributions of positions (i, group_end[i]] — i.e. same-cell
    users decoded after i.  Masked matvec, not a cumsum difference — see the
    module docstring for why (exact empty-suffix ties, no cancellation)."""
    u = contrib_sorted.shape[-1]
    idx = jnp.arange(u)
    same = group_end[..., :, None] == group_end[..., None, :]
    later = idx[None, :] > idx[:, None]
    mask = (same & later).astype(contrib_sorted.dtype)
    return jnp.einsum("...ij,...j->...i", mask, contrib_sorted,
                      precision=jax.lax.Precision.HIGHEST)


def uplink_sinr(scn, beta_up, p):
    """beta_up (U, M) in [0,1]; p (U,) watts. Returns SINR (U, M)."""
    cfg = scn.cfg
    own = scn.own_gain_up()                       # (U, M)
    contrib = beta_up * p[:, None] * own          # (U, M) β·p·|h|²

    # intra-cell: suffix sums along the static SIC order
    c_sorted = jnp.take_along_axis(contrib.T, scn.up_order, axis=1)  # (M, U)
    intra_sorted = _suffix_interference(c_sorted, scn.up_group_end)
    intra = jnp.zeros_like(c_sorted).at[
        jnp.arange(c_sorted.shape[0])[:, None], scn.up_order
    ].set(intra_sorted).T                          # back to (U, M)

    # inter-cell: received at AP n from users of OTHER cells, summed
    # cancellation-free over a (U, N) other-cell mask — never as
    # t_all - own_cell, whose f32 residue (~1e-13 W) can exceed the noise
    # floor when one cell holds every user on a channel, and whose ±ulp
    # sign noise makes the zero-interference relu tie nondeterministic
    # (the fused step kernel, kernels/era_step, replicates this exact-tie
    # behaviour; keep the two formulations in sync)
    other = 1.0 - jax.nn.one_hot(scn.assoc, cfg.n_aps,
                                 dtype=contrib.dtype)         # (U, N)
    t_other = jnp.einsum("um,unm,un->nm", beta_up * p[:, None], scn.h_up,
                         other, precision=jax.lax.Precision.HIGHEST)
    inter = jnp.maximum(t_other, 0.0)[scn.assoc]   # (U, M)

    sig = p[:, None] * own
    return sig / (jnp.maximum(intra, 0.0) + inter + scn.env.noise_w)


def downlink_sinr(scn, beta_dn, p_ap):
    """beta_dn (U, M); p_ap (U,) watts (per-user power component at its AP)."""
    cfg = scn.cfg
    own = scn.own_gain_dn()                        # (U, M)
    # intra-cell: components for stronger users, all through user i's gain.
    # The paper's eq. (8) weights each component by the interferer's gain; we
    # follow the standard formulation sum_q β_q P_q · |H_i|² (all signals
    # reach user i through its own channel), which matches eq. (8)'s intent.
    comp = beta_dn * p_ap[:, None]                 # (U, M) power components
    c_sorted = jnp.take_along_axis(comp.T, scn.dn_order, axis=1)
    intra_sorted = _suffix_interference(c_sorted, scn.dn_group_end)
    intra_pwr = jnp.zeros_like(c_sorted).at[
        jnp.arange(c_sorted.shape[0])[:, None], scn.dn_order
    ].set(intra_sorted).T
    intra = intra_pwr * own

    # inter-cell: OTHER APs' total power through the cross gain
    # h_dn[x, i, m], masked per user rather than cross_total - own_ap
    # (see the uplink cancellation note)
    ap_power = jax.ops.segment_sum(comp, scn.assoc,
                                   num_segments=cfg.n_aps)   # (N, M)
    other = 1.0 - jax.nn.one_hot(scn.assoc, cfg.n_aps, dtype=comp.dtype)
    cross = jnp.einsum("nm,num,un->um", ap_power, scn.h_dn, other,
                       precision=jax.lax.Precision.HIGHEST)
    inter = jnp.maximum(cross, 0.0)

    sig = p_ap[:, None] * own
    return sig / (jnp.maximum(intra, 0.0) + inter + scn.env.noise_w)


def rates(scn, beta, sinr, bandwidth=None):
    """Σ_m β·(B/M)·log2(1+SINR) per user. Returns (U,) bits/s."""
    bw = scn.env.subchannel_bw if bandwidth is None else bandwidth
    per_ch = bw * jnp.log2(1.0 + sinr)
    return jnp.sum(beta * per_ch, axis=1)


def uplink_rates(scn, beta_up, p):
    return rates(scn, beta_up, uplink_sinr(scn, beta_up, p))


def downlink_rates(scn, beta_dn, p_ap):
    return rates(scn, beta_dn, downlink_sinr(scn, beta_dn, p_ap))


def sic_feasible(scn, beta_up, p):
    """Uplink SIC decode-threshold constraint p·|h|² > I (paper §II.B):
    users failing it must run device-only.  Evaluated on the hard-assigned
    channel (argmax β)."""
    own = scn.own_gain_up()
    ch = jnp.argmax(beta_up, axis=1)
    gain = jnp.take_along_axis(own, ch[:, None], axis=1)[:, 0]
    return p * gain > scn.env.sic_threshold_w
