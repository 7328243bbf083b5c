"""ERA utility: inference delay (eq. 12), energy (eq. 22), QoE terms
(16,17) and the weighted objective Γ (eqs. 24–27).

Variables per user i (paper §II.E):
  s_i      split point               — discrete, handled by the Li-GD layer loop
  β_up/β_dn subchannel assignment    — relaxed to [0,1]^{U×M} (Corollary 1)
  p_i      device uplink tx power    — continuous in [p_min, p_max]
  P_i      AP downlink power share   — continuous in [P_min, P_max]
  r_i      edge compute units        — continuous in [r_min, r_max]

λ(r) = r^lambda_exponent models nonlinear multi-unit scaling (paper [18];
TPU adaptation per DESIGN.md §4).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import noma, qoe


class Allocation(NamedTuple):
    beta_up: jnp.ndarray  # (U, M)
    beta_dn: jnp.ndarray  # (U, M)
    p: jnp.ndarray        # (U,)
    p_ap: jnp.ndarray     # (U,)
    r: jnp.ndarray        # (U,)


@dataclass(frozen=True)
class Weights:
    """ω_T + ω_Q + ω_R = 1 (eq. 24)."""
    w_t: float = 0.4
    w_q: float = 0.3
    w_r: float = 0.3
    qoe_a: float = qoe.DEFAULT_A
    # scale normalisers so the three addends are commensurate
    t_scale: float = 1.0       # seconds -> utility units
    e_scale: float = 1.0
    r_cost_scale: float = 0.01


def lam(r, cfg):
    """λ(r): effective compute multiple of r allocated units.

    ``cfg`` is anything with a ``lambda_exponent`` attribute — a
    ``NetworkConfig`` on host paths, a ``CellEnv`` inside traced code."""
    return r ** cfg.lambda_exponent


def uniform_alloc(scn, rng=None):
    """Feasible uninformed starting point (paper Table I line 1)."""
    cfg, env = scn.cfg, scn.env
    u, m = cfg.n_users, cfg.n_subchannels
    if rng is not None:
        b_up = jax.random.uniform(rng, (u, m))
        b_dn = jax.random.uniform(jax.random.fold_in(rng, 1), (u, m))
        b_up = b_up / b_up.sum(1, keepdims=True)
        b_dn = b_dn / b_dn.sum(1, keepdims=True)
    else:
        b_up = jnp.full((u, m), 1.0 / m)
        b_dn = jnp.full((u, m), 1.0 / m)
    mid = lambda lo, hi: jnp.full((u,), 0.5 * (lo + hi))
    return Allocation(b_up, b_dn, mid(env.p_min_w, env.p_max_w),
                      mid(env.ap_p_min_w, env.ap_p_max_w),
                      mid(env.r_min, env.r_max))


def delay_terms(scn, prof, s, alloc):
    """Per-user (T_device, T_server, T_up, T_down), each (U,) seconds.

    ``s``: (U,) int32 split points in {0..F}."""
    env = scn.env
    dev_fl = prof.device_flops[s]
    edge_fl = prof.edge_flops[s]
    w_up = prof.uplink_bits[s]
    w_dn = prof.downlink_bits[s]

    r_up = noma.uplink_rates(scn, alloc.beta_up, alloc.p)
    r_dn = noma.downlink_rates(scn, alloc.beta_dn, alloc.p_ap)

    t_dev = dev_fl / env.c_device_flops
    t_srv = edge_fl / (lam(alloc.r, env) * env.c_min_flops)
    t_up = w_up / jnp.maximum(r_up, 1.0)
    t_dn = w_dn / jnp.maximum(r_dn, 1.0)
    return t_dev, t_srv, t_up, t_dn, r_up, r_dn


def energy(scn, prof, s, alloc, r_up, r_dn):
    """Per-user energy E_i (eq. 22), joules."""
    env = scn.env
    dev_fl = prof.device_flops[s]
    edge_fl = prof.edge_flops[s]
    w_up = prof.uplink_bits[s]
    w_dn = prof.downlink_bits[s]

    # eq. (18)/(21): E = ξ · c² · f  (power ξc³ × time f/c); ξ calibrated so
    # device inference costs O(0.1 J/GFLOP) and the edge pays quadratically
    # for allocating faster effective compute λ(r)·c_min — the paper's
    # resource/latency tension.
    e_dev = env.xi_device * (env.c_device_flops ** 2) * dev_fl
    edge_c = lam(alloc.r, env) * env.c_min_flops
    e_edge = env.xi_edge * (edge_c ** 2) * edge_fl
    e_up = alloc.p * w_up / jnp.maximum(r_up, 1.0)
    e_dn = alloc.p_ap * w_dn / jnp.maximum(r_dn, 1.0)
    return e_dev + e_edge + e_up + e_dn


class Terms(NamedTuple):
    t: jnp.ndarray        # (U,) latency
    e: jnp.ndarray        # (U,) energy
    c: jnp.ndarray        # scalar smooth ΣDCT
    z: jnp.ndarray        # scalar expected violators
    gamma: jnp.ndarray    # scalar utility Γ


def utility(scn, prof, s, alloc, q_thresh, w: Weights) -> Terms:
    """Γ = ω_T ΣT + ω_Q (C + z) + ω_R (ΣE + Σλ(r))   (eqs. 24–27).

    q_thresh: (U,) per-user QoE latency thresholds Q_i (seconds).

    Batch-safe: the Σ reductions run over the per-cell user axis of
    unbatched (U,)/(U,M) operands, so under ``vmap`` (ligd.solve_batch)
    each cell's Γ stays independent — nothing sums across cells.  Shard-
    safe for the same reason: under ``shard_map`` over the ``cells`` mesh
    axis (distributed.solver_mesh) no Γ term needs a cross-device
    collective — the cell axis partitions cleanly."""
    t_dev, t_srv, t_up, t_dn, r_up, r_dn = delay_terms(scn, prof, s, alloc)
    t = t_dev + t_srv + t_up + t_dn
    e = energy(scn, prof, s, alloc, r_up, r_dn)
    c, z = qoe.system_qoe(t, q_thresh, w.qoe_a)
    gamma = (w.w_t * jnp.sum(t) * w.t_scale
             + w.w_q * (c * w.t_scale + z)
             + w.w_r * (jnp.sum(e) * w.e_scale
                        + jnp.sum(lam(alloc.r, scn.env)) * w.r_cost_scale))
    return Terms(t, e, c, z, gamma)


def clip_alloc(scn, alloc: Allocation) -> Allocation:
    """Projection onto the feasible box + β row-simplex (Σ_m β = 1)."""
    env = scn.env

    def simplex(b):
        b = jnp.clip(b, 0.0, 1.0)
        return b / jnp.maximum(b.sum(axis=1, keepdims=True), 1e-9)

    with jax.named_scope("era.clip_alloc"):
        return Allocation(
            beta_up=simplex(alloc.beta_up),
            beta_dn=simplex(alloc.beta_dn),
            p=jnp.clip(alloc.p, env.p_min_w, env.p_max_w),
            p_ap=jnp.clip(alloc.p_ap, env.ap_p_min_w, env.ap_p_max_w),
            r=jnp.clip(alloc.r, env.r_min, env.r_max),
        )


def round_beta(scn, alloc: Allocation, cap=None) -> Allocation:
    """Discretise β to one-hot (paper Table I line 19), honouring the
    ≤ max_users_per_channel cap per (AP, channel) greedily.

    Host-side (NumPy) by design — the greedy cap is sequential.  In the
    batched solver this runs once per cell AFTER the vmapped GD sweep, so
    it stays off the compiled hot path."""
    cfg = scn.cfg
    cap = cfg.max_users_per_channel if cap is None else cap

    def harden(beta):
        import numpy as np
        b = np.asarray(beta)
        assoc = np.asarray(scn.assoc)
        u, m = b.shape
        counts = {}
        hard = np.zeros_like(b)
        # strongest preference first
        order = np.argsort(-b.max(axis=1))
        for i in order:
            for ch in np.argsort(-b[i]):
                key = (int(assoc[i]), int(ch))
                if counts.get(key, 0) < cap:
                    counts[key] = counts.get(key, 0) + 1
                    hard[i, ch] = 1.0
                    break
        return jnp.asarray(hard)

    return alloc._replace(beta_up=harden(alloc.beta_up),
                          beta_dn=harden(alloc.beta_dn))
