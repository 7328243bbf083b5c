"""Plain reference of the ERA admission solve (arXiv:2409.16537, §II-§III),
written from the paper's equations in straightforward ``jax.numpy`` with
autodiff, independent of the program under test.

For every candidate split point s = 0..F it runs projected, preconditioned
gradient descent on the relaxed utility Gamma_s (eq. 24), each split
starting from the solved split whose crossing size is nearest (Table I's
loop-iteration warm start), then picks s* = argmin Gamma_s, rounds the
subchannel assignment to one-hot under the per-(AP, channel) user cap, and
sends users that fail the uplink SIC decode threshold to device-only
(s = F).  A re-solve in a later admission round starts from the previous
round's rounded allocation, blended 10% back towards uniform.

Precision: the contractions run at the precision of the caller's
``jax.default_matmul_precision`` context: ``highest`` for the reference,
``high`` (three bf16 passes) for the control.  Where no chip is there to
honour the context (XLA on the CPU contracts float32 in full), ``bits``
rounds every contraction's operands to that many significant bits before
contracting: 16 for the bf16 hi + lo pair of a three-pass product, 8 for
one bf16 pass.

Inputs are the benchmark's own: the scenario's gains, association and SIC
orders (``bench.harness.traffic``), the split profile
(``bench.harness.profiles``), the network numbers and the utility weights
of the configuration file.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Cell(NamedTuple):
    """One cell's solver inputs (unbatched)."""
    h_up: jnp.ndarray        # (U, N, M) uplink gain user -> AP
    h_dn: jnp.ndarray        # (N, U, M) downlink gain AP -> user
    assoc: jnp.ndarray       # (U,) serving AP
    up_order: jnp.ndarray    # (M, U) uplink decode order (sorted position)
    dn_order: jnp.ndarray    # (M, U)
    up_mask: jnp.ndarray     # (U, U) in sorted order: [same AP][j after i]
    dn_mask: jnp.ndarray


class Alloc(NamedTuple):
    beta_up: jnp.ndarray     # (U, M)
    beta_dn: jnp.ndarray
    p: jnp.ndarray           # (U,) device power, W
    p_ap: jnp.ndarray        # (U,) AP power component, W
    r: jnp.ndarray           # (U,) edge compute units


def suffix_mask(group_end) -> np.ndarray:
    """In sorted order, user j interferes with user i iff both are in the
    same AP group and j is decoded after i.  Groups are contiguous in the
    sorted order and the same on every channel (AP is the primary sort
    key), which this checks."""
    ge = np.asarray(group_end)
    if not (ge == ge[:1]).all():
        raise ValueError("SIC groups differ between channels")
    g = ge[0]
    idx = np.arange(g.shape[0])
    return ((g[:, None] == g[None, :])
            & (idx[None, :] > idx[:, None])).astype(np.float32)


def make_cell(scn) -> Cell:
    """The reference's view of a ``Scenario``-shaped input."""
    return Cell(scn.h_up, scn.h_dn, scn.assoc, scn.up_order, scn.dn_order,
                jnp.asarray(suffix_mask(scn.up_group_end)),
                jnp.asarray(suffix_mask(scn.dn_group_end)))


@partial(jax.custom_jvp, nondiff_argnums=(1,))
def _round_bits(x, bits):
    drop = 24 - bits                      # float32 keeps 24 significant bits
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1)
                                                       & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@_round_bits.defjvp
def _round_bits_jvp(bits, primals, tangents):
    # the derivative of rounding is taken as 1, as a lower-precision
    # contraction's is: through the bit pattern it would be 0
    return _round_bits(primals[0], bits), tangents[0]


def round_bits(x, bits):
    """``x`` rounded to ``bits`` significant bits (8: bfloat16; 16: the
    bfloat16 hi + lo pair), to nearest on the bit pattern, so that no
    compiler can fold the rounding away as it may a pair of casts; None
    leaves it as it is.  Gradients pass through the rounding unchanged."""
    if bits is None:
        return x
    if bits not in (8, 16):
        raise ValueError(f"bits must be None, 8 or 16, got {bits!r}")
    return _round_bits(x, bits)


def _einsum(spec, *ops, bits=None):
    return jnp.einsum(spec, *[round_bits(o, bits) for o in ops])


def _own(h_ua, assoc):
    """(U, M) gain between each user and its own AP from (U, N, M)."""
    return jnp.take_along_axis(h_ua, assoc[:, None, None], axis=1)[:, 0, :]


def _suffix(x_um, order, mask, bits=None):
    """Per channel, the sum of ``x`` over same-group users decoded after
    each user, returned in user order."""
    xs = jnp.take_along_axis(x_um.T, order, axis=1)          # (M, U) sorted
    s = _einsum("mj,ij->mi", xs, mask, bits=bits)           # masked sums
    inv = jnp.argsort(order, axis=1)
    return jnp.take_along_axis(s, inv, axis=1).T              # (U, M)


def rates(cell: Cell, alloc: Alloc, net, bits=None):
    """Uplink and downlink rates (eqs. 5-11), bits/s per user."""
    n_aps = cell.h_up.shape[1]
    onehot = jax.nn.one_hot(cell.assoc, n_aps, dtype=jnp.float32)   # (U, N)
    other = 1.0 - onehot
    bw = net["bandwidth_hz"] / net["n_subchannels"]
    noise = 10 ** (net["noise_psd_dbm_hz"] / 10.0) * 1e-3 * bw

    own_up = _own(cell.h_up, cell.assoc)
    bp = alloc.beta_up * alloc.p[:, None]
    intra_up = _suffix(bp * own_up, cell.up_order, cell.up_mask, bits)
    t_other = _einsum("um,unm,un->nm", bp, cell.h_up, other, bits=bits)
    inter_up = jnp.maximum(t_other, 0.0)[cell.assoc]
    sinr_up = (alloc.p[:, None] * own_up
               / (jnp.maximum(intra_up, 0.0) + inter_up + noise))

    own_dn = _own(jnp.swapaxes(cell.h_dn, 0, 1), cell.assoc)
    comp = alloc.beta_dn * alloc.p_ap[:, None]
    intra_dn = _suffix(comp, cell.dn_order, cell.dn_mask, bits) * own_dn
    ap_power = _einsum("um,un->nm", comp, onehot, bits=bits)
    cross = _einsum("nm,num,un->um", ap_power, cell.h_dn, other, bits=bits)
    sinr_dn = (alloc.p_ap[:, None] * own_dn
               / (jnp.maximum(intra_dn, 0.0) + jnp.maximum(cross, 0.0)
                  + noise))

    r_up = jnp.sum(alloc.beta_up * bw * jnp.log2(1.0 + sinr_up), axis=1)
    r_dn = jnp.sum(alloc.beta_dn * bw * jnp.log2(1.0 + sinr_dn), axis=1)
    return r_up, r_dn


def tables(prof):
    """Split-indexed (F+1,) tables: device FLOPs, edge FLOPs, uplink bits
    (raw input at s=0, layer s's output, nothing at s=F) and downlink bits
    (the result, nothing at s=F)."""
    fl = np.asarray(prof["layer_flops"], np.float32)
    dev = np.concatenate([[0.0], np.cumsum(fl)]).astype(np.float32)
    edge = (np.float32(np.sum(fl)) - dev).astype(np.float32)
    up = np.concatenate([[prof["input_bits"]],
                         prof["out_bits"]]).astype(np.float32)
    up[-1] = 0.0
    dn = np.full(fl.shape[0] + 1, prof["result_bits"], np.float32)
    dn[-1] = 0.0
    return dev, edge, up, dn


def terms(cell, alloc, s, q, prof_t, net, w, bits=None):
    """Per-user delay t and energy e, and the utility Gamma (eq. 24)."""
    dev, edge, up, dn = (jnp.asarray(x)[s] for x in prof_t)
    r_up, r_dn = rates(cell, alloc, net, bits)
    lam = alloc.r ** net["lambda_exponent"]
    edge_c = lam * net["c_min_flops"]
    t = (dev / net["c_device_flops"] + edge / edge_c
         + up / jnp.maximum(r_up, 1.0) + dn / jnp.maximum(r_dn, 1.0))
    e = (net["xi_device"] * net["c_device_flops"] ** 2 * dev
         + net["xi_edge"] * edge_c ** 2 * edge
         + alloc.p * up / jnp.maximum(r_up, 1.0)
         + alloc.p_ap * dn / jnp.maximum(r_dn, 1.0))
    rq = jax.nn.sigmoid(w["qoe_a"] * (t / q - 1.0))
    gamma = (w["w_t"] * jnp.sum(t) * w["t_scale"]
             + w["w_q"] * (jnp.sum((t - q) * rq) * w["t_scale"]
                           + jnp.sum(rq))
             + w["w_r"] * (jnp.sum(e) * w["e_scale"]
                           + jnp.sum(lam) * w["r_cost_scale"]))
    return t, e, gamma


def project(alloc: Alloc, net) -> Alloc:
    def simplex(b):
        b = jnp.clip(b, 0.0, 1.0)
        return b / jnp.maximum(b.sum(axis=1, keepdims=True), 1e-9)
    return Alloc(simplex(alloc.beta_up), simplex(alloc.beta_dn),
                 jnp.clip(alloc.p, net["p_min_w"], net["p_max_w"]),
                 jnp.clip(alloc.p_ap, net["ap_p_min_w"], net["ap_p_max_w"]),
                 jnp.clip(alloc.r, net["r_min"], net["r_max"]))


def uniform(net) -> Alloc:
    u, m = net["n_users"], net["n_subchannels"]
    mid = lambda lo, hi: jnp.full((u,), 0.5 * (net[lo] + net[hi]),
                                  jnp.float32)
    return Alloc(jnp.full((u, m), 1.0 / m, jnp.float32),
                 jnp.full((u, m), 1.0 / m, jnp.float32),
                 mid("p_min_w", "p_max_w"), mid("ap_p_min_w", "ap_p_max_w"),
                 mid("r_min", "r_max"))


def soften(alloc: Alloc, m: int, eps: float = 0.1) -> Alloc:
    return alloc._replace(beta_up=(1 - eps) * alloc.beta_up + eps / m,
                          beta_dn=(1 - eps) * alloc.beta_dn + eps / m)


def predecessors(up_bits) -> np.ndarray:
    """Table I's warm start: split s starts from the already-solved split
    whose uplink size is nearest (first wins ties); s = 0 starts from the
    initial point."""
    w = np.asarray(up_bits)
    pred = np.arange(w.shape[0], dtype=np.int32)
    for s in range(1, w.shape[0]):
        pred[s] = int(np.argmin(np.abs(w[s] - w[:s])))
    return pred


# a stop test whose measure lies within this share of its threshold is
# decided by round-off: Gamma's change between late steps is a few ulps of
# Gamma, so two correct solvers may stop a step apart there
NEAR_STOP = 0.25


def _gd(cell, s, q, x0, prof_t, net, w, solver, bits=None):
    """GD for one split point: normalised gradient steps scaled by each
    variable's feasible range, projected; stops when Gamma changes by less
    than tol relative, the gradient norm falls under tol, or after
    max_steps.  Also returns whether any step's stop test lay within
    ``NEAR_STOP`` of its threshold."""
    u = q.shape[0]
    s_vec = jnp.full((u,), s, jnp.int32)
    lr, tol, max_steps = solver["lr"], solver["tol"], solver["max_steps"]
    scales = (1.0, 1.0, net["p_max_w"] - net["p_min_w"],
              net["ap_p_max_w"] - net["ap_p_min_w"],
              net["r_max"] - net["r_min"])

    def loss(a):
        return terms(cell, a, s_vec, q, prof_t, net, w, bits)[2]

    def cond(c):
        return (~c[3]) & (c[2] < max_steps)

    def body(c):
        a, prev, k, _, near = c
        val, g = jax.value_and_grad(loss)(a)
        g = jax.tree.map(lambda x: jnp.where(jnp.isfinite(x), x, 0.0), g)
        gnorm = jnp.sqrt(sum(jnp.sum(x ** 2) for x in g))
        a = project(Alloc(*[x - lr * sc * gx / (gnorm + 1e-12)
                            for x, gx, sc in zip(a, g, scales)]), net)
        change = jnp.abs(val - prev) / (tol * (1.0 + jnp.abs(val)))
        done = (change < 1.0) | (gnorm < tol)
        near = near | (jnp.abs(change - 1.0) < NEAR_STOP) \
            | (jnp.abs(gnorm / tol - 1.0) < NEAR_STOP)
        return a, val, k + 1, done, near

    a, _, k, _, near = jax.lax.while_loop(
        cond, body, (x0, jnp.float32(jnp.inf), jnp.int32(0),
                     jnp.bool_(False), jnp.bool_(False)))
    return a, loss(a), k, near


def _sweep(cell, q, x0, pred, prof_t, net, w, solver, bits=None):
    """Every split point in turn, each GD starting from its predecessor's
    solution (or ``x0`` where the predecessor is itself)."""
    n_s = pred.shape[0]
    buf0 = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_s,) + x.shape),
                        x0)

    def body(buf, xs):
        s, p = xs
        a, g, k, near = _gd(cell, s, q, jax.tree.map(lambda b: b[p], buf),
                            prof_t, net, w, solver, bits)
        buf = jax.tree.map(lambda b, x: b.at[s].set(x), buf, a)
        return buf, (g, k, near)

    buf, (gammas, iters, near) = jax.lax.scan(
        body, buf0, (jnp.arange(n_s, dtype=jnp.int32), jnp.asarray(pred)))
    return buf, gammas, iters, near


def round_beta(beta, assoc, cap):
    """One-hot subchannels: users in order of their strongest preference
    take their most preferred channel that has fewer than ``cap`` users of
    their AP."""
    b = np.asarray(beta)
    assoc = np.asarray(assoc)
    hard = np.zeros_like(b)
    counts = {}
    for i in np.argsort(-b.max(axis=1)):
        for ch in np.argsort(-b[i]):
            key = (int(assoc[i]), int(ch))
            if counts.get(key, 0) < cap:
                counts[key] = counts.get(key, 0) + 1
                hard[i, ch] = 1.0
                break
    return hard


class Outcome(NamedTuple):
    s: np.ndarray               # (U,) split per user
    alloc: Alloc                # rounded allocation (host arrays)
    gamma_by_layer: np.ndarray  # (F+1,)
    iters_by_layer: np.ndarray  # (F+1,)
    soft: Alloc                 # the GD solution at s*, before rounding
    near_stop: np.ndarray       # (F+1,) a stop test within round-off


class Solver:
    """Solves batches of cells (a leading lane axis on every input) with
    one compiled sweep."""

    def __init__(self, prof, net, weights, solver, bits=None):
        self.prof_t = tables(prof)
        self.pred = predecessors(self.prof_t[2])
        self.f = int(np.asarray(prof["layer_flops"]).shape[0])
        self.net, self.w, self.solver = dict(net), dict(weights), dict(solver)
        self._sweep = jax.jit(jax.vmap(partial(
            _sweep, pred=self.pred, prof_t=self.prof_t, net=self.net,
            w=self.w, solver=self.solver, bits=bits)))
        self._grad = jax.jit(jax.vmap(jax.value_and_grad(
            lambda a, cell, s, q: terms(cell, a, s, q, self.prof_t, self.net,
                                        self.w, bits)[2])))
        self._final = jax.jit(jax.vmap(self._final_one))

    def _final_one(self, cell, hard, s_user):
        """Users that fail the uplink SIC decode threshold on their
        rounded subchannel run device-only (s = F)."""
        own_up = _own(cell.h_up, cell.assoc)
        ch = jnp.argmax(hard.beta_up, axis=1)
        gain = jnp.take_along_axis(own_up, ch[:, None], axis=1)[:, 0]
        feasible = hard.p * gain > self.net["sic_threshold_w"]
        return jnp.where(feasible, s_user, self.f)

    def value_and_grad(self, cells, s, q, alloc):
        """Gamma and its gradient by the allocation, per lane, at split
        ``s`` (B, U) — what one GD step of the program computes."""
        return self._grad(alloc, cells, s, q)

    def solve(self, cells, q, x0):
        """``cells``: a batched ``Cell``; ``q``: (B, U); ``x0``: a batched
        ``Alloc``.  Returns one ``Outcome`` per lane."""
        buf, gammas, iters, near = self._sweep(cells, q, x0)
        gammas, iters = np.asarray(gammas), np.asarray(iters)
        near = np.asarray(near)
        s_star = np.argmin(gammas, axis=1)
        n = gammas.shape[0]
        soft = [jax.tree.map(lambda x, b=b: x[b, int(s_star[b])], buf)
                for b in range(n)]
        cap = int(self.net["max_users_per_channel"])
        hard = [Alloc(jnp.asarray(round_beta(a.beta_up, cells.assoc[b], cap)),
                      jnp.asarray(round_beta(a.beta_dn, cells.assoc[b], cap)),
                      a.p, a.p_ap, a.r) for b, a in enumerate(soft)]
        hard_b = jax.tree.map(lambda *xs: jnp.stack(xs), *hard)
        u = q.shape[1]
        s_user = jnp.asarray(np.repeat(s_star[:, None], u, 1), jnp.int32)
        s_fin = np.asarray(self._final(cells, hard_b, s_user))
        return [Outcome(s_fin[b], jax.tree.map(np.asarray, hard[b]),
                        gammas[b], iters[b],
                        jax.tree.map(np.asarray, soft[b]), near[b])
                for b in range(n)]
