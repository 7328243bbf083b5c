"""Traffic generation for the benchmark's cells, driven by the numbers in a
``bench/traffic/<mix>.json`` file and seeded from ``--seed``.

Three generators, each independent of the program's own copies (the
program's ``core.network.make_scenario`` / ``evolve_scenario`` and
``loadgen``'s Poisson traces are the originals; these are the yardstick's
copies, so a change to the program cannot move the traffic):

* ``ChannelChains`` — per cell, the initial channel snapshot (users uniform
  over the area, APs on a jittered grid, path loss times unit-mean
  exponential fading, nearest-AP association) and a Gauss-Markov drift
  chain ``h' = rho*h + (1-rho)*fresh*mean_m(h)``, with the per-channel SIC
  decode orders the scenario carries (grouped by AP, descending own-AP gain
  uplink, ascending downlink).  All of it is made on the device in one
  jitted call per cell; the orders are sorted there too.
* ``PoissonArrivals`` — an open-loop Poisson stream over all cells of a
  deployment: each arrival names a cell, a user and a QoE deadline drawn
  uniformly from the mix's ``deadline_s`` range.
* ``chat_round`` — one closed-loop serving round: each user re-posts its
  deadline with the mix's probability, and every user sends a prompt of
  random token ids.
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np


def jax_key(seed: int, *path: int):
    """A JAX key for ``seed`` (any size: the high bits are folded in, since
    a 32-bit key cannot hold the larger seeds) and a path of stream ids."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for p in path:
        key = jax.random.fold_in(key, int(p))
    return key


def np_rng(seed: int, *path: int) -> np.random.Generator:
    """A numpy generator for ``seed`` and a path of stream ids."""
    return np.random.default_rng([int(seed), *map(int, path)])


# ---------------------------------------------------------------- channels
def _ap_grid(n_aps: int, area_m: float) -> np.ndarray:
    g = int(np.ceil(np.sqrt(n_aps)))
    grid = np.stack(np.meshgrid(np.linspace(0.15, 0.85, g),
                                np.linspace(0.15, 0.85, g)),
                    -1).reshape(-1, 2)[:n_aps] * area_m
    return grid.astype(np.float32)


def _orders(h_up, h_dn, assoc):
    """Per-channel SIC orders and group ends, sorted on the device.

    Users are grouped by serving AP (primary key) and ordered by own-AP
    gain within the group: descending uplink, ascending downlink.  The
    group of sorted position k ends at the last position with the same AP,
    which is the same on every channel because AP is the primary key."""
    import jax.numpy as jnp
    n_users = assoc.shape[0]
    n_ch = h_up.shape[-1]
    own_up = jnp.take_along_axis(h_up, assoc[:, None, None], axis=1)[:, 0, :]
    own_dn = jnp.take_along_axis(jnp.swapaxes(h_dn, 0, 1),
                                 assoc[:, None, None], axis=1)[:, 0, :]
    ap = jnp.broadcast_to(assoc[None, :], (n_ch, n_users))
    up_order = jnp.lexsort((-own_up.T, ap), axis=-1).astype(jnp.int32)
    dn_order = jnp.lexsort((own_dn.T, ap), axis=-1).astype(jnp.int32)
    sorted_ap = jnp.sort(assoc)
    n_aps = h_up.shape[1]
    last = jnp.cumsum(jnp.bincount(assoc, length=n_aps)) - 1
    group_end = jnp.broadcast_to(last[sorted_ap][None, :],
                                 (n_ch, n_users)).astype(jnp.int32)
    return up_order, group_end, dn_order, group_end


def _chain(key, n_users, n_aps, n_ch, area_m, ref_distance_m, path_loss_exp,
           rho, steps):
    """Initial snapshot plus ``steps - 1`` Gauss-Markov drift steps, stacked
    on a leading step axis."""
    import jax
    import jax.numpy as jnp
    ku, _, kf_up, kf_dn = jax.random.split(key, 4)
    users = jax.random.uniform(ku, (n_users, 2), minval=0.0, maxval=area_m)
    aps = jnp.asarray(_ap_grid(n_aps, area_m))
    d = jnp.linalg.norm(users[:, None, :] - aps[None, :, :], axis=-1)
    d = jnp.maximum(d, ref_distance_m)
    path_loss = d ** (-path_loss_exp)
    assoc = jnp.argmin(d, axis=1).astype(jnp.int32)
    h_up = path_loss[:, :, None] * jax.random.exponential(
        kf_up, (n_users, n_aps, n_ch))
    h_dn = jnp.swapaxes(path_loss, 0, 1)[:, :, None] * jax.random.exponential(
        kf_dn, (n_aps, n_users, n_ch))

    def drift(carry, k):
        up, dn = carry
        k_up, k_dn = jax.random.split(jax.random.fold_in(key, 1000 + k))
        up = rho * up + (1 - rho) * jax.random.exponential(
            k_up, up.shape) * jnp.mean(up, axis=-1, keepdims=True)
        dn = rho * dn + (1 - rho) * jax.random.exponential(
            k_dn, dn.shape) * jnp.mean(dn, axis=-1, keepdims=True)
        return (up, dn), (up, dn)

    _, (ups, dns) = jax.lax.scan(drift, (h_up, h_dn),
                                 jnp.arange(steps - 1))
    ups = jnp.concatenate([h_up[None], ups])
    dns = jnp.concatenate([h_dn[None], dns])
    orders = jax.vmap(lambda u, dn: _orders(u, dn, assoc))(ups, dns)
    return assoc, ups, dns, orders


class ChannelChains:
    """Per-cell channel snapshots: step 0 is the cell's initial scenario,
    steps 1.. its drift chain.  ``scenario(cell, i)`` wraps step
    ``i mod steps`` as the program's ``Scenario``; a walk past the end wraps
    round to step 0, which is one more (larger) drift step."""

    def __init__(self, seed: int, net_cfg, n_cells: int, steps: int,
                 rho: float):
        import jax
        from repro.core import network
        self.steps = int(steps)
        self.n_cells = int(n_cells)
        self.net_cfg = net_cfg
        make = jax.jit(partial(
            _chain, n_users=net_cfg.n_users, n_aps=net_cfg.n_aps,
            n_ch=net_cfg.n_subchannels, area_m=net_cfg.area_m,
            ref_distance_m=net_cfg.ref_distance_m,
            path_loss_exp=net_cfg.path_loss_exp, rho=float(rho),
            steps=self.steps))
        unstack = jax.jit(lambda *xs: [[x[i] for x in xs]
                                       for i in range(self.steps)])
        self._scns = []
        for b in range(self.n_cells):
            assoc, ups, dns, (uo, uge, do, dge) = make(jax_key(seed, 1, b))
            per_step = unstack(ups, dns, uo, uge, do, dge)
            self._scns.append([
                network.Scenario(cfg=net_cfg, assoc=assoc, h_up=s[0],
                                 h_dn=s[1], up_order=s[2], up_group_end=s[3],
                                 dn_order=s[4], dn_group_end=s[5])
                for s in per_step])

    def scenario(self, cell: int, i: int):
        return self._scns[cell][i % self.steps]

    def block_until_ready(self):
        import jax
        jax.block_until_ready([[s.h_up for s in c] for c in self._scns])


# ---------------------------------------------------------------- arrivals
class PoissonArrivals:
    """Open-loop Poisson arrivals at ``rate_per_cell`` per cell, over
    ``n_cells`` cells of ``n_users`` users, on a stream clock that starts
    at 0.  ``due_by(t)`` returns, in due order, every arrival due at or
    before ``t`` that has not been returned yet: ``(due_s, cell, user,
    q_s)``.  The sequence depends on the seed alone, not on when it is
    read."""

    def __init__(self, seed: int, rate_per_cell: float, n_cells: int,
                 n_users: int, deadline_s):
        self.rng = np_rng(seed, 2)
        self.rate = float(rate_per_cell) * n_cells
        self.n_cells, self.n_users = int(n_cells), int(n_users)
        self.lo, self.hi = map(float, deadline_s)
        self._next = self.rng.exponential(1.0 / self.rate)

    def due_by(self, t: float):
        out = []
        while self._next <= t:
            out.append((self._next, int(self.rng.integers(self.n_cells)),
                        int(self.rng.integers(self.n_users)),
                        float(self.rng.uniform(self.lo, self.hi))))
            self._next += self.rng.exponential(1.0 / self.rate)
        return out


# -------------------------------------------------------------------- chat
def chat_round(rng: np.random.Generator, n_cells: int, n_users: int,
               repost_prob: float, deadline_s, prompt_len: int, vocab: int):
    """One closed-loop serving round: ``(reposts, prompts)`` with
    ``reposts`` a list of ``(cell, user, q_s)`` and ``prompts`` a
    ``(n_cells, n_users, prompt_len)`` int32 array of token ids."""
    lo, hi = map(float, deadline_s)
    post = rng.random((n_cells, n_users)) < repost_prob
    q = rng.uniform(lo, hi, (n_cells, n_users))
    reposts = [(int(c), int(u), float(q[c, u]))
               for c, u in zip(*np.nonzero(post))]
    prompts = rng.integers(0, vocab, (n_cells, n_users, prompt_len),
                           dtype=np.int32)
    return reposts, prompts


def percentile(values, p: float) -> float:
    """The ``p``-th percentile by the nearest-rank rule: the smallest
    sample with at least ``p`` percent of the samples at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    k = max(1, math.ceil(p / 100.0 * len(v)))
    return float(v[k - 1])
