"""NOMA SIC rate evaluation as a Pallas TPU kernel — the inner loop of the
ERA scheduler (one evaluation per candidate allocation per admission round).

Grid tiles the subchannel axis; each instance holds (bm, U) operand tiles
in VMEM and evaluates the suffix interference as a same-group/decoded-later
mask matvec (an MXU batched dot; see ref.py for why cumsum differences are
numerically unacceptable here), then the SINR/log2 tail on the VPU — one
VMEM pass instead of five HBM round-trips (mask, dot, add, div, log).
The (bm, U, U) mask is built in-registers from the (bm, U) group-key tile
and never touches HBM; it bounds the tile ladder at U ≈ 512 for bm=8
(8 MiB VMEM) — the paper-scale U=1250 grid needs the channel-tiled
cross-block reduction tracked in ROADMAP (same follow-up as
kernels/era_step).  No data-dependent indexing anywhere in the kernel.

The GD path keeps the pure-jnp implementation (autodiff); this kernel serves
the no-gradient evaluation path (scheduler scoring, benchmarks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(contrib_ref, sig_ref, gend_ref, inter_ref, rate_ref, *, bw):
    contrib = contrib_ref[...].astype(jnp.float32)     # (bm, U)
    sig = sig_ref[...].astype(jnp.float32)
    gend = gend_ref[...]
    inter = inter_ref[...].astype(jnp.float32)

    u = contrib.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (u, u), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (u, u), 1)
    same = gend[:, :, None] == gend[:, None, :]            # (bm, U, U)
    mask = jnp.where(same & (jdx > idx)[None], 1.0, 0.0).astype(jnp.float32)
    intra = jnp.einsum("bij,bj->bi", mask, contrib,
                       preferred_element_type=jnp.float32)
    sinr = sig / (intra + inter)
    rate_ref[...] = (bw * jnp.log2(1.0 + sinr)).astype(rate_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bw", "bm", "interpret"))
def noma_rate(contrib, sig, group_end, inter, *, bw, bm=8, interpret=False):
    """All inputs (M, U) in SIC-sorted order; returns rates (M, U)."""
    m, u = contrib.shape
    bm = min(bm, m)
    grid = (pl.cdiv(m, bm),)
    kernel = functools.partial(_kernel, bw=bw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, u), lambda i: (i, 0))] * 4,
        out_specs=pl.BlockSpec((bm, u), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, u), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(contrib, sig, group_end, inter)
