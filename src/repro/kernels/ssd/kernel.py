"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU adaptation of the paper's algorithm (DESIGN.md §4): one kernel instance
owns a (batch, head) pair; the chunk axis is the innermost grid dimension
("arbitrary") so the (P, N) f32 state lives in VMEM scratch and is carried
across chunks — the inter-chunk recurrence never touches HBM.  Per chunk the
intra-chunk quadratic term runs on the MXU ((Q,N)@(N,Q) and (Q,Q)@(Q,P)
dots with Q=chunk=128/256, all 128-multiples).

VMEM working set per instance (Q=256, N=128, P=64):
  x,dt,B,C blocks + (Q,Q) decay matrix + (P,N) state ≈ 0.6 MiB ≪ 16 MiB.

Validated in interpret mode against kernels/ssd/ref.py (ssd_chunked and the
sequential recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e30


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, fin_ref,
            state_ref, *, q, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q,)
    a = a_ref[0].astype(jnp.float32)                # scalar
    bc = b_ref[0, 0].astype(jnp.float32)            # (Q, N)
    cc = c_ref[0, 0].astype(jnp.float32)            # (Q, N)
    dd = d_ref[0].astype(jnp.float32)

    da = dt * a
    cs = jnp.cumsum(da)                              # (Q,)
    total = cs[-1]
    xb = dt[:, None] * x                             # (Q, P)

    # intra-chunk: M[i,j] = C_i·B_j · exp(cs_i - cs_j), i >= j
    g = jax.lax.dot_general(cc, bc, (((1,), (1,)), ((), ())))   # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    diff = jnp.where(ii >= jj, cs[:, None] - cs[None, :], NEG_BIG)
    m = jnp.exp(diff) * g
    y = m @ xb                                       # (Q, P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                           # (P, N)
    y = y + jnp.exp(cs)[:, None] * (cc @ state.T)    # (Q,N)@(N,P)

    # state update: S <- e^total · S + Σ_j e^{total-cs_j} xb_j B_j^T
    decay_to_end = jnp.exp(total - cs)               # (Q,)
    s_local = jax.lax.dot_general(
        xb * decay_to_end[:, None], bc, (((0,), (0,)), ((), ())))  # (P, N)
    state_ref[...] = jnp.exp(total) * state + s_local

    y_ref[0, 0, 0] = (y + x * dd).astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _fin():
        fin_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bhcqp(x, dt, a, b, c, d, *, chunk, interpret=False):
    """x (B,H,nc,Q,P); dt (B,H,nc,Q); a (H,); b/c (B,nc,Q,N); d (H,).

    Returns (y (B,H,nc,Q,P), final_state (B,H,P,N))."""
    bt, h, nc, q, p = x.shape
    n = b.shape[-1]

    kernel = functools.partial(_kernel, q=q, n_chunks=nc)
    grid = (bt, h, nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1, 1, q, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, h, nc, q, p), x.dtype),
            jax.ShapeDtypeStruct((bt, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, b, c, d)
