"""RG-LRU gated linear recurrence (h_t = a_t·h_{t-1} + b_t) as a Pallas TPU
kernel — the Griffin/RecurrentGemma hot loop.

TPU adaptation: XLA's associative_scan materialises O(log L) full-sequence
intermediates in HBM; this kernel streams (Lc, bd) tiles through VMEM with
the (bd,) hidden state in scratch, so HBM traffic is exactly read(a,b) +
write(h) — the bandwidth floor.  Grid = (B, D/bd, L/Lc), the L axis
innermost/"arbitrary" so the state persists across chunks; bd = 128-lane
multiples keep the VPU dense.

Validated in interpret mode against ref.linear_scan_sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_ref, state_ref, *, lc):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    def body(i, h):
        h = a_ref[0, i, :] * h + b_ref[0, i, :]
        h_ref[0, i, :] = h.astype(h_ref.dtype)
        return h

    state_ref[...] = jax.lax.fori_loop(0, lc, body, state_ref[...])


@functools.partial(jax.jit, static_argnames=("lc", "bd", "interpret"))
def rglru_scan(a, b, *, lc=256, bd=256, interpret=False):
    """a, b: (B, L, D) f32. Returns h (B, L, D)."""
    bt, l, d = a.shape
    lc = min(lc, l)
    bd = min(bd, d)
    assert l % lc == 0 and d % bd == 0, (l, lc, d, bd)
    grid = (bt, d // bd, pl.cdiv(l, lc))
    kernel = functools.partial(_kernel, lc=lc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, lc, bd), lambda ib, id_, il: (ib, il, id_)),
            pl.BlockSpec((1, lc, bd), lambda ib, id_, il: (ib, il, id_)),
        ],
        out_specs=pl.BlockSpec((1, lc, bd), lambda ib, id_, il: (ib, il, id_)),
        out_shape=jax.ShapeDtypeStruct((bt, l, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((bd,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
