"""Flash attention for TPU via pl.pallas_call.

Design (TPU-native, MXU/VMEM-aware — DESIGN.md §4):
  grid = (batch·q_heads, S/bq, T/bk); the kv-block axis is the innermost
  ("arbitrary") dimension so the f32 running max / sum / accumulator scratch
  persists across kv blocks (online softmax), while (bh, iq) parallelise.
  Block shapes default to (bq, d) = (512, head_dim) and bk = 512: the
  working set q + k + v + acc ≈ 512·128·(2+2+2+4) B ≈ 640 KiB ≪ 16 MiB
  VMEM, and 128-multiple tile dims keep the MXU fed.
  GQA is native: the kv BlockSpec index_map folds the q-head -> kv-head
  mapping (h // group), so no repeated-KV materialisation.
  Causal/sliding-window masking is applied per block from program ids;
  fully-masked blocks are skipped with pl.when.

Validated in interpret mode against ref.attention_ref (CPU container);
TPU is the target.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, bq, bk, n_kb, causal, window, seq_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = iq * bq
    k0 = ik * bk
    # block-level reachability: lowest q pos attends back to q0 - window + 1
    reachable = True
    if causal:
        reachable = k0 <= q0 + bq - 1
    if window:
        reachable = reachable & (k0 + bk - 1 > q0 - window)

    @pl.when(reachable)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ik == n_kb - 1)
    def _fin():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0, scale=None,
                         bq=512, bk=512, interpret=False):
    """q (BH, S, D); k/v (BKH, T, D) with BH % BKH == 0 (GQA folded by the
    caller into the leading axis ordering: h-major within each batch)."""
    bh, s, d = q.shape
    bkh, t, _ = k.shape
    group = bh // bkh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq_ = min(bq, s)
    bk_ = min(bk, t)
    n_kb = pl.cdiv(t, bk_)
    grid = (bh, pl.cdiv(s, bq_), n_kb)

    kernel = functools.partial(
        _kernel, scale=scale, bq=bq_, bk=bk_, n_kb=n_kb,
        causal=causal, window=window, seq_len=t)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, iq, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, iq, ik, g=group: (b // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),      # running max m
            pltpu.VMEM((bq_,), jnp.float32),      # running sum l
            pltpu.VMEM((bq_, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
