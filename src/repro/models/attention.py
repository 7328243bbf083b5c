"""Causal attention: GQA/MQA, RoPE / M-RoPE, global + sliding-window, with a
naive path (tests), a chunked path (32k+ prefill without an S×S buffer), and a
ring-buffer KV-cache decode step.

Sharding intent (constraint applied by the caller / transformer.py):
  activations (B, S, D): B -> data, S -> model between blocks (sequence
  parallelism); inside attention the head dim carries the model axis
  (Megatron tensor parallelism) — GSPMD inserts the boundary collectives.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.common import apply_mrope, apply_rope, dense_init

NEG_INF = -2.0e38


def init(key, cfg):
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    p = {
        "wq": dense_init(keys[0], (d, h, hd), dt),
        "wk": dense_init(keys[1], (d, k, hd), dt),
        "wv": dense_init(keys[2], (d, k, hd), dt),
        "wo": dense_init(keys[3], (h, hd, d), dt, in_axis_size=h * hd),
    }
    if cfg.attn_qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dt)
        p["bk"] = jnp.zeros((k, hd), dt)
        p["bv"] = jnp.zeros((k, hd), dt)
    return p


def _rope(cfg, x, positions):
    if cfg.mrope_sections is not None:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def _project_qkv(params, cfg, x, positions):
    """x (B,S,D) -> q (B,S,H,hd), k/v (B,S,K,hd), RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.attn_qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    return q, k, v


def _sdpa(q, k, v, mask, scale):
    """q (B,S,H,hd), k/v (B,T,H,hd) (kv already head-expanded), mask
    broadcastable to (B,1,S,T).

    GQA is expressed by repeating kv heads to H rather than grouping q into
    (K,G): the grouped reshape of a model-axis-sharded H dim is not
    GSPMD-shardable when K < mesh model size, which replicated the S×T score
    tensor per chip (observed 0.8 GiB/chip/chunk on dbrx).  The Pallas flash
    kernel does native grouping on real TPUs."""
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    scores = _SCORE_CONSTRAIN[0](scores, "attn_scores")
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# module-level score-sharding hook, set by the distributed layer for archs
# whose head count doesn't divide the model axis (musicgen 24H): sharding
# the key axis of the scores splits the otherwise-replicated attention
# compute (context parallelism).  Default: identity.
_SCORE_CONSTRAIN = [lambda x, name: x]


def set_score_constrain(fn):
    _SCORE_CONSTRAIN[0] = fn or (lambda x, name: x)


def _expand_kv(k, n_heads):
    """(B,T,K,hd) -> (B,T,H,hd) by repeating each kv head H//K times."""
    reps = n_heads // k.shape[2]
    return jnp.repeat(k, reps, axis=2) if reps > 1 else k


def _noop(x, name):
    return x


def _attend(cfg, q, k, v, window, scale, impl, q_chunk, constrain=_noop):
    b, s, h, hd = q.shape
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                      scale=scale)
    if impl == "naive" or s <= q_chunk:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(s)[None, :]
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        kf = constrain(_expand_kv(k, h), "heads")
        vf = constrain(_expand_kv(v, h), "heads")
        return _sdpa(constrain(q, "heads"), kf, vf, mask[None, None], scale)
    if impl == "chunked":
        return _chunked_forward(cfg, q, k, v, window, scale, q_chunk,
                                constrain)
    if impl == "chunked_tri":
        return _chunked_tri_forward(cfg, q, k, v, window, scale, q_chunk,
                                    constrain)
    raise ValueError(impl)


def _chunked_tri_forward(cfg, q, k, v, window, scale, q_chunk,
                         constrain=_noop):
    """Triangular chunked attention: an unrolled Python loop over query
    chunks with STATIC key slices k[:, :(i+1)·qc], so the causal upper
    triangle is never computed (the scan-based ``chunked`` path scores each
    chunk against the full key range and masks — ~2× attention FLOPs).
    Trade-off: HLO grows with n_chunks (no scan), so compile time rises;
    a §Perf iteration lever."""
    b, s, h, hd = q.shape
    qc = min(q_chunk, s)
    n_chunks = s // qc
    assert s % qc == 0, (s, qc)
    k = constrain(_expand_kv(k, h), "heads")
    v = constrain(_expand_kv(v, h), "heads")
    q = constrain(q, "heads")

    outs = []
    for i in range(n_chunks):
        q_i = q[:, i * qc:(i + 1) * qc]
        hi = (i + 1) * qc
        s0 = max(0, hi - min(s, window + qc)) if window else 0
        k_i, v_i = k[:, s0:hi], v[:, s0:hi]
        qpos = i * qc + jnp.arange(qc)[:, None]
        kpos = s0 + jnp.arange(hi - s0)[None, :]
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        outs.append(_sdpa(q_i, k_i, v_i, mask[None, None], scale))
    return jnp.concatenate(outs, axis=1)


def forward(params, cfg, x, positions, mixer="attn", impl="naive",
            q_chunk=1024, constrain=_noop):
    """Full-sequence causal attention (training / prefill).

    mixer: "attn" (global) or "local" (sliding window of cfg.window).
    impl:  "naive" (S×S scores — small inputs / tests)
           "chunked" (scan over query chunks — long-context prefill)
           "pallas" (flash-attention kernel; interpret mode on CPU)
    """
    hd = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)
    q, k, v = _project_qkv(params, cfg, x, positions)
    window = cfg.window if mixer == "local" else 0
    out = _attend(cfg, q, k, v, window, scale, impl, q_chunk, constrain)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def prefill(params, cfg, x, positions, max_seq, mixer="attn", impl="naive",
            q_chunk=1024, constrain=_noop):
    """Forward + ring-buffer cache capture for subsequent decode."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)
    q, k, v = _project_qkv(params, cfg, x, positions)
    window = cfg.window if mixer == "local" else 0
    out = _attend(cfg, q, k, v, window, scale, impl, q_chunk, constrain)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])

    size = min(max_seq, cfg.window) if mixer == "local" else max_seq
    n_keep = min(s, size)
    p0 = s - n_keep + jnp.arange(n_keep, dtype=jnp.int32)   # positions kept
    # position p lives in ring slot p % size, so the kept run fills slots
    # start, start+1, ... (mod size) — all static: write it at slot 0 and
    # roll, rather than scatter (XLA:TPU's scatter fusion aborts on the
    # scatter form inside the prefill scan)
    start = (s - n_keep) % size

    def ring(buf, new, axis):
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), 0, axis)
        return jnp.roll(buf, start, axis)

    cache = init_cache(cfg, b, max_seq, mixer=mixer, dtype=k.dtype)
    cache = {
        "k": ring(cache["k"], k[:, -n_keep:], 1),
        "v": ring(cache["v"], v[:, -n_keep:], 1),
        "pos": ring(cache["pos"], p0, 0),
    }
    return y, cache


def _chunked_forward(cfg, q, k, v, window, scale, q_chunk, constrain=_noop):
    """Scan over query chunks. Local attention slices a (window + qc) key band
    so compute is O(S·W); global attention scores each chunk against the full
    key range (O(S²) with causal masking — the Pallas kernel is the TPU path
    that skips the masked half)."""
    b, s, h, hd = q.shape
    qc = min(q_chunk, s)
    n_chunks = s // qc
    assert s % qc == 0, (s, qc)
    k = constrain(_expand_kv(k, h), "heads")
    v = constrain(_expand_kv(v, h), "heads")
    qs = jnp.moveaxis(constrain(q, "heads").reshape(b, n_chunks, qc, h, hd),
                      1, 0)

    band = s if not window else min(s, window + qc)

    def chunk(i, q_i):
        q0 = i * qc
        qpos = q0 + jnp.arange(qc)[:, None]
        if window:
            s0 = jnp.clip(q0 + qc - band, 0, s - band)
            k_i = jax.lax.dynamic_slice_in_dim(k, s0, band, axis=1)
            v_i = jax.lax.dynamic_slice_in_dim(v, s0, band, axis=1)
            kpos = s0 + jnp.arange(band)[None, :]
            mask = (kpos <= qpos) & (kpos > qpos - window)
        else:
            k_i, v_i = k, v
            kpos = jnp.arange(s)[None, :]
            mask = kpos <= qpos
        return _sdpa(q_i, k_i, v_i, mask[None, None], scale)

    def body(carry, inp):
        i, q_i = inp
        return carry, chunk(i, q_i)

    _, outs = jax.lax.scan(body, 0, (jnp.arange(n_chunks), qs))
    # outs: (nc, B, qc, H, hd) -> (B, S, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, hd)


# --------------------------------------------------------------------------- #
# decode with ring-buffer KV cache
# --------------------------------------------------------------------------- #
def init_cache(cfg, batch, max_seq, mixer="attn", dtype=None):
    """Ring-buffer cache. Local mixers only keep ``window`` keys."""
    dt = dtype or jnp.dtype(cfg.dtype)
    size = min(max_seq, cfg.window) if mixer == "local" else max_seq
    kd, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, size, kd, hd), dt),
        "v": jnp.zeros((batch, size, kd, hd), dt),
        "pos": jnp.full((size,), -1, jnp.int32),
    }


def decode_step(params, cfg, x, pos, cache, mixer="attn", constrain=_noop):
    """x (B,1,D); pos: scalar int32 absolute position; returns (y, cache)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(pos[None, None, None], (b, 3, 1)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    size = cache["k"].shape[1]
    idx = (pos % size).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, idx, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos[None].astype(jnp.int32), idx, axis=0)

    window = cfg.window if mixer == "local" else 0
    valid = (cpos >= 0) & (cpos <= pos)
    if window:
        valid &= cpos > pos - window
    kf = constrain(_expand_kv(ck, cfg.n_heads), "heads_decode")
    vf = constrain(_expand_kv(cv, cfg.n_heads), "heads_decode")
    out = _sdpa(constrain(q, "heads_decode"), kf, vf,
                valid[None, None, None, :], scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": ck, "v": cv, "pos": cpos}
