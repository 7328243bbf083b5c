"""Plain reference forward of a dense GQA decoder (InternLM2, arXiv:2403.17297)
in float32, written from the architecture's equations, independent of the
program under test:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                q, k, v = h Wq, h Wk, h Wv      (K kv heads, query head i
                                                 reads kv head i // (H/K))
                rotary position embedding on q and k (half-split pairs,
                frequencies theta^(-2j/hd))
                x += softmax(q k^T / sqrt(hd) + causal mask) v Wo
                h = rmsnorm(x) * norm2
                x += (silu(h Wgate) * (h Win)) Wout
    logits = (rmsnorm(x) * final_norm) lm_head

It runs layer by layer over the benchmark's own weights
(``bench.harness.weights``), upcasting each layer's to float32, with every
matmul at ``Precision.HIGHEST``, over all the rows it is given at once.  ``quantized=True`` is the control: every
matmul's operands rounded to float8 e4m3 with one scale per tensor, the
precision below the configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b, quantized):
    if quantized:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, S, heads, hd): rotate pairs (j, j + hd/2) by pos * freq_j."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2.0 / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, model, quantized):
    f32 = lambda a: a.astype(jnp.float32)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h_heads = model["num_attention_heads"]
    s = x.shape[1]
    h = _rms(x, f32(lw["norm1"]), eps)
    q = _rope(_einsum("bsd,dhk->bshk", h, f32(lw["wq"]), quantized), theta)
    k = _rope(_einsum("bsd,dhk->bshk", h, f32(lw["wk"]), quantized), theta)
    v = _einsum("bsd,dhk->bshk", h, f32(lw["wv"]), quantized)
    rep = h_heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = _einsum("bshd,bthd->bhst", q, k, quantized) \
        / np.sqrt(q.shape[-1])
    causal = np.tril(np.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _einsum("bhst,bthd->bshd", p, v, quantized)
    x = x + _einsum("bshk,hkd->bsd", o, f32(lw["wo"]), quantized)
    h = _rms(x, f32(lw["norm2"]), eps)
    g = _einsum("bsd,df->bsf", h, f32(lw["w_gate"]), quantized)
    u = _einsum("bsd,df->bsf", h, f32(lw["w_in"]), quantized)
    x = x + _einsum("bsf,fd->bsd", jax.nn.silu(g) * u, f32(lw["w_out"]),
                    quantized)
    return x


def logits(w: dict, model: dict, tokens, first: int,
           quantized: bool = False) -> np.ndarray:
    """Logits (B, S - first, Vp) at positions ``first`` .. S-1 of
    ``tokens`` (B, S), one layer at a time: each layer is its own call
    (one program, reused), so that no program holds more than one layer's
    weights in float32.  (One program scanning all 24 layers gave every
    logit non-finite for 12 rows of 263 tokens on a TPU v5e, where the
    same layers called one by one, and that program at 4 rows, gave finite
    ones.)"""
    layer = jax.jit(partial(_layer, model=model, quantized=quantized))
    head = jax.jit(partial(_head, first=first, eps=model["rms_norm_eps"],
                           quantized=quantized))
    x = w["embed"][jnp.asarray(np.asarray(tokens, np.int32))] \
        .astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        x = layer(x, {k: w[k][i] for k in LAYER_LEAVES})
    return np.asarray(head(x, w["final_norm"], w["lm_head"]))


LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_in", "w_gate",
                "w_out")


def _head(x, final_norm, lm_head, first, eps, quantized):
    x = _rms(x[:, first:], final_norm.astype(jnp.float32), eps)
    return _einsum("bsd,dv->bsv", x, lm_head.astype(jnp.float32), quantized)
