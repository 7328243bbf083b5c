"""Top-level decoder model: embedding -> scanned pattern units (+ tail) ->
final norm -> LM head.  Handles all 10 assigned architectures via ModelConfig:
text decoders, MoE, Griffin hybrid, Mamba-2, Qwen2-VL (stub vision frontend),
MusicGen (multi-codebook audio tokens).

Compile time is depth-independent: the repeating pattern unit is scanned;
``n_layers % pattern_len`` remainder layers form an unstacked tail.

``constrain(x, name)`` is an optional sharding-constraint hook injected by the
distributed layer (names: "resid", "logits"); it defaults to identity so the
model stays mesh-agnostic.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.common import dense_init, positions_for, rms_norm


def _noop(x, name):
    return x


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def init(key, cfg):
    dt = jnp.dtype(cfg.dtype)
    vp = cfg.padded_vocab
    keys = jax.random.split(key, 5)

    if cfg.n_codebooks > 1:
        embed = dense_init(keys[0], (cfg.n_codebooks, vp, cfg.d_model), dt,
                           in_axis_size=cfg.d_model)
    else:
        embed = dense_init(keys[0], (vp, cfg.d_model), dt,
                           in_axis_size=cfg.d_model)

    def init_unit(k):
        ks = jax.random.split(k, cfg.pattern_len)
        return tuple(blocks.init(ks[i], cfg, spec)
                     for i, spec in enumerate(cfg.pattern))

    params = {"embed": embed}
    if cfg.n_units > 0:
        unit_keys = jax.random.split(keys[1], cfg.n_units)
        params["units"] = jax.vmap(init_unit)(unit_keys)
    tail_keys = jax.random.split(keys[2], max(len(cfg.tail_specs), 1))
    params["tail"] = tuple(
        blocks.init(tail_keys[i], cfg, spec)
        for i, spec in enumerate(cfg.tail_specs))
    params["final_norm"] = (jnp.zeros((cfg.d_model,), dt) if cfg.gemma_style
                            else jnp.ones((cfg.d_model,), dt))
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            params["lm_head"] = dense_init(
                keys[3], (cfg.n_codebooks, cfg.d_model, vp), dt)
        else:
            params["lm_head"] = dense_init(keys[3], (cfg.d_model, vp), dt)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------- #
# embedding / head
# --------------------------------------------------------------------------- #
def embed_tokens(params, cfg, tokens, vision_embeds=None):
    """tokens: (B,S) int32, or (B,K,S) for multi-codebook audio."""
    if cfg.n_codebooks > 1:
        # sum codebook embeddings per step: tokens (B,K,S), embed (K,Vp,d)
        parts = [jnp.take(params["embed"][k], tokens[:, k, :], axis=0)
                 for k in range(cfg.n_codebooks)]
        x = sum(parts)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)  # (B,S,d)
    if cfg.gemma_style:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    return x


def lm_logits(params, cfg, x, constrain=_noop):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 gemma_style=cfg.gemma_style)
    if cfg.n_codebooks > 1:
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,kvd->bskv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,kdv->bskv", x, params["lm_head"])
    else:
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return constrain(logits.astype(jnp.float32), "logits")


# --------------------------------------------------------------------------- #
# forward / prefill / decode
# --------------------------------------------------------------------------- #
def forward(params, cfg, tokens, vision_embeds=None, positions=None,
            impl="naive", constrain=_noop, remat=False):
    """Full-sequence forward. Returns (logits, moe_aux)."""
    x = embed_tokens(params, cfg, tokens, vision_embeds)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = positions_for(cfg, b, s)
    x = constrain(x, "resid")

    def unit_body(x, unit_params):
        aux = jnp.float32(0.0)
        for i, spec in enumerate(cfg.pattern):
            x, a = blocks.forward(unit_params[i], cfg, spec, x, positions,
                                  impl=impl, constrain=constrain)
            aux = aux + a
        return constrain(x, "resid"), aux

    if remat:
        unit_body = jax.checkpoint(unit_body)

    aux_total = jnp.float32(0.0)
    if cfg.n_units > 0:
        def scan_body(carry, unit_params):
            x, aux = carry
            x, a = unit_body(x, unit_params)
            return (x, aux + a), None
        (x, aux_total), _ = jax.lax.scan(
            scan_body, (x, aux_total), params["units"])
    for i, spec in enumerate(cfg.tail_specs):
        x, a = blocks.forward(params["tail"][i], cfg, spec, x, positions,
                              impl=impl, constrain=constrain)
        x = constrain(x, "resid")
        aux_total = aux_total + a
    return lm_logits(params, cfg, x, constrain), aux_total


def init_caches(cfg, batch, max_seq, dtype=None):
    def unit_cache():
        return tuple(blocks.init_cache(cfg, spec, batch, max_seq, dtype=dtype)
                     for spec in cfg.pattern)
    caches = {}
    if cfg.n_units > 0:
        uc = unit_cache()
        caches["units"] = jax.tree.map(
            lambda x: jnp.stack([x] * cfg.n_units), uc)
    caches["tail"] = tuple(
        blocks.init_cache(cfg, spec, batch, max_seq, dtype=dtype)
        for spec in cfg.tail_specs)
    return caches


@partial(jax.jit, static_argnames=("cfg", "max_seq", "impl", "constrain"))
def prefill(params, cfg, tokens, max_seq, vision_embeds=None, positions=None,
            impl="naive", constrain=_noop):
    """Full-sequence forward + decode-cache capture.

    Compiled once per (cfg, shapes, max_seq, impl, constrain) and cached
    across calls.  Returns (logits, caches, aux).
    """
    x = embed_tokens(params, cfg, tokens, vision_embeds)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = positions_for(cfg, b, s)
    x = constrain(x, "resid")

    aux_total = jnp.float32(0.0)
    caches = {}
    if cfg.n_units > 0:
        def scan_body(carry, unit_params):
            x, aux = carry
            unit_caches = []
            for i, spec in enumerate(cfg.pattern):
                x, c, a = blocks.prefill(unit_params[i], cfg, spec, x,
                                         positions, max_seq, impl=impl,
                                         constrain=constrain)
                aux = aux + a
                unit_caches.append(c)
            return (constrain(x, "resid"), aux), tuple(unit_caches)
        (x, aux_total), caches["units"] = jax.lax.scan(
            scan_body, (x, aux_total), params["units"])
    tail_caches = []
    for i, spec in enumerate(cfg.tail_specs):
        x, c, a = blocks.prefill(params["tail"][i], cfg, spec, x, positions,
                                 max_seq, impl=impl, constrain=constrain)
        x = constrain(x, "resid")
        aux_total = aux_total + a
        tail_caches.append(c)
    caches["tail"] = tuple(tail_caches)
    return lm_logits(params, cfg, x, constrain), caches, aux_total


@partial(jax.jit, static_argnames=("cfg", "constrain"),
         donate_argnames=("caches",))
def decode_step(params, cfg, tokens, pos, caches, constrain=_noop):
    """One decode step.

    tokens: (B,) int32 (or (B,K) for multi-codebook); pos: scalar int32
    absolute position of this token. Returns (logits (B, V...), caches).
    Compiled once per (cfg, shapes, constrain) and cached across calls;
    ``caches`` is donated, so the stacked cache is updated in place and
    the value passed in must not be used again.
    """
    if cfg.n_codebooks > 1:
        x = embed_tokens(params, cfg, tokens[:, :, None])  # (B,1,d)
    else:
        x = embed_tokens(params, cfg, tokens[:, None])
    x = constrain(x, "resid")
    pos = jnp.asarray(pos, jnp.int32)

    new_caches = {}
    if cfg.n_units > 0:
        # the stacked cache rides in the scan CARRY and is updated in place
        # per unit (dynamic_update_index): threading it through xs/ys keeps
        # two full cache copies alive (observed ~2× cache bytes of temp on
        # qwen2-vl 32k decode)
        def scan_body(carry, xs):
            x, stacked = carry
            i, unit_params = xs
            unit_cache = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, 0,
                                                       keepdims=False),
                stacked)
            new_unit = []
            for j, spec in enumerate(cfg.pattern):
                x, c = blocks.decode(unit_params[j], cfg, spec, x, pos,
                                     unit_cache[j], constrain=constrain)
                new_unit.append(c)
            stacked = jax.tree.map(
                lambda c, nc: jax.lax.dynamic_update_index_in_dim(
                    c, nc, i, 0),
                stacked, tuple(new_unit))
            return (constrain(x, "resid"), stacked), None
        (x, new_caches["units"]), _ = jax.lax.scan(
            scan_body, (x, caches["units"]),
            (jnp.arange(cfg.n_units), params["units"]))
    new_tail = []
    for i, spec in enumerate(cfg.tail_specs):
        x, c = blocks.decode(params["tail"][i], cfg, spec, x, pos,
                             caches["tail"][i], constrain=constrain)
        x = constrain(x, "resid")
        new_tail.append(c)
    new_caches["tail"] = tuple(new_tail)

    logits = lm_logits(params, cfg, x, constrain)  # (B,1,...)
    return logits[:, 0], new_caches
