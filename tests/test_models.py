"""Model substrate unit tests: decode==forward consistency, chunked==naive
attention, MoE semantics, RG-LRU scan vs loop, M-RoPE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config, list_architectures
from repro.models import attention, rglru, transformer as T
from repro.models.common import apply_mrope, apply_rope
from repro.telemetry import recording


def _f32(name, **kw):
    return get_tiny_config(name).replace(dtype="float32", **kw)


def _decode_case(name, b=2, s=12):
    """(cfg, params, tokens of s + 1 steps, vision embeds, position offset)
    for a float32 tiny config.  MoE archs use a generous capacity factor so
    no tokens drop (drops are count-dependent and legitimately differ
    between prefill and decode)."""
    kw = {"capacity_factor": 8.0} if "moe" in get_tiny_config(name).arch_type \
        else {}
    cfg = _f32(name, **kw)
    key = jax.random.PRNGKey(1)
    params = T.init(key, cfg)
    shape = (b, cfg.n_codebooks, s + 1) if cfg.n_codebooks > 1 else (b, s + 1)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab_size)
    ve, offset = None, 0
    if cfg.vision_tokens:
        ve = 0.02 * jax.random.normal(key, (b, cfg.vision_tokens, cfg.d_model))
        offset = cfg.vision_tokens
    return cfg, params, tokens, ve, offset


@pytest.mark.parametrize("name", list_architectures())
def test_decode_matches_forward(name):
    s = 12
    cfg, params, tokens, ve, offset = _decode_case(name, s=s)
    pre = tokens[..., :s]
    new = tokens[..., s]
    full, _ = T.forward(params, cfg, tokens, vision_embeds=ve)
    _, caches, _ = T.prefill(params, cfg, pre, max_seq=32, vision_embeds=ve)
    dec, _ = T.decode_step(params, cfg, new, jnp.int32(s + offset), caches)
    want = full[:, -1]
    rel = float(jnp.max(jnp.abs(dec - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-9)
    assert rel < 5e-4, rel


def _greedy_continuation(params, cfg, prompt, ve, offset, n_steps):
    """Prefill, then ``n_steps`` decode steps fed their own greedy tokens,
    each step handed the caches the previous one returned (and donated).
    Returns the logits of every step and the greedy tokens."""
    s = prompt.shape[-1]
    logits, caches, _ = T.prefill(params, cfg, prompt, max_seq=32,
                                  vision_embeds=ve)
    logits = logits[:, -1]
    outs = [logits]
    for step in range(n_steps):
        cur = jnp.argmax(logits, -1)
        logits, caches = T.decode_step(params, cfg, cur,
                                       jnp.int32(s + offset + step), caches)
        outs.append(logits)
    return ([np.asarray(x) for x in outs],
            [np.asarray(jnp.argmax(x, -1)) for x in outs])


@pytest.mark.parametrize("name", list_architectures())
def test_compiled_decode_matches_eager(name):
    """``prefill`` and ``decode_step`` are cached compiled programs (caches
    donated); three chained steps give the eager path's greedy tokens and
    logits, and a second continuation of the same shapes compiles
    nothing."""
    s = 12
    cfg, params, tokens, ve, offset = _decode_case(name, s=s)
    prompt = tokens[..., :s]
    logits, toks = _greedy_continuation(params, cfg, prompt, ve, offset, 3)
    with jax.disable_jit():
        want_logits, want_toks = _greedy_continuation(params, cfg, prompt,
                                                      ve, offset, 3)
    for got, want in zip(toks, want_toks):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(logits, want_logits):
        # float32 with fusion reordering: a few ulps of the largest logit
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())
    with recording(True) as rec:
        _greedy_continuation(params, cfg, prompt, ve, offset, 3)
    assert rec.total_compiles == 0


def test_decode_step_nested_under_outer_jit():
    """``launch/steps.make_decode_step`` calls the compiled ``decode_step``
    inside its caller's ``jit`` with a sharding ``constrain``: it lowers
    with the caches donated and gives the direct call's logits."""
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_decode_step

    s = 12
    cfg, params, tokens, _, _ = _decode_case("llama3-8b", s=s)
    rules = ShardingRules(cfg, make_host_mesh(), mode="serve")
    step = jax.jit(make_decode_step(cfg, constrain=rules.constrain),
                   donate_argnums=(3,))
    new, pos = tokens[..., s], jnp.int32(s)
    _, caches, _ = T.prefill(params, cfg, tokens[..., :s], max_seq=32)
    want, _ = T.decode_step(params, cfg, new, pos, caches)
    _, caches, _ = T.prefill(params, cfg, tokens[..., :s], max_seq=32)
    hlo = step.lower(params, new, pos, caches).as_text()
    assert "sharding" in hlo                  # the constraints are there
    got, caches = step(params, new, pos, caches)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
    got2, _ = step(params, jnp.argmax(got, -1), pos + 1, caches)
    assert bool(jnp.isfinite(got2).all())


@pytest.mark.parametrize("name", ["llama3-8b", "gemma3-12b", "mixtral-8x22b"])
def test_chunked_matches_naive(name):
    cfg = _f32(name, capacity_factor=8.0)
    params = T.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0,
                                cfg.vocab_size)
    naive, _ = T.forward(params, cfg, tokens, impl="naive")
    chunked, _ = T.forward(params, cfg, tokens, impl="chunked")
    assert float(jnp.max(jnp.abs(naive - chunked))) < 1e-4


def test_sliding_window_restricts_context():
    """A token outside the window must not influence attention output."""
    cfg = _f32("mixtral-8x22b").replace(window=4)
    key = jax.random.PRNGKey(3)
    p = attention.init(key, cfg)
    x = jax.random.normal(key, (1, 10, cfg.d_model)) * 0.1
    pos = jnp.arange(10)[None, :]
    y1 = attention.forward(p, cfg, x, pos, mixer="local")
    # perturb position 0: outputs at positions >= 4 must be unchanged
    x2 = x.at[:, 0].add(100.0)
    y2 = attention.forward(p, cfg, x2, pos, mixer="local")
    assert float(jnp.max(jnp.abs(y1[:, 5:] - y2[:, 5:]))) < 1e-4
    assert float(jnp.max(jnp.abs(y1[:, :4] - y2[:, :4]))) > 1e-3


def test_rglru_matches_sequential():
    cfg = _f32("recurrentgemma-2b")
    key = jax.random.PRNGKey(4)
    p = rglru.init(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model)) * 0.3
    y_scan, h_last = rglru.forward(p, cfg, x)
    # sequential via repeated decode steps
    cache = rglru.init_cache(cfg, 2)
    outs = []
    for t in range(16):
        y_t, cache = rglru.decode_step(p, cfg, x[:, t:t + 1], cache)
        outs.append(y_t)
    y_seq = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(y_scan - y_seq))) < 1e-4
    assert float(jnp.max(jnp.abs(cache["h"] - h_last))) < 1e-4


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor=1.0 some tokens drop but output stays finite and
    the aux loss stays O(1)."""
    cfg = _f32("dbrx-132b", capacity_factor=1.0)
    from repro.models import moe
    p = moe.init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 32, cfg.d_model)) * 0.3
    y, aux = moe.forward(p, cfg, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert 0.5 < float(aux) < 16.0  # ≈1 when balanced, ≤E when collapsed


def test_moe_capacity_chunked_equals_direct():
    from repro.models import moe
    cfg = _f32("dbrx-132b", capacity_factor=2.0)
    p = moe.init(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model)) * 0.3
    y_small, _ = moe.forward(p, cfg, x)          # direct path (cap small)
    old = moe.C_CHUNK
    try:
        moe.C_CHUNK = 8                           # force the chunked path
        y_chunk, _ = moe.forward(p, cfg, x)
    finally:
        moe.C_CHUNK = old
    # capacity rounding differs, so compare where both keep all tokens
    assert float(jnp.max(jnp.abs(y_small - y_chunk))) < 1e-4


def test_mrope_sections_rotate_by_component():
    """Text positions (t=h=w) must reduce M-RoPE to plain RoPE."""
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (2, 8, 4, 64))
    pos = jnp.broadcast_to(jnp.arange(8)[None, :], (2, 8))
    mpos = jnp.broadcast_to(pos[:, None, :], (2, 3, 8))
    plain = apply_rope(x, pos, 10_000.0)
    mr = apply_mrope(x, mpos, 10_000.0, (8, 12, 12))
    assert float(jnp.max(jnp.abs(plain - mr))) < 1e-5


def test_ring_buffer_wraps():
    """Decoding past the cache size keeps only the window (local mixer)."""
    cfg = _f32("mixtral-8x22b").replace(window=8)
    p = attention.init(jax.random.PRNGKey(10), cfg)
    cache = attention.init_cache(cfg, 1, max_seq=64, mixer="local")
    assert cache["k"].shape[1] == 8  # ring sized to the window
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 1, cfg.d_model))
    for t in range(20):
        y, cache = attention.decode_step(p, cfg, x, jnp.int32(t), cache,
                                         mixer="local")
    assert int(cache["pos"].min()) >= 20 - 8
