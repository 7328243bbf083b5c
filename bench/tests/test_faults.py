"""The correctness check catches a broken timed path: each test drives a
whole run at the small CPU size of ``tiny.py`` (the harness's look for a
chip skipped) with one fault planted in the program underneath, and sees
``correct`` come out false.  The same runs unbroken come out true."""
import contextlib
import math

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny

@contextlib.contextmanager
def patched(owner, name, make):
    real = getattr(owner, name)
    setattr(owner, name, make(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def _no_steps(real, at):
    """The sweep run for zero GD steps: every split point returns its
    starting point, the state unchanged."""
    def fn(*args, **kw):
        args = list(args)
        args[at] = 0
        return real(*args, **kw)
    return fn


def _lanes_from_first(block):
    """Every lane past the first ``block`` takes the first block's result:
    the rest of the batch is never computed (or never gathered)."""
    def wrap(real):
        def fn(*args, **kw):
            out = real(*args, **kw)

            def fill(x):
                n = x.shape[0]
                idx = jnp.arange(n) % min(block(n), n)
                return jnp.take(x, idx, axis=0)
            return jax.tree.map(fill, out)
        return fn
    return wrap


def _altered_split(real):
    """The solve's answer altered where it is produced: user 0's split."""
    def fn(*args, **kw):
        s, terms = real(*args, **kw)
        return s.at[:, 0].set((s[:, 0] + 1) % (args[6] + 1)), terms
    return fn


def run(cell, seed=11):
    return tiny.run_tiny(cell, seed=seed, seconds=0.5)


def _solver_faults():
    from repro.core import ligd
    return {
        "state_unchanged": [(ligd, "_sweep_batch",
                             lambda r: _no_steps(r, 6))],
        "half_batch": [(ligd, "_sweep_batch",
                        _lanes_from_first(lambda n: n // 2))],
        "answer_altered": [(ligd, "_discretize_eval_batch",
                            lambda r: _altered_split(r))],
    }


CASES = [("paper.drift", "state_unchanged"),
         ("paper.drift", "answer_altered"),
         ("internlm2.chat", "state_unchanged"),
         ("internlm2.chat", "half_batch"),
         ("internlm2.chat", "answer_altered")]


@pytest.mark.parametrize("cell", ["paper.drift", "internlm2.chat"])
def test_sound_run_is_correct(cell):
    assert run(cell)["correct"]


def test_every_check_carries_its_limit_and_the_step_its_place(capfd):
    result = tiny.run_tiny("paper.drift", seed=11, seconds=0.5)
    for k, c in result["checks"].items():
        assert type(c["value"]) is float, (k, c)
        assert isinstance(c["limit"], (int, float)), (k, c)
    place, = [x for x in capfd.readouterr().err.splitlines()
              if x.startswith("fused step: step_grad_p99 at ")]
    for word in ("lane", "split", "leaf", "leaf norm", "median leaf norm"):
        assert word in place, place
    assert "np." not in place, place


def _scaled_leaf(leaf):
    """The fused step's gradient with one leaf scaled by 1 + 1e-5."""
    def wrap(real):
        def fn(*args, **kw):
            val, g = real(*args, **kw)
            return val, g._replace(**{leaf: getattr(g, leaf) * (1 + 1e-5)})
        return fn
    return wrap


@pytest.mark.parametrize("leaf", ["beta_up", "p", "r"])
def test_scaled_gradient_leaf_is_caught(leaf):
    from repro.kernels.era_step import ops
    with patched(ops, "era_step_value_and_grad", _scaled_leaf(leaf)):
        result = run("paper.drift")
    assert not result["correct"], result["checks"]
    step = tiny.step_grad(result)
    assert step["value"] > step["limit"]


def _scaled_rows(real):
    """The fused step's gradient with the rows of the last tenth of the
    users (at least one) scaled by 1 + 1e-4: a fault in one block of
    users, as a wrong last tile or an off-by-one padding mask makes."""
    def fn(*args, **kw):
        val, g = real(*args, **kw)
        n = g.p.shape[0]
        k = n - math.ceil(n / 10)
        return val, jax.tree.map(lambda x: x.at[k:].multiply(1 + 1e-4), g)
    return fn


@pytest.mark.parametrize("cell", ["paper.drift", "internlm2.chat"])
def test_scaled_gradient_rows_are_caught(cell):
    from repro.kernels.era_step import ops
    with patched(ops, "era_step_value_and_grad", _scaled_rows):
        result = run(cell)
    assert not result["correct"], result["checks"]
    step = tiny.step_grad(result)
    assert step["value"] > step["limit"]


@pytest.mark.parametrize("cell,fault", CASES)
def test_solver_fault_is_caught(cell, fault):
    with contextlib.ExitStack() as stack:
        for owner, name, make in _solver_faults()[fault]:
            stack.enter_context(patched(owner, name, make))
        result = run(cell)
    assert not result["correct"], result["checks"]


def _decode_faults():
    from repro.models import transformer as T

    def stale_cache(real):
        # the decode step hands back the cache it was given
        def fn(params, cfg, tokens, pos, caches, **kw):
            logits, _ = real(params, cfg, tokens, pos, caches, **kw)
            return logits, caches
        return fn

    def half_batch(real):
        # only the first half of the users is decoded; the rest copy them
        def fn(params, cfg, tokens, pos, caches, **kw):
            logits, caches = real(params, cfg, tokens, pos, caches, **kw)
            n = logits.shape[0]
            return jnp.take(logits, jnp.arange(n) % (n // 2), 0), caches
        return fn

    def altered_token(real):
        # a decoded token altered where it is produced
        def fn(params, cfg, tokens, pos, caches, **kw):
            logits, caches = real(params, cfg, tokens, pos, caches, **kw)
            wrong = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
            return logits.at[jnp.arange(logits.shape[0]), wrong].add(
                1e4), caches
        return fn

    return {"stale_cache": stale_cache, "half_batch": half_batch,
            "altered_token": altered_token}


@pytest.mark.parametrize("fault", ["stale_cache", "half_batch",
                                   "altered_token"])
def test_decode_fault_is_caught(fault):
    from repro.models import transformer as T
    with patched(T, "decode_step", _decode_faults()[fault]):
        result = run("internlm2.chat")
    assert not result["correct"], result["checks"]
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
