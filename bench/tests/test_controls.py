"""A control run comes out not correct: the plain reference put in the
program's place at the precision below the configuration's
(``core.Run.control``), driven through a whole run at the small CPU size
of ``tiny.py``, and the one-pass control too.  Off a TPU a control's bf16
passes are its operands rounded to 16 significant bits (three passes) or
8 (one pass) (``bench.harness.solver.precision_of``)."""
import pytest

from bench.tests import tiny


@pytest.mark.parametrize("control", ["high", "default"])
@pytest.mark.parametrize("cell", ["paper.drift", "internlm2.chat"])
def test_control_is_not_correct(cell, control):
    result = tiny.run_tiny(cell, seed=11, seconds=0.5, control=control)
    assert not result["correct"], result["checks"]
    step = tiny.step_grad(result)
    assert step["value"] > step["limit"]


@pytest.mark.parametrize("bits", [8, 16])
def test_round_bits_keeps_that_many_bits(bits):
    import jax.numpy as jnp
    import numpy as np
    from bench.reference.era import round_bits
    x = jnp.asarray(np.random.default_rng(0).standard_normal(1000),
                    jnp.float32)
    rel = np.abs(np.asarray(round_bits(x, bits)) - np.asarray(x)) \
        / np.abs(np.asarray(x))
    assert 0 < rel.max() <= 2.0 ** -bits


def test_round_bits_passes_gradients():
    import jax
    import jax.numpy as jnp
    from bench.reference.era import round_bits
    x = jnp.linspace(0.5, 2.0, 7, dtype=jnp.float32)
    g = jax.grad(lambda v: jnp.sum(round_bits(v, 16) * x))(x)
    assert jnp.array_equal(g, x)
