"""Compile-only checks of the main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed alongside JAX
and compiles for a topology that is described, not attached.  That is
enough to catch what interpret mode cannot — tiles Mosaic refuses,
contractions it does not lower, scoped VMEM over the limit, programs
over the chip's memory — at no chip time.  Results and times still need
the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import ligd, network, profiles
from repro.core.era import Weights, uniform_alloc
from repro.kernels.era_step import ops as eops
from repro.kernels.era_step.kernel import choose_block_m, era_step_fused
from test_era_step import _paper_setup, _setup

pytestmark = pytest.mark.kernels

HBM_BYTES = 16 * 10 ** 9            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _assert_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < HBM_BYTES, total


SCALES = {"u12m6": _setup, "u1250m250": _paper_setup}


@pytest.mark.parametrize("scale", sorted(SCALES))
def test_era_step_kernel_compiles(scale, one_chip):
    """The fused step kernel at the block size the program picks."""
    scn, prof, q, w, s_vec, alloc = SCALES[scale]()
    operands = jax.eval_shape(
        lambda: eops._operands(scn, prof, s_vec, q, alloc,
                               eops.build_aux(scn), w))
    m, u = operands[0].shape
    bm = choose_block_m(m, u, scn.cfg.n_aps)
    compiled = jax.jit(
        lambda *a: era_step_fused(*a, block_m=bm, interpret=False)
    ).lower(*_on(one_chip, operands)).compile()
    _assert_fits(compiled)


@pytest.mark.parametrize("u,m", [(64, 20), (1250, 250)])
def test_era_step_kernel_compiles_tiled(u, m, one_chip):
    """A tiled grid with a zero-padded remainder block (bm=8 at M=20,
    bm=64 at M=250): the path the auto choice takes past the VMEM
    budget."""
    scn, prof, q, w, s_vec, alloc = _paper_setup(u=u, m=m)
    operands = jax.eval_shape(
        lambda: eops._operands(scn, prof, s_vec, q, alloc,
                               eops.build_aux(scn), w))
    bm = 8 if m < 64 else 64
    compiled = jax.jit(
        lambda *a: era_step_fused(*a, block_m=bm, interpret=False)
    ).lower(*_on(one_chip, operands)).compile()
    _assert_fits(compiled)


@pytest.mark.parametrize("u,m", [(12, 6), (1250, 250)])
def test_fused_sweep_compiles(u, m, one_chip, monkeypatch):
    """The whole batched Li-GD sweep with ``step_impl='fused'`` — the
    kernel under vmap, scan and while_loop, with the gemma-2b split
    profile that chip_smoke.py solves at paper scale."""
    cfg = (network.NetworkConfig() if (u, m) == (1250, 250)
           else network.small_config(n_users=u, n_subchannels=m))
    scn = network.make_scenario(jax.random.PRNGKey(0), cfg)
    prof = profiles.transformer_profile(get_config("gemma-2b"), seq=32)
    prep = ligd.prepare_batch([scn], prof, True)
    args = (prep.scn_b, jnp.full((1, u), 0.4), uniform_alloc(scn),
            jnp.asarray(prep.pred_b), jnp.float32(0.05), jnp.float32(1e-5))
    # the program picks the kernel from the default backend, which is the
    # CPU here: steer it to the TPU branch for this trace only
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        compiled = ligd._sweep_batch.lower(
            *_on(one_chip, args), 8, Weights(), _on(one_chip, prep.prof_b),
            step_impl="fused", prof_batched=prep.prof_batched,
            x_init_batched=False).compile()
    finally:
        # drop the kernel-bearing trace so no later CPU call reuses it
        ligd._sweep_batch.clear_cache()
    _assert_fits(compiled)


@pytest.mark.parametrize("step", ["forward", "prefill", "decode"])
def test_gemma_2b_serving_programs_compile(step, one_chip):
    """gemma-2b at its published widths, as chip_smoke.py serves it: the
    full-model forward, the prefill that fills the decode caches (its
    ring-buffer write once aborted XLA:TPU's scatter fusion) and one
    decode step."""
    from repro.models import transformer as T
    cfg = get_config("gemma-2b")
    batch, seq, max_seq = 16, 32, 37
    params = _on(one_chip, jax.eval_shape(
        lambda: T.init(jax.random.PRNGKey(0), cfg)))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    caches = _on(one_chip, jax.eval_shape(
        lambda: T.init_caches(cfg, batch, max_seq)))
    last = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    fn, args = {
        "forward": (lambda p, t: T.forward(p, cfg, t)[0], (params, tokens)),
        "prefill": (lambda p, t: T.prefill(p, cfg, t, max_seq=max_seq),
                    (params, tokens)),
        "decode": (lambda p, t, c: T.decode_step(p, cfg, t, jnp.int32(seq),
                                                 c), (params, last, caches)),
    }[step]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
