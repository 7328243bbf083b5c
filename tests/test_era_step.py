"""Fused ERA GD-step kernel suite (kernels/era_step).

Three layers of regression, mirroring the kernel's layering:
  * math:     the analytic oracle (ref.fused_step_math) against
              ``jax.value_and_grad`` of the real utility — the fused
              pipeline IS the autodiff step, to f32 roundoff;
  * plumbing: the Pallas kernel against the oracle (shared arithmetic, so
              only BlockSpec/ref wiring can diverge), in interpret mode on
              CPU and compiled on TPU;
  * solver:   full Li-GD solves with ``SolverSpec(step_impl='fused')``
              against the XLA path across all three backends and both
              lane placements — final Γ trajectories and allocations
              within rtol=1e-5, split decisions and iteration counts
              exactly equal.

The rtol=1e-5 solve bound is only achievable because noma.py and the
fused step share the masked-matvec SIC formulation (exact empty-suffix
relu ties, no cumsum cancellation — see noma.py's module docstring); if
these tests start drifting, the two formulations have diverged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import era, ligd, network, profiles
from repro.core.era import Weights
from repro.kernels.era_step import ops as eops
from repro.kernels.era_step import ref as eref
from repro.kernels.era_step.kernel import (
    DEFAULT_VMEM_BUDGET, block_vmem_bytes, choose_block_m, era_step_fused,
    legal_block_m)

pytestmark = pytest.mark.kernels

# interpret=False compiles for a real TPU — only meaningful there; the
# interpret=True lane keeps the whole suite green on CPU-only CI
INTERPRET_MODES = [
    True,
    pytest.param(False, marks=pytest.mark.skipif(
        jax.default_backend() != "tpu",
        reason="compiled Pallas kernel needs a TPU")),
]


def _setup(u=12, m=6, seed=0):
    cfg = network.small_config(n_users=u, n_subchannels=m)
    scn = network.make_scenario(jax.random.PRNGKey(seed), cfg)
    prof = profiles.get_profile("nin")
    q = jnp.full((u,), 0.4)
    w = Weights()
    s_vec = jnp.full((u,), min(3, len(prof.device_flops) - 1),
                     dtype=jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 5)
    alloc = era.Allocation(
        beta_up=jax.nn.softmax(jax.random.normal(ks[0], (u, m)), axis=1),
        beta_dn=jax.nn.softmax(jax.random.normal(ks[1], (u, m)), axis=1),
        p=jnp.exp(jax.random.normal(ks[2], (u,)) * 0.3) * 0.1,
        p_ap=jnp.exp(jax.random.normal(ks[3], (u,)) * 0.3),
        r=1.0 + jnp.exp(jax.random.normal(ks[4], (u,)) * 0.2))
    return scn, prof, q, w, s_vec, alloc


def _assert_alloc_close(got, want, tol):
    for name in ("beta_up", "beta_dn", "p", "p_ap", "r"):
        a, b = np.asarray(getattr(want, name)), np.asarray(getattr(got, name))
        scale = np.max(np.abs(a)) + 1e-30
        np.testing.assert_allclose(b / scale, a / scale, atol=tol,
                                   err_msg=name)


# ------------------------------------------------------------------- math
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ref_matches_autodiff(seed):
    """The analytic fused pipeline reproduces jax.value_and_grad of the
    real utility to f32 roundoff — including the balanced relu-tie rule at
    exactly-zero interference."""
    scn, prof, q, w, s_vec, alloc = _setup(seed=seed)

    def loss(a):
        return era.utility(scn, prof, s_vec, a, q, w).gamma

    g0, grad0 = jax.value_and_grad(loss)(alloc)
    g1, grad1 = eops.era_step_value_and_grad(scn, prof, s_vec, q, alloc, w,
                                             impl="ref")
    np.testing.assert_allclose(float(g1), float(g0), rtol=1e-5)
    _assert_alloc_close(grad1, grad0, 1e-4)


def test_sic_mask_semantics():
    """mask[i, j] = same group AND decoded later; empty rows sum to an
    EXACT 0.0 (the relu-tie invariant the backward depends on)."""
    rank = jnp.asarray([[0., 1., 2., 3.]])
    gid = jnp.asarray([[0., 0., 2., 2.]])
    mask = eref._sic_mask(rank, gid)
    want = np.asarray([[[0, 1, 0, 0], [0, 0, 0, 0],
                        [0, 0, 0, 1], [0, 0, 0, 0]]], np.float32)
    np.testing.assert_array_equal(np.asarray(mask), want)
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    out = np.asarray(eref._suffix_apply(mask, x))
    np.testing.assert_array_equal(out, [[2.0, 0.0, 4.0, 0.0]])
    # adjoint identity: <Ax, y> == <x, A^T y>
    y = jnp.asarray([[0.5, -1.0, 2.0, 0.25]])
    lhs = float(jnp.sum(eref._suffix_apply(mask, x) * y))
    rhs = float(jnp.sum(x * eref._suffix_transpose(mask, y)))
    assert abs(lhs - rhs) < 1e-6


# --------------------------------------------------------------- plumbing
def _assert_leaves_close(grads_ref, grads_got, tol=1e-5):
    for a, b in zip(grads_ref, grads_got):
        scale = np.max(np.abs(np.asarray(a))) + 1e-30
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=tol)


@pytest.mark.parametrize("interpret", INTERPRET_MODES)
@pytest.mark.parametrize("u,m", [(8, 4), (16, 8), (32, 8)])
def test_kernel_matches_ref(u, m, interpret):
    scn, prof, q, w, s_vec, alloc = _setup(u=u, m=m, seed=u + m)
    aux = eops.build_aux(scn)
    operands = eops._operands(scn, prof, s_vec, q, alloc, aux, w)
    g_ref, grads_ref = eref.era_step_ref(*operands)
    g_ker, *grads_ker = era_step_fused(*operands, interpret=interpret)
    np.testing.assert_allclose(float(g_ker[0, 0]), float(g_ref), rtol=1e-5)
    _assert_leaves_close(grads_ref, grads_ker)


# ------------------------------------------------------------- tiled grid
def test_tiled_ref_matches_untiled():
    """The block-decomposed tiled mirror reproduces the untiled oracle —
    Γ and all five gradient leaves to f32 roundoff — including a remainder
    block (m=6 with block_m=4 → blocks of 4 and 2)."""
    scn, prof, q, w, s_vec, alloc = _setup(u=12, m=6, seed=7)
    aux = eops.build_aux(scn)
    operands = eops._operands(scn, prof, s_vec, q, alloc, aux, w)
    g0, grads0 = eref.era_step_ref(*operands)
    for bm in (1, 2, 3, 4):
        g_t, grads_t = eref.era_step_ref(*operands, block_m=bm)
        np.testing.assert_allclose(float(g_t), float(g0), rtol=1e-5)
        _assert_leaves_close(grads0, grads_t)


@pytest.mark.parametrize("interpret", INTERPRET_MODES)
@pytest.mark.parametrize("bm", [1, 2, 3, 4])
def test_tiled_kernel_matches_untiled_ref(bm, interpret):
    """The (2, nb) two-pass kernel grid at every block size — divisible
    (1, 2, 3 of m=6) and indivisible (4 → zero-padded remainder block) —
    against the untiled oracle."""
    scn, prof, q, w, s_vec, alloc = _setup(u=12, m=6, seed=11)
    aux = eops.build_aux(scn)
    operands = eops._operands(scn, prof, s_vec, q, alloc, aux, w)
    g_ref, grads_ref = eref.era_step_ref(*operands)
    g_ker, *grads_ker = era_step_fused(*operands, block_m=bm,
                                       interpret=interpret)
    np.testing.assert_allclose(float(g_ker[0, 0]), float(g_ref), rtol=1e-5)
    for a, b in zip(grads_ref, grads_ker):
        assert b.shape == a.shape        # padded rows sliced back off
    _assert_leaves_close(grads_ref, grads_ker)


def test_choose_block_m_budget():
    """Auto-sizing: untiled whenever the whole problem fits the VMEM the
    kernel requests (every test scale, and the paper's U=1250/M=250 now
    that the kernel holds one channel's mask at a time), else the largest
    legal tile under budget — a multiple of 8 (the f32 sublane tile)
    dividing M rounded up to 8, so at most 7 channels are zero-padded."""
    assert choose_block_m(6, 12, 2) == 6          # test scale: untiled
    assert choose_block_m(16, 64, 4) == 16
    assert choose_block_m(250, 1250, 5) == 250    # paper scale: untiled
    assert block_vmem_bytes(250, 1250, 5) <= DEFAULT_VMEM_BUDGET
    for m, u in ((500, 1250), (250, 2500), (1000, 1250)):
        bm = choose_block_m(m, u, 5)
        m8 = -(-m // 8) * 8
        assert bm < m and bm % 8 == 0 and m8 % bm == 0, (m, u, bm)
        assert block_vmem_bytes(bm, u, 5) <= DEFAULT_VMEM_BUDGET
        # maximal: the next legal divisor up is over budget
        bigger = [b for b in range(bm + 8, m8, 8) if m8 % b == 0]
        if bigger:
            assert block_vmem_bytes(bigger[0], u, 5) > DEFAULT_VMEM_BUDGET
    # monotone: block estimate grows with bm, so the chosen bm is maximal
    assert block_vmem_bytes(64, 1250, 5) < block_vmem_bytes(128, 1250, 5)
    # compiled blocks are legal: M itself or a multiple of 8
    assert [legal_block_m(b, 6) for b in (1, 4, 6, 9)] == [6, 6, 6, 6]
    assert [legal_block_m(b, 250) for b in (1, 8, 60, 64, 249, 300)] == \
        [8, 8, 64, 64, 250, 250]


def test_weight_sweep_shares_one_compile():
    """Weights ride in the traced env row, not jit statics: distinct
    weight triples must NOT recompile the kernel (the PR-5 recompile-churn
    bug).  Probed via the jit lowering cache."""
    scn, prof, q, _, s_vec, alloc = _setup(u=8, m=4, seed=5)
    aux = eops.build_aux(scn)
    era_step_fused.clear_cache()
    for w in (Weights(), Weights(w_t=0.6, w_q=0.2, w_r=0.2),
              Weights(w_t=0.1, w_q=0.1, w_r=0.8)):
        operands = eops._operands(scn, prof, s_vec, q, alloc, aux, w)
        era_step_fused(*operands, interpret=True)
    assert era_step_fused._cache_size() == 1


# ------------------------------------------------------------ paper scale
def _paper_setup(u=1250, m=250, n_aps=5, seed=0):
    cfg = network.small_config(n_users=u, n_subchannels=m, n_aps=n_aps)
    scn = network.make_scenario(jax.random.PRNGKey(seed), cfg)
    prof = profiles.get_profile("nin")
    q = jnp.full((u,), 0.4)
    w = Weights()
    s_vec = jnp.full((u,), min(3, len(prof.device_flops) - 1),
                     dtype=jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 5)
    alloc = era.Allocation(
        beta_up=jax.nn.softmax(jax.random.normal(ks[0], (u, m)), axis=1),
        beta_dn=jax.nn.softmax(jax.random.normal(ks[1], (u, m)), axis=1),
        p=jnp.exp(jax.random.normal(ks[2], (u,)) * 0.3) * 0.1,
        p_ap=jnp.exp(jax.random.normal(ks[3], (u,)) * 0.3),
        r=1.0 + jnp.exp(jax.random.normal(ks[4], (u,)) * 0.2))
    return scn, prof, q, w, s_vec, alloc


@pytest.mark.slow
def test_paper_scale_tiled_ref_matches_untiled():
    """Acceptance: at the paper's (U=1250, M=250) the tiled decomposition
    (at the auto-chosen bm AND a remainder-forcing bm) matches the untiled
    oracle to f32 roundoff on Γ and all five gradient leaves."""
    scn, prof, q, w, s_vec, alloc = _paper_setup()
    aux = eops.build_aux(scn)
    operands = eops._operands(scn, prof, s_vec, q, alloc, aux, w)
    g0, grads0 = eref.era_step_ref(*operands)
    assert np.isfinite(float(g0))
    bm_auto = choose_block_m(250, 1250, scn.cfg.n_aps)
    for bm in {bm_auto, 64}:             # 64 ∤ 250 → short remainder block
        g_t, grads_t = eref.era_step_ref(*operands, block_m=bm)
        np.testing.assert_allclose(float(g_t), float(g0), rtol=1e-5)
        _assert_leaves_close(grads0, grads_t, tol=1e-4)


@pytest.mark.slow
def test_paper_scale_tiled_kernel_interpret():
    """The Pallas grid itself at paper scale (interpret mode, bm=64 →
    nb=4 with a zero-padded remainder block) against the untiled oracle.
    bm=64 rather than the auto bm: interpret mode emulates every grid
    step, so 2×4 steps is tractable where 2×250 is not."""
    scn, prof, q, w, s_vec, alloc = _paper_setup()
    aux = eops.build_aux(scn)
    operands = eops._operands(scn, prof, s_vec, q, alloc, aux, w)
    g0, grads0 = eref.era_step_ref(*operands)
    g_k, *grads_k = era_step_fused(*operands, block_m=64, interpret=True)
    np.testing.assert_allclose(float(g_k[0, 0]), float(g0), rtol=1e-5)
    for a, b in zip(grads0, grads_k):
        assert b.shape == a.shape
    _assert_leaves_close(grads0, grads_k, tol=1e-4)


@pytest.mark.parametrize("interpret", INTERPRET_MODES)
def test_ops_kernel_impl_dispatch(interpret):
    """era_step_value_and_grad(impl='kernel') returns Allocation-shaped
    grads matching the ref dispatch."""
    scn, prof, q, w, s_vec, alloc = _setup()
    g_r, grad_r = eops.era_step_value_and_grad(scn, prof, s_vec, q, alloc,
                                               w, impl="ref")
    g_k, grad_k = eops.era_step_value_and_grad(scn, prof, s_vec, q, alloc,
                                               w, impl="kernel",
                                               interpret=interpret)
    assert grad_k.beta_up.shape == alloc.beta_up.shape
    np.testing.assert_allclose(float(g_k), float(g_r), rtol=1e-5)
    _assert_alloc_close(grad_k, grad_r, 1e-5)


# ----------------------------------------------------------------- solver
@pytest.mark.parametrize("backend,kw", [
    ("reference", {}),
    ("chunked", {"gd_chunk": 8}),
])
def test_fused_solve_matches_xla(backend, kw):
    """Acceptance: step_impl='fused' reproduces the XLA path's full solve —
    Γ trajectory and final allocations within rtol=1e-5, split decisions
    and iteration counts exact.  tol=0.0 pins every lane to max_steps so
    the two paths take identical step counts by construction."""
    scn, prof, q, w, _, _ = _setup(seed=3)
    sx = ligd.SolverSpec(backend=backend, tol=0.0, max_steps=40, **kw)
    ox = ligd.solve(scn, prof, q, w, spec=sx)
    of = ligd.solve(scn, prof, q, w, spec=sx.replace(step_impl="fused"))
    np.testing.assert_allclose(of.gamma_by_layer, ox.gamma_by_layer,
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(of.s), np.asarray(ox.s))
    np.testing.assert_array_equal(np.asarray(of.iters_by_layer),
                                  np.asarray(ox.iters_by_layer))
    _assert_alloc_close(of.alloc, ox.alloc, 1e-5)


@pytest.mark.parametrize("lane_placement", ["none", "sorted"])
def test_fused_solve_matches_xla_sharded(lane_placement):
    """The sharded backend (shard_map + while_loop — the composition that
    miscompiles dynamic gathers on XLA:CPU, see ref.py) with both lane
    placements.  'sorted' runs twice so the second round actually permutes
    lanes from recorded history."""
    cfg = network.small_config(n_users=8, n_subchannels=4)
    scns = [network.make_scenario(jax.random.PRNGKey(i), cfg)
            for i in range(4)]
    prof = profiles.get_profile("nin")
    qb = jnp.full((4, cfg.n_users), 0.4)
    w = Weights()
    sx = ligd.SolverSpec(backend="sharded", gd_chunk=8, tol=0.0,
                         max_steps=40, lane_placement=lane_placement)
    sf = sx.replace(step_impl="fused")
    ligd.reset_lane_history()
    for _round in range(2 if lane_placement == "sorted" else 1):
        ox = ligd.solve_batch(scns, prof, qb, w, spec=sx)
        of = ligd.solve_batch(scns, prof, qb, w, spec=sf)
        for a, b in zip(ox, of):
            np.testing.assert_allclose(b.gamma_by_layer, a.gamma_by_layer,
                                       rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(b.s), np.asarray(a.s))
            np.testing.assert_array_equal(np.asarray(b.iters_by_layer),
                                          np.asarray(a.iters_by_layer))
            _assert_alloc_close(b.alloc, a.alloc, 1e-5)


def test_fused_solve_tiled_matches_untiled():
    """step_block_m tiles the fused step under a full solve: forcing a
    block (including one that does not divide M) must leave the solve's
    outcome at the untiled fused path's answer — the cross-block
    reductions are plain f32 sums, so only roundoff-order differs.
    M=24 so both blocks are legal compiled tiles (multiples of 8)."""
    scn, prof, q, w, _, _ = _setup(m=24, seed=4)
    base = ligd.SolverSpec(tol=0.0, max_steps=40, step_impl="fused")
    o0 = ligd.solve(scn, prof, q, w, spec=base)
    for bm in (8, 16):                             # divisible + remainder
        ot = ligd.solve(scn, prof, q, w,
                        spec=base.replace(step_block_m=bm))
        np.testing.assert_allclose(ot.gamma_by_layer, o0.gamma_by_layer,
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(ot.s), np.asarray(o0.s))
        _assert_alloc_close(ot.alloc, o0.alloc, 1e-5)


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_ops_rounds_block_to_legal_tile(impl):
    """The program's step entry rounds a forced block to the tile Mosaic
    compiles (kernel.legal_block_m) before either impl sees it, so a
    ``step_block_m`` tiles alike on CPU and TPU: 3 runs as 8, and a
    block past M as M — bitwise the same launch."""
    scn, prof, q, w, s_vec, alloc = _setup(u=12, m=24, seed=9)
    aux = eops.build_aux(scn)

    def step(bm):
        return eops.era_step_value_and_grad(scn, prof, s_vec, q, alloc, w,
                                            aux=aux, impl=impl,
                                            interpret=True, block_m=bm)
    for forced, legal in ((3, 8), (30, 24)):
        g_f, grad_f = step(forced)
        g_l, grad_l = step(legal)
        assert float(g_f) == float(g_l)
        for a, b in zip(grad_f, grad_l):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ spec surface
def test_spec_validates_step_impl_and_placement():
    with pytest.raises(ValueError):
        ligd.SolverSpec(step_impl="pallas")
    with pytest.raises(ValueError):
        ligd.SolverSpec(lane_placement="zigzag")
    with pytest.raises(ValueError):
        ligd.SolverSpec(step_block_m=-1)
    with pytest.raises(ValueError):
        # the block knob tiles the fused kernel's grid; meaningless (and
        # so rejected) on the XLA autodiff step
        ligd.SolverSpec(step_block_m=4)
    assert ligd.SolverSpec(step_impl="fused", step_block_m=4).step_block_m \
        == 4
    with pytest.raises(ValueError):
        # sorted placement permutes the batch before shard_map; it is
        # meaningless (and so rejected) off the sharded backend
        ligd.SolverSpec(backend="reference", lane_placement="sorted")
    spec = ligd.SolverSpec(backend="sharded", lane_placement="sorted",
                           step_impl="fused")
    assert spec.step_impl == "fused"


def test_lane_permutation_round_robin():
    """Heaviest lanes (by previous-round iteration count) must stripe
    across shards, not pile onto one."""
    ligd.reset_lane_history()
    assert ligd._lane_permutation(4, 2) is None        # no history yet
    ligd._LANE_ITERS[4] = np.asarray([10, 50, 20, 40])
    assert ligd._lane_permutation(4, 1) is None        # 1 shard: pointless
    perm = ligd._lane_permutation(4, 2)
    assert perm.tolist() == [1, 2, 3, 0]
    # shard 0 gets lanes [1, 2] (iters 50, 20), shard 1 [3, 0] (40, 10):
    # the two heaviest lanes land on different shards
    shard0, shard1 = perm[:2], perm[2:]
    hist = ligd._LANE_ITERS[4]
    assert {int(hist[i]) for i in shard0} == {50, 20}
    assert {int(hist[i]) for i in shard1} == {40, 10}
    ligd.reset_lane_history()
