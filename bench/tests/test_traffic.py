"""The benchmark's traffic generators: the channel snapshots match the
program's own generator from the same key, and every stream is a function
of the seed alone."""
import numpy as np
import pytest

from bench.harness import traffic as tr
from bench.reference import era as ref


def _net():
    from repro.core import network
    return network.small_config(n_users=12, n_subchannels=6)


def test_initial_snapshot_matches_program_generator():
    from repro.core import network
    net = _net()
    chains = tr.ChannelChains(11, net, 2, 3, 0.85)
    for b in range(2):
        want = network.make_scenario(tr.jax_key(11, 1, b), net)
        got = chains.scenario(b, 0)
        for f in ("assoc", "h_up", "h_dn", "up_order", "up_group_end",
                  "dn_order", "dn_group_end"):
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(want, f)), f)


def test_drift_steps_keep_program_ordering_rule():
    from repro.core import network
    net = _net()
    chains = tr.ChannelChains(3, net, 1, 4, 0.85)
    for i in range(1, 4):
        s = chains.scenario(0, i)
        own_up = np.asarray(s.own_gain_up())
        own_dn = np.asarray(s.own_gain_dn())
        up, up_end = network._orderings(own_up, np.asarray(s.assoc), True)
        dn, dn_end = network._orderings(own_dn, np.asarray(s.assoc), False)
        np.testing.assert_array_equal(np.asarray(s.up_order), up)
        np.testing.assert_array_equal(np.asarray(s.up_group_end), up_end)
        np.testing.assert_array_equal(np.asarray(s.dn_order), dn)
        np.testing.assert_array_equal(np.asarray(s.dn_group_end), dn_end)
        drift = network.scenario_drift(s, chains.scenario(0, i - 1))
        assert 0.05 < drift < 0.5
    # the walk wraps round to the initial snapshot
    assert chains.scenario(0, 4) is chains.scenario(0, 0)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_arrivals_depend_on_seed_alone(seed):
    a = tr.PoissonArrivals(seed, 50.0, 2, 1250, (0.2, 0.6))
    b = tr.PoissonArrivals(seed, 50.0, 2, 1250, (0.2, 0.6))
    # read in different chunks: the same sequence
    got = a.due_by(0.5) + a.due_by(2.0)
    assert got == b.due_by(2.0)
    assert all(0.2 <= q <= 0.6 for *_, q in got)
    assert 100 < len(got) < 300          # ~200 expected at 100/s
    other = tr.PoissonArrivals(seed + 1, 50.0, 2, 1250, (0.2, 0.6))
    assert other.due_by(2.0) != got
    key_a, key_b = tr.jax_key(seed, 1), tr.jax_key(seed + 2**32, 1)
    assert not np.array_equal(np.asarray(key_a), np.asarray(key_b))


def test_chat_round_shapes():
    rng = tr.np_rng(5, 5)
    reposts, prompts = tr.chat_round(rng, 2, 16, 0.2, (0.2, 0.6), 256,
                                     92544)
    assert prompts.shape == (2, 16, 256) and prompts.dtype == np.int32
    assert prompts.max() < 92544
    assert all(0 <= c < 2 and 0 <= u < 16 for c, u, _ in reposts)


def test_percentile_nearest_rank():
    assert tr.percentile(range(1, 101), 95) == 95.0
    assert tr.percentile([3.0], 95) == 3.0
    assert tr.percentile([1.0, float("inf")], 95) == float("inf")


def test_reference_suffix_mask_matches_program_order():
    """In sorted order, the reference's mask sums exactly the same-AP users
    decoded after each user, as the scenario's group ends say."""
    net = _net()
    s = tr.ChannelChains(2, net, 1, 1, 0.85).scenario(0, 0)
    mask = ref.suffix_mask(s.up_group_end)
    ge = np.asarray(s.up_group_end)[0]
    for i in range(mask.shape[0]):
        want = [j for j in range(i + 1, mask.shape[0]) if ge[j] == ge[i]]
        assert list(np.nonzero(mask[i])[0]) == want
