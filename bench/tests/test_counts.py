"""The shape-only counts behind era_step_roofline, era_mfu and serve_mfu."""
import inspect

import pytest

from bench.counts import era_step, transformer


@pytest.mark.parametrize("u,m,n,ops,nbytes", [
    # paper scale: U=1250 users, M=250 subchannels, N=5 APs
    (1250, 250, 5, 44_175_000.0, 20_060_004.0),
    # the small size of the solver tests
    (12, 6, 3, 9_504.0, 4_036.0),
])
def test_era_step_counts_pinned(u, m, n, ops, nbytes):
    assert era_step.step_ops(u, m, n) == ops
    assert era_step.step_bytes(u, m, n) == nbytes
    assert era_step.step_ops(u, m, n, lanes=4) == 4 * ops
    assert era_step.step_bytes(u, m, n, lanes=4) == 4 * nbytes


def test_era_step_counts_depend_on_shape_alone():
    # no tiling or implementation parameter reaches the count
    for fn in (era_step.step_ops, era_step.step_bytes):
        assert list(inspect.signature(fn).parameters) == \
            ["u", "m", "n", "lanes"]
    # and the kernel's auto-sized block at paper scale changes nothing:
    # the same shapes give the same count whatever block_m the program
    # picks (it picks 250 today, 64 when forced)
    from repro.kernels.era_step.kernel import choose_block_m
    assert choose_block_m(250, 1250, 5) == 250
    assert era_step.step_bytes(1250, 250, 5) == 4.0 * (16 * 250 * 1250
                                                       + 12 * 1250 + 1)


def test_era_step_paper_scale_is_bandwidth_bound():
    t, bound = era_step.min_seconds(1250, 250, 5, 197e12, 819e9)
    assert bound == "bytes"
    assert t == pytest.approx(20_060_004 / 819e9)


def test_kernel_label():
    assert era_step.is_kernel("jit__vmapped_sweep/era_step_fused")
    assert not era_step.is_kernel("jit__vmapped_sweep/fusion")


def test_transformer_request_flops():
    model = {"hidden_size": 2048, "num_hidden_layers": 24,
             "num_attention_heads": 16, "num_key_value_heads": 8,
             "intermediate_size": 8192, "vocab_size": 92544}
    per_layer = 2.0 * (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                       + 3 * 2048 * 8192)
    # one token, no decode: every layer's matmuls, attention to itself,
    # and one LM head
    one = transformer.request_flops(model, 1, 1)
    assert one == 24 * (per_layer + 4.0 * 128 * 16) + 2.0 * 2048 * 92544
    # 256-token prompt, 32 served tokens: 287 tokens through the layers,
    # contexts 1..287, 32 heads
    full = transformer.request_flops(model, 256, 32)
    assert full == 24 * (per_layer * 287 + 4.0 * 128 * 16 * 287 * 288 / 2) \
        + 32 * 2.0 * 2048 * 92544
