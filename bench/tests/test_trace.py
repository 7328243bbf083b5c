"""The reduction from a profiler trace to busy and idle time, kernel time,
per-chip busy time and the breakdown."""
import os

import pytest

from bench.harness import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_names():
    assert trace.op_name("%era_step_fused.8 = (f32[1,1]) custom-call(...)") \
        == "era_step_fused"
    assert trace.op_name("%fusion.174 = (f32[250]) fusion(...)") == "fusion"
    assert trace.op_name("%copy-done.20 = f32[1] copy-done(...)") \
        == "copy-done"
    assert trace.op_name("%select_reduce_fusion = (f32[2]) fusion()") \
        == "select_reduce_fusion"
    assert trace.module_name("jit__vmapped_sweep(18120499250679038215)") \
        == "jit__vmapped_sweep"


def _synthetic():
    # chip 0: a while (0-100) holding two kernels and a fusion; chip 1
    # busy 0-40; window 0-200 with host spans covering the idle tail
    ops = {0: [("%while.3 = () while()", 0, 100),
               ("%era_step_fused.1 = () custom-call()", 10, 30),
               ("%era_step_fused.1 = () custom-call()", 50, 30),
               ("%fusion.7 = () fusion()", 85, 10),
               ("%copy.2 = () copy()", 150, 10)],
           1: [("%fusion.2 = () fusion()", 0, 40)]}
    modules = {0: [("jit__vmapped_sweep(123)", 0, 100),
                   ("jit_copy(9)", 150, 10)],
               1: [("jit_f(1)", 0, 40)]}
    host = [("bench:window", 0, 200), ("bench:cluster.step", 0, 190),
            ("bench:scheduler.schedule", 95, 55)]
    return ops, modules, host


def test_busy_idle_and_kernel_time():
    tr = trace.reduce(*_synthetic(), n_chips=2, window_s=0.0)
    assert tr.window_s == pytest.approx(200e-9)
    assert tr.busy_ns(0) == 110 and tr.busy_ns(1) == 40
    assert tr.busy_s == pytest.approx(75e-9)
    assert tr.idle_share() == pytest.approx(1 - 75 / 200)
    assert tr.op_ns(lambda n: "era_step" in n) == 60
    assert tr.gaps(0) == [(100, 150), (160, 200)]


def test_self_time_breakdown_and_idle_labels():
    tr = trace.reduce(*_synthetic(), n_chips=1, window_s=0.0)
    labels = {n: own for n, _, _, own in tr.chips[0]}
    assert labels["jit__vmapped_sweep/while"] == 100 - 30 - 30 - 10
    assert labels["jit__vmapped_sweep/era_step_fused"] == 30
    bd = tr.breakdown()
    ops = dict(bd["device_ops"])
    assert ops["jit__vmapped_sweep/era_step_fused"] == pytest.approx(60e-9)
    assert ops["jit__vmapped_sweep/while"] == pytest.approx(30e-9)
    gaps = dict(bd["idle_gaps"])
    # 100-150 has schedule open (innermost); 160-200 only cluster.step
    # at its middle (180)
    assert gaps == {"bench:scheduler.schedule": pytest.approx(50e-9),
                    "bench:cluster.step": pytest.approx(40e-9)}


def test_window_clips_events():
    ops, modules, host = _synthetic()
    host = [("bench:window", 20, 60)] + host[1:]
    tr = trace.reduce(ops, modules, host, n_chips=1, window_s=0.0)
    assert tr.busy_ns(0) == 60
    assert tr.op_ns(lambda n: "era_step" in n) == 20 + 30


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three calls of a jitted
    ``fixture_step`` (a fori_loop of matmuls) inside ``bench:step`` spans,
    each followed by a 5 ms ``bench:host_wait`` sleep, all inside
    ``bench:window``."""
    tr = trace.reduce_file(os.path.join(DATA, "fixture.xplane.pb"), 1, 0.0)
    assert list(tr.chips) == [0]
    assert [n for n, _, _ in tr.host].count("bench:step") == 3
    assert tr.window_s == pytest.approx(0.01957702)
    # three short device bursts in a window that mostly sleeps
    assert 0 < tr.busy_s < 0.001
    assert tr.idle_share() > 0.95
    labels = {n for n, _, _, _ in tr.chips[0]}
    assert "jit_fixture_step/while" in labels
    assert "jit_fixture_step/fusion" in labels
    # the while holds the loop body: its self time is a sliver of its span
    whiles = [(d, own) for n, _, d, own in tr.chips[0]
              if n == "jit_fixture_step/while"]
    assert whiles and all(own < d for d, own in whiles)
    bd = tr.breakdown()
    ops = dict(bd["device_ops"])
    assert sum(ops.values()) <= tr.busy_s * (1 + 1e-9)
    gaps = dict(bd["idle_gaps"])
    assert set(gaps) <= {"bench:step", "bench:host_wait", "host: no span"}
    assert gaps["bench:host_wait"] > 0.9 * tr.window_s
