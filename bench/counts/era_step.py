"""Operations and bytes that one gradient step of the ERA utility needs, as
a function of the problem's shape alone: users U, subchannels M, APs N and
the number of cells (lanes) stepped together.  Never of a kernel's tiling
or implementation: a cheaper formulation of the same step reads fewer
bytes of nothing here, so its share of this roofline cannot pass 100%.

Bytes, 4 per f32 or int32 element, each input read once and each gradient
written once:

* in: both relaxed subchannel assignments (2 M U), the up- and downlink
  gains to every AP (2 N M U), the two per-channel SIC decode orders
  (2 M U), and per user the powers, compute share, QoE threshold, the
  split point's four profile entries and the serving AP (9 U);
* out: the gradients of both assignments (2 M U), of both powers and the
  compute share (3 U), and Gamma (1).

Operations, forward per (channel, user) element and direction, counting
each add, multiply, divide, compare and transcendental once: uplink
13 + 2N (the SIC suffix sum is one add per element given the decode
order: no (U, U) mask), downlink 14 + 2N; the backward pass at twice the
forward; and 30 operations per user for the delay, energy and QoE tail,
again times three with its backward.
"""
from __future__ import annotations


def step_bytes(u: int, m: int, n: int, lanes: int = 1) -> float:
    return 4.0 * lanes * ((6 + 2 * n) * m * u + 12 * u + 1)


def step_ops(u: int, m: int, n: int, lanes: int = 1) -> float:
    per_elem = (13 + 2 * n) + (14 + 2 * n)
    return float(lanes * (3 * per_elem * m * u + 3 * 30 * u))


def min_seconds(u: int, m: int, n: int, peak_flops: float, mem_bw: float,
                lanes: int = 1):
    """The least time the chip could take for the step, and which bound
    binds ('bytes' or 'ops')."""
    t_ops = step_ops(u, m, n, lanes) / peak_flops
    t_bytes = step_bytes(u, m, n, lanes) / mem_bw
    return max(t_ops, t_bytes), ("bytes" if t_bytes >= t_ops else "ops")


def is_kernel(label: str) -> bool:
    """Whether a device operation (``bench/harness/trace.op_label``) is the
    fused era_step Pallas kernel."""
    return "era_step" in label
