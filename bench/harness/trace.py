"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy and idle time, the time of named
device operations, each chip's busy time, and the ``breakdown`` of the
result line.

Device operations are the events of each TPU plane's ``XLA Ops`` line; a
chip is busy wherever one of them runs (the union of their intervals).
Ops nest there (a ``while`` holds its body's ops), so the breakdown ranks
operations by self time: an op's time less that of the ops inside it.
Host spans are the host plane's events named by the benchmark's own
``TraceAnnotation`` wrappers (``bench:cluster.step``,
``bench:scheduler.schedule`` ...); the traced window is the harness's
``bench:window`` span, and where that is missing the extent of all events.

An operation is named by its HLO module (the ``XLA Modules`` event that
holds it, without its fingerprint) and its HLO instruction name without
the numeric suffix, e.g. ``jit__vmapped_sweep/era_step_fused``: names that
stay put when the compiler renumbers.  A Pallas kernel's instruction is
named after its kernel.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:[.\-]\d+)*\s*(?:=|$)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")

Event = Tuple[str, int, int, int]    # label, start ns, duration ns, self ns


def op_name(hlo_text: str) -> str:
    """``%fusion.174 = (...) fusion(...)`` -> ``fusion``."""
    m = _OP.match(hlo_text.strip())
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def module_name(text: str) -> str:
    """``jit__vmapped_sweep(1812...)`` -> ``jit__vmapped_sweep``."""
    return _MODULE.match(text.strip()).group(1)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def label_ops(ops: List[Tuple[str, int, int]],
              modules: List[Tuple[str, int, int]]) -> List[Event]:
    """Label each op ``module/op`` and work out its self time."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out: List[Event] = []
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    stack: List[int] = []             # indices into out, open parents
    for i in order:
        text, s, d = ops[i]
        k = bisect.bisect_right(starts, s) - 1
        mod = module_name(mods[k][0]) if k >= 0 \
            and s < mods[k][1] + mods[k][2] else ""
        name = op_name(text)
        while stack and out[stack[-1]][1] + out[stack[-1]][2] <= s:
            stack.pop()
        if stack:                     # charge this op to its parent
            p = out[stack[-1]]
            out[stack[-1]] = (p[0], p[1], p[2], p[3] - min(d, p[1] + p[2]
                                                           - s))
        out.append((f"{mod}/{name}" if mod else name, s, d, d))
        stack.append(len(out) - 1)
    return out


@dataclass
class Trace:
    """Events of one traced window, reduced per chip."""
    chips: Dict[int, List[Event]]            # chip id -> labelled op events
    host: List[Tuple[str, int, int]]         # benchmark spans
    lo: int                                  # window, trace clock ns
    hi: int
    n_chips: int
    window_s: float
    busy: Dict[int, List[Tuple[int, int]]] = field(default_factory=dict)

    def __post_init__(self):
        for c, evs in self.chips.items():
            self.busy[c] = clip(union([(s, s + d) for _, s, d, _ in evs]),
                                self.lo, self.hi)

    def busy_ns(self, chip: int) -> int:
        return sum(e - s for s, e in self.busy.get(chip, []))

    @property
    def used_chips(self) -> List[int]:
        return sorted(self.chips)[:self.n_chips]

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips the cell uses."""
        chips = self.used_chips
        if not chips:
            return 0.0
        return sum(self.busy_ns(c) for c in chips) / len(chips) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_ns(self, match) -> int:
        """Device ns of the ops whose label satisfies ``match``, summed
        over chips (the union per chip, clipped to the window)."""
        total = 0
        for c in self.used_chips:
            iv = [(s, s + d) for n, s, d, _ in self.chips[c] if match(n)]
            total += sum(e - s for s, e in clip(union(iv), self.lo,
                                                 self.hi))
        return total

    def gaps(self, chip: int) -> List[Tuple[int, int]]:
        """Idle intervals of ``chip`` inside the window."""
        out, t = [], self.lo
        for s, e in self.busy.get(chip, []):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.hi:
            out.append((t, self.hi))
        return out

    def host_at(self, t: int) -> str:
        """The innermost benchmark span open at ``t`` (the window span
        itself does not count), or ``host: no span``."""
        best = None
        for name, s, d in self.host:
            if name != WINDOW_SPAN and s <= t < s + d \
                    and (best is None or d < best[1]):
                best = (name, d)
        return best[0] if best else "host: no span"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations with the most self time (seconds per
        chip, averaged over the chips used), and the first chip's idle
        time by the benchmark span open in the middle of each gap."""
        ops: Dict[str, int] = {}
        for c in self.used_chips:
            for n, s, d, own in self.chips[c]:
                if self.lo <= s < self.hi:
                    ops[n] = ops.get(n, 0) + own
        k = max(len(self.used_chips), 1)
        dev = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        idle: Dict[str, int] = {}
        chips = self.used_chips
        for s, e in self.gaps(chips[0]) if chips else []:
            label = self.host_at((s + e) // 2)
            idle[label] = idle.get(label, 0) + e - s
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v / k * 1e-9] for n, v in dev],
                "idle_gaps": [[n, v * 1e-9] for n, v in gaps]}


def load(path):
    """Per TPU chip its ``XLA Ops`` and ``XLA Modules`` events, and the
    benchmark's host spans, as (name, start ns, duration ns) on the
    trace's common clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            c = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(c, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return ops, modules, host


def reduce(ops, modules, host, n_chips: int, window_s: float) -> Trace:
    chips = {c: label_ops(evs, modules.get(c, [])) for c, evs in ops.items()}
    win = [(s, d) for n, s, d in host if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0][0], win[0][0] + win[0][1]
        window_s = (hi - lo) * 1e-9
    else:
        spans = [(s, s + d) for evs in chips.values() for _, s, d, _ in evs] \
            + [(s, s + d) for _, s, d in host]
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    return Trace(chips=chips, host=host, lo=lo, hi=hi, n_chips=n_chips,
                 window_s=window_s)


def reduce_file(path, n_chips: int, window_s: float) -> Trace:
    return reduce(*load(path), n_chips, window_s)
