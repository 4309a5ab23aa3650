"""Reduction of a JAX profiler trace to device metrics.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per device operation. The benchmark's own host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane, on the same
clock. From these:

* busy time of a device: the union of its operations' intervals inside
  the traced window (span ``bench.window``); idle share = 1 - busy/window;
* time of a kernel: the summed durations of its events, found by a
  stable name;
* idle gaps: the stretches between busy intervals, each named by the
  innermost benchmark span open on the host at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# benchmark spans are named "<area>.<what>" (bench/harness.Run.span)
SPAN_NAME = re.compile(r"[a-z0-9_]+\.[a-z0-9_.]+")


@dataclass
class Op:
    name: str      # the HLO instruction as the trace names it
    start: float   # ns
    dur: float     # ns


def short_name(hlo: str) -> str:
    """An operation's HLO text without layouts and attributes."""
    return re.sub(r"\{[^{}]*\}", "", hlo).split(", kind=")[0].split(", custom_call_target")[0][:160]


def innermost_segments(spans) -> list:
    """Cut possibly nested (name, start, end) spans into non-overlapping
    (start, end, name) segments labelled by the innermost open span."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for a, b in zip(points, points[1:]):
        mid, best = (a + b) / 2, None
        for name, s, e in spans:
            if s <= mid < e and (best is None or s >= best[1]):
                best = (name, s)
        if best:
            out.append((a, b, best[0]))
    return out


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list:
    """(start, end) stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Profile:
    """The traced window: per-device operations and the host spans."""

    def __init__(self, ops: dict, spans: list, window: tuple):
        self.ops = ops          # device plane name -> [Op]
        self.spans = spans      # [(name, start_ns, end_ns)] benchmark spans
        self.window = window    # (start_ns, end_ns)
        self._segments = None

    @classmethod
    def load(cls, trace_dir: str) -> "Profile":
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"no profiler trace under {trace_dir}")
        return cls.from_data(ProfileData.from_file(files[-1]))

    @classmethod
    def from_data(cls, data) -> "Profile":
        ops, spans = defaultdict(list), []
        for plane in data.planes:
            if DEVICE_PLANE.fullmatch(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops[plane.name].extend(
                            Op(e.name, e.start_ns, e.duration_ns) for e in line.events
                        )
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if SPAN_NAME.fullmatch(e.name):
                            spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not win:
            raise RuntimeError(f"trace holds no {WINDOW_SPAN!r} span")
        return cls(dict(ops), spans, win[-1])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_ns(self, plane: str) -> float:
        return union_ns(((o.start, o.start + o.dur) for o in self.ops[plane]), *self.window)

    def busy_s(self, devices: int) -> float:
        """Busy seconds averaged over the ``devices`` traced chips."""
        if len(self.ops) != devices:
            raise RuntimeError(f"trace has {len(self.ops)} device planes, run used {devices}")
        return sum(self.busy_ns(p) for p in self.ops) * 1e-9 / devices

    def idle_share(self) -> float:
        """1 - busy/window, averaged over the traced devices."""
        shares = [1.0 - self.busy_ns(p) / (self.window[1] - self.window[0]) for p in self.ops]
        return sum(shares) / len(shares)

    def kernel_ops(self, pattern: str) -> list:
        """Device events in the window whose name matches ``pattern``."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [o for ops in self.ops.values() for o in ops
                if lo <= o.start < hi and rx.search(o.name)]

    def span_at(self, t: float) -> str:
        """Innermost benchmark span (other than the window) open at ``t``."""
        if self._segments is None:
            self._segments = innermost_segments(
                [sp for sp in self.spans if sp[0] != WINDOW_SPAN])
            self._starts = [a for a, _, _ in self._segments]
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._segments[i][1]:
            return self._segments[i][2]
        return "no span"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds summed over
        devices), and the idle time of all devices by the host span open
        at each gap (seconds summed over devices)."""
        lo, hi = self.window
        by_op, by_span = defaultdict(float), defaultdict(float)
        for ops in self.ops.values():
            for o in ops:
                if lo <= o.start < hi:
                    by_op[o.name] += o.dur * 1e-9
            for s, e in gaps_ns(((o.start, o.start + o.dur) for o in ops), lo, hi):
                by_span[self.span_at((s + e) / 2)] += (e - s) * 1e-9
        by_short = defaultdict(float)
        for name, t in by_op.items():
            by_short[short_name(name)] += t
        rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_short), "idle_gaps": rank(by_span)}
