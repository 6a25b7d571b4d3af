"""Reduce a profiler trace to device busy/idle time, kernel time, the ops
that took most time and the longest idle gaps, each gap named by the harness span the host
was in.

The reduction works on plain event lists (:class:`Trace`), so it can be
checked on a hand-made trace; :func:`load` fills one from the profiler's
``.xplane.pb``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str  # for a device op, its HLO name without "%" (e.g. "fusion.3")
    start: float  # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: list  # per device: [Event] of device operations
    spans: list  # [Event] host spans named bench.*

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0].start, w[-1].end

    def window_s(self) -> float:
        t0, t1 = self.window()
        return t1 - t0

    def busy_s(self) -> float:
        """Seconds in which some operation ran, inside the window, averaged
        over the devices traced."""
        t0, t1 = self.window()
        per = [_covered(_merge(_clip(ev, t0, t1)), t0, t1) for ev in self.ops]
        return sum(per) / len(per) if per else 0.0

    def kernel_events(self, patterns) -> list:
        """Device ops inside the window whose HLO name matches a pattern."""
        t0, t1 = self.window()
        rx = re.compile("|".join(re.escape(p) for p in patterns))
        return [e for dev in self.ops for e in dev
                if t0 <= e.start < t1 and rx.search(e.name)]

    def kernel_s(self, patterns) -> float:
        return sum(e.dur for e in self.kernel_events(patterns)) / max(len(self.ops), 1)

    def top_ops(self, n: int = 10) -> list:
        """[[op name, seconds]] of the device ops that took most time, names
        stripped of their numeric suffix."""
        t0, t1 = self.window()
        tot: dict = {}
        for dev in self.ops:
            for e in dev:
                if t0 <= e.start < t1:
                    key = re.sub(r"\.\d+$", "", e.name)
                    tot[key] = tot.get(key, 0.0) + e.dur / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[[host span, seconds]] of the longest device-idle gaps in the
        window (first device), each named by the innermost harness span that
        holds the gap's midpoint."""
        t0, t1 = self.window()
        busy = _merge(_clip(self.ops[0], t0, t1)) if self.ops else []
        gaps, cur = [], t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            gaps.append((cur, t1))
        inner = [s for s in self.spans if s.name != WINDOW_SPAN]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (a + b)
            holders = [s for s in inner if s.start <= mid <= s.end]
            name = min(holders, key=lambda s: s.dur).name if holders else "no span"
            out.append([name, b - a])
        return out


def _clip(events, t0, t1):
    return [(max(e.start, t0), min(e.end, t1)) for e in events if e.end > t0 and e.start < t1]


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(merged, t0, t1):
    return sum(min(e, t1) - max(s, t0) for s, e in merged if e > t0 and s < t1)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read the profiler's xplane file: each TPU device plane's "XLA Ops"
    line, and the host's bench.* spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev_ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    # An op event is named by its HLO text, "%name = type op(...)".
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    s = ev.start_ns * 1e-9
                    dev_ops.append(Event(name, s, s + ev.duration_ns * 1e-9))
            ops.append(dev_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append(Event(ev.name, s, s + ev.duration_ns * 1e-9))
    spans.sort(key=lambda e: e.start)
    return Trace(ops=ops, spans=spans)
