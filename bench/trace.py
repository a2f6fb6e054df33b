"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps set against the benchmark's host spans.

``load`` reads the ``.xplane.pb`` the profiler wrote. Device operations are
the events of each ``/device:*`` plane's ``XLA Ops`` line; where the
trace has no device plane (the CPU backend, in the tests) they are the
events that carry an ``hlo_op`` stat. Host spans are the host plane's
events named ``bench.*``. All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: int
    dur: int
    stats: Dict[str, str] = dataclasses.field(default_factory=dict)
    device: str = ""

    @property
    def end(self) -> int:
        return self.start + self.dur

    @property
    def op(self) -> str:
        """The HLO instruction's name: a TPU trace names an op by its
        whole instruction text (``%fusion.12 = f32[8]{0} fusion(...)``),
        the CPU backend by the name alone."""
        n = self.name[1:] if self.name.startswith("%") else self.name
        return n.split(" = ", 1)[0]

    @property
    def kind(self) -> str:
        """The instruction name without its ``.N`` suffix: for a Pallas
        kernel, the name of the kernel."""
        return re.sub(r"\.\d+$", "", self.op)


@dataclasses.dataclass
class Trace:
    device_ops: List[Event]
    spans: List[Event]

    def window(self, name: str) -> Tuple[int, int]:
        """Start and end of the first host span called ``name``."""
        for s in self.spans:
            if s.name == name:
                return s.start, s.end
        raise ValueError(f"no host span {name!r} in the trace")

    def devices(self) -> List[str]:
        return sorted({e.device for e in self.device_ops})


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    trace: Trace
    lo: int
    hi: int
    window: dict          # steps, seconds, tokens of the traced window
    cell: object          # spec.Cell
    peaks: Optional[dict]
    counters: dict

    def ops(self) -> List[Event]:
        return clip(self.trace.device_ops, self.lo, self.hi)


def _stats(ev) -> Dict[str, str]:
    try:
        return {str(k): str(v) for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def parse(path: str) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, spans, cpu_ops = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops.extend(Event(e.name, int(e.start_ns), int(e.duration_ns),
                                 _stats(e), plane.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, int(e.start_ns),
                                           int(e.duration_ns)))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        cpu_ops.append(Event(e.name, int(e.start_ns),
                                             int(e.duration_ns), st,
                                             "cpu:" + st.get(
                                                 "device_ordinal", "0")))
    ops = ops or cpu_ops
    ops.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(ops, spans)


def load(directory: str) -> Trace:
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return parse(found[0])


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(events: Iterable[Event], lo: int, hi: int) -> List[Event]:
    """The parts of ``events`` inside [lo, hi]."""
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append(dataclasses.replace(e, start=a, dur=b - a))
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(ops: Sequence[Event], lo: int, hi: int) -> float:
    """Seconds in [lo, hi] in which some operation ran, averaged over the
    devices that ran any."""
    inside = clip(ops, lo, hi)
    devs = {e.device for e in inside}
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(b - a for a, b in union(
            (e.start, e.end) for e in inside if e.device == d))
    return total / len(devs) / 1e9


def idle_gaps(ops: Sequence[Event], lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """Intervals of [lo, hi] in which no operation ran (one device)."""
    gaps, t = [], lo
    for a, b in union((e.start, e.end) for e in clip(ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(gap: Tuple[int, int], spans: Sequence[Event],
              exclude: Sequence[str] = ("bench.window",)) -> str:
    """The host span that covers most of the gap; of spans covering it
    alike, the innermost (shortest). ``(no span)`` when none overlaps."""
    best, key = "(no span)", None
    for s in spans:
        if s.name in exclude:
            continue
        ov = min(s.end, gap[1]) - max(s.start, gap[0])
        if ov <= 0:
            continue
        k = (ov, -s.dur)
        if key is None or k > key:
            best, key = s.name, k
    return best


def pick(ops: Iterable[Event], patterns: Sequence[str]) -> List[Event]:
    """Operations whose instruction name, less its ``.N``, matches one of
    the regexes whole. An op that only takes a kernel's result as an
    operand does not match."""
    rx = [re.compile(p) for p in patterns]
    return [e for e in ops if any(r.fullmatch(e.kind) for r in rx)]


def op_label(e: Event) -> str:
    """A readable name for a device operation: the instruction and the
    shape it produces."""
    return e.name[:120]


# control-flow ops whose events enclose the ops they run
ENVELOPES = ("while", "conditional", "call")


def breakdown(tr: Trace, lo: int, hi: int, n: int = 10) -> dict:
    """The device operations that took most time (each instruction, its
    runs summed; loops and conditionals, which enclose other ops, left
    out), and the longest idle gaps with the host span each fell in."""
    inside = clip(tr.device_ops, lo, hi)
    by: Dict[str, int] = {}
    for e in inside:
        if e.kind not in ENVELOPES:
            by[op_label(e)] = by.get(op_label(e), 0) + e.dur
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    dev = tr.devices()[:1]
    gaps = idle_gaps([e for e in inside if e.device in dev], lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return {"device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": [[attribute(g, tr.spans), (g[1] - g[0]) / 1e9]
                          for g in gaps]}
