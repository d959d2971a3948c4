"""Profiler capture, and the reduction from a trace to per-layer numbers.

A trace is read with ``jax.profiler.ProfileData`` and reduced to plain
``(name, start_ns, end_ns)`` triples: the device operations of each chip
(the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane) and the benchmark's
own host spans (``bench.*`` annotations, on the same clock).  Everything
below works on those triples, so the CPU tests feed it synthetic events.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil

import jax

OPS_LINE = "XLA Ops"
CHIP_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(text: str, stats=lambda: ()) -> str:
    """A device op's name for the reduction: the HLO instruction up to its
    operands, without layouts (``%fusion.3 = f32[8,256] fusion``); a
    custom call (a Pallas kernel) also carries its string stats (``stats``
    returns the event's (name, value) pairs), where the kernel's own name
    is."""
    clean = _LAYOUT.sub("", text)
    m = re.match(r"(\S+) = (.*?)\s([\w.-]+)\(", clean)
    head = (f"{m.group(1)} = {m.group(2)[:60]} {m.group(3)}" if m
            else clean[:120])
    if "custom-call" in head:
        extra = " ".join(str(v) for _, v in stats() if isinstance(v, str))
        return f"{head} [{extra[:400]}]"
    return head


@dataclasses.dataclass
class Trace:
    devices: dict          # plane name -> [(op name, start_ns, end_ns)]
    spans: list            # [(span name, start_ns, end_ns)] host spans
    window: tuple          # (start_ns, end_ns) of the measured window


class Spans:
    """Host spans around the calls into each layer.  With tracing on a
    span is a ``TraceAnnotation`` in the profiler's host plane, on the
    device trace's clock; with tracing off it costs nothing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        return contextlib.nullcontext()

    def wrap(self, name: str, fn):
        if not self.on:
            return fn

        def spanned(*args, **kwargs):
            with self(name):
                return fn(*args, **kwargs)
        return spanned


@contextlib.contextmanager
def capture(log_dir: str):
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    for plane in data.planes:
        if CHIP_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name, lambda e=e: e.stats),
                                float(e.start_ns), float(e.end_ns))
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name[len(SPAN_PREFIX):], float(e.start_ns),
                              float(e.end_ns)) for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if not wins:
        raise ValueError("the trace holds no bench.window span")
    return Trace(devices, spans, wins[-1])


# ---------------------------------------------------------------------------
# reduction (pure functions of event triples)
# ---------------------------------------------------------------------------

def merged(events, lo: float, hi: float) -> list:
    """The union of the events' intervals clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    ivs = sorted((max(s, lo), min(e, hi)) for _, s, e in events
                 if e > lo and s < hi)
    out = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(events, lo, hi))


def idle_gaps(events, lo: float, hi: float) -> list:
    """(start, end) of every stretch of [lo, hi] with no event running."""
    gaps, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def matching(events, patterns) -> list:
    """The events whose name matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return [ev for ev in events if any(r.search(ev[0]) for r in rx)]


def summed_ns(events, lo: float, hi: float) -> float:
    """Summed durations of the events, clipped to [lo, hi]."""
    return sum(min(e, hi) - max(s, lo) for _, s, e in events
               if e > lo and s < hi)


def label(gap, spans) -> str:
    """The innermost benchmark span open at the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    open_ = [(e - s, n) for n, s, e in spans
             if s <= mid <= e and n != WINDOW_SPAN[len(SPAN_PREFIX):]]
    return min(open_)[1] if open_ else "no_span"


def self_times(events, lo: float, hi: float) -> dict:
    """Per op name, the summed self time inside [lo, hi]: an event's
    duration less that of the events nested in it (a loop op holds the
    ops of its body on the same line)."""
    tot: dict = {}
    stack: list = []                  # [name, start, end, child ns]

    def close(entry):
        name, s, e, child = entry
        own = max(0.0, min(e, hi) - max(s, lo)) - child
        tot[name] = tot.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        # an op that ended, or that this one only partly overlaps, is done
        while stack and (stack[-1][2] <= s or stack[-1][2] < e):
            close(stack.pop())
        if stack:
            stack[-1][3] += max(0.0, min(e, hi) - max(s, lo))
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return tot


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[op name, seconds]] of the ops with the most device self time
    inside the window, averaged over the chips."""
    lo, hi = trace.window
    tot: dict = {}
    for evs in trace.devices.values():
        for name, ns in self_times(evs, lo, hi).items():
            tot[name] = tot.get(name, 0.0) + ns
    k = max(len(trace.devices), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def top_gaps(trace: Trace, n: int = 10) -> list:
    """[[host span, seconds]] of the longest idle gaps of the first chip,
    each labelled by what the benchmark's host code was doing."""
    lo, hi = trace.window
    if not trace.devices:
        return []
    first = sorted(trace.devices)[0]
    gaps = sorted(idle_gaps(trace.devices[first], lo, hi),
                  key=lambda g: g[0] - g[1])[:n]
    return [[label(g, trace.spans), (g[1] - g[0]) / 1e9] for g in gaps]


def device_time(trace: Trace) -> dict:
    """busy_s (mean over chips), window_s, and each chip's busy share."""
    lo, hi = trace.window
    per = {name: busy_ns(evs, lo, hi) / 1e9
           for name, evs in sorted(trace.devices.items())}
    window_s = (hi - lo) / 1e9
    busy = sum(per.values()) / max(len(per), 1)
    return {"busy_s": busy, "window_s": window_s,
            "per_device_busy_s": per}
