"""Reduction of a JAX profiler trace (``.xplane.pb``) to device intervals.

A TPU device appears as a plane named ``/device:TPU:<n>``; its ``XLA Ops``
line holds one event per executed operation and its ``XLA Modules`` line one
event per executed program (``jit_<name>(<id>)``). The host plane
``/host:CPU`` holds the benchmark's own ``TraceAnnotation`` spans, whose
names start with ``bench.``; ``bench.window`` brackets the measured window.
Host and device events share one clock in the file, in nanoseconds, to
about a millisecond: in the recorded chip trace under ``bench/testdata`` a
device program starts 1 ms before the host span that launched it.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SHAPE = re.compile(r"\b(pred|[suf](?:8|16|32|64)|bf16)\[([0-9,]*)\]")
BYTES = {"pred": 1, "bf16": 2}


@dataclasses.dataclass
class Device:
    ops: list  # (start_ns, end_ns, name)
    modules: list  # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> Device
    spans: list  # host (start_ns, end_ns, name) whose name starts with bench.

    def window(self) -> tuple[float, float]:
        """(start_ns, end_ns) of the ``bench.window`` span."""
        found = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if not found:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return found[0]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = Device(ops=[], modules=[])
            for line in plane.lines:
                target = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}.get(line.name)
                if target is not None:
                    target.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                                  for e in line.events)
            devices[plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(devices=devices, spans=spans)


def clip(intervals, lo: float, hi: float) -> list:
    """Intervals cut to [lo, hi], empty ones dropped."""
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, *rest))
    return out


def union(intervals) -> list:
    """Sorted, merged (start, end) pairs covering the intervals."""
    merged: list = []
    for s, e, *_ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which some operation ran on the device."""
    return sum(e - s for s, e in union(clip(dev.ops, lo, hi)))


def module_ns(dev: Device, lo: float, hi: float, contains: str) -> float:
    """Device time in [lo, hi] of the programs whose name contains ``contains``."""
    return sum(e - s for s, e in union(clip([m for m in dev.modules if contains in m[2]], lo, hi)))


def op_name(event_name: str, width: int = 160) -> str:
    """The HLO instruction's name, result type (layouts left out) and
    operation from the event's text (``%fusion.12 = bf16[4,2048,576]
    fusion``), at most ``width`` characters."""
    head, _, rest = event_name.partition(" = ")
    found = re.match(r"(\(.*?\)|\S+) ([a-z][a-z0-9-]*)\(", re.sub(r"\{[^{}]*\}", "", rest))
    return (f"{head} = {found.group(1)} {found.group(2)}" if found else head)[:width]


def self_times(ops) -> list:
    """[(name, self ns)]: an operation's time less that of the operations
    nested inside it (a while loop holds its body's operations)."""
    out, stack = [], []  # stack: [end, name, self]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out.append((n, own))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((n, own) for _, n, own in stack)
    return out


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[name, self seconds per device] of the operations that took most
    time, named by their HLO instruction."""
    totals: dict = {}
    for dev in trace.devices.values():
        for name, ns in self_times(clip(dev.ops, lo, hi)):
            key = op_name(name)
            totals[key] = totals.get(key, 0.0) + ns
    per_dev = max(len(trace.devices), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / per_dev / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[what the host was doing, seconds] for the longest gaps in which no
    operation ran on the first device, named by the innermost ``bench.``
    span (other than the window) covering the gap's middle."""
    if not trace.devices:
        return []
    dev = trace.devices[sorted(trace.devices)[0]]
    busy = union(clip(dev.ops, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    inner = [sp for sp in trace.spans if sp[2] != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        covering = [sp for sp in inner if sp[0] <= mid <= sp[1]]
        name = min(covering, key=lambda sp: sp[1] - sp[0])[2] if covering else "host.other"
        out.append([name, (e - s) / 1e9])
    return out


def shapes(text: str) -> list:
    """[(dtype, dims)] of the array types in a piece of HLO text."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in SHAPE.findall(text)]


def nbytes(shape) -> int:
    dt, dims = shape
    return math.prod(dims) * (BYTES.get(dt) or int(dt[1:]) // 8)


def custom_call(event_name: str):
    """(results, operands) of a custom-call operation's event, each a list
    of (dtype, dims), or None for any other operation. The operands' types
    are those printed in its argument list (none where the text omits
    them)."""
    head, sep, rest = event_name.partition(" custom-call(")
    if not sep or " = " not in head:
        return None
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth < 0:
            break
    return shapes(head.split(" = ", 1)[1]), shapes(rest[:i])


def roofline_share(trace: Trace, lo: float, hi: float, cost, peak: dict):
    """Percent of its roofline that a kernel reached in [lo, hi]: over the
    custom calls that ``cost(results, operands)`` recognises, returning
    (operations, bytes), the least time the chip could take (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s), summed, over
    the calls' device time summed. None when no call lies inside."""
    least = spent = 0.0
    for dev in trace.devices.values():
        for s, e, name in dev.ops:
            if s < lo or e > hi:
                continue
            parsed = custom_call(name)
            counted = cost(*parsed) if parsed else None
            if counted is None:
                continue
            flops, moved = counted
            least += max(flops / peak["bf16_flops_per_s"], moved / peak["hbm_bytes_per_s"])
            spent += (e - s) / 1e9
    return 100.0 * least / spent if spent > 0 else None
