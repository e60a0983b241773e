"""Device time by the program's scopes, and idle gaps named by its host spans.

The round program puts its layers in ``jax.named_scope`` scopes and its
driver puts each phase in a ``TraceAnnotation`` span (``repro.tracing``;
names start with ``repro.``). A TPU trace keeps an operation's scoped path
as the ``tf_op`` stat of the operation's event metadata, which
``jax.profiler.ProfileData`` does not expose, so :func:`tf_ops` decodes it
from the ``.xplane.pb`` file itself. :func:`load` returns the trace of
:func:`bench.trace.load` with that map and the ``repro.`` host spans added;
every reading of :mod:`bench.trace` stays as it was.
"""
from __future__ import annotations

import dataclasses

from bench import trace as tr

PROGRAM_PREFIX = "repro."
METADATA_PLANE = "/host:metadata"
TF_OP = "tf_op"
UNSCOPED = "unscoped"

# protobuf field numbers (tsl/profiler/protobuf/xplane.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
META_NAME, META_STATS = 2, 5
STAT_META_ID, STAT_META_NAME = 1, 2
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7


@dataclasses.dataclass
class ScopedTrace(tr.Trace):
    tf_ops: dict  # device operation's event name -> its scoped op path
    program_spans: list  # host (start_ns, end_ns, name) whose name starts with repro.


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message:
    varints as ints, length-delimited fields as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not in XSpace")
        yield key >> 3, value


def _map_values(entry) -> memoryview:
    return next((v for f, v in _fields(entry) if f == MAP_VALUE), memoryview(b""))


def _varint_bytes(value: int) -> bytes:
    out = bytearray()
    while value >= 0x80:
        out.append(value & 0x7F | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def save_without_hlo(src: str, dst: str) -> None:
    """Copy a trace leaving out the programs' HLO (``/host:metadata``),
    which most of a small trace's bytes are and no reader here reads."""
    with open(src, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for field, value in _fields(space):  # every field of XSpace is length-delimited
        if field == SPACE_PLANES and next(
                (bytes(v).decode() for f, v in _fields(value) if f == PLANE_NAME), "") == METADATA_PLANE:
            continue
        out += _varint_bytes(field << 3 | 2) + _varint_bytes(len(value)) + value
    with open(dst, "wb") as f:
        f.write(out)


def tf_ops(path: str) -> dict:
    """{event name: ``tf_op`` path} of the operations on the trace's device
    planes, read from each plane's event metadata (the planes' event lines
    are skipped unparsed). Operations without the stat are left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(space):
        if field != SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == PLANE_NAME:
                name = bytes(v).decode()
            elif f == PLANE_EVENT_METADATA:
                events.append(_map_values(v))
            elif f == PLANE_STAT_METADATA:
                meta = dict(_fields(_map_values(v)))
                stat_names[meta.get(STAT_META_ID, 0)] = bytes(meta.get(STAT_META_NAME, b"")).decode()
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
        for meta in events:
            ev_name, path_ = None, None
            for f, v in _fields(meta):
                if f == META_NAME:
                    ev_name = bytes(v).decode()
                elif f == META_STATS:
                    stat = dict(_fields(v))
                    if stat.get(STAT_METADATA_ID) in tf_op_ids:
                        if STAT_STR in stat:
                            path_ = bytes(stat[STAT_STR]).decode()
                        elif STAT_REF in stat:
                            path_ = stat_names.get(stat[STAT_REF])
            if ev_name is not None and path_:
                out[ev_name] = path_
    return out


def load(path: str) -> ScopedTrace:
    """:func:`bench.trace.load`, plus the device operations' ``tf_op`` map
    and the host spans of the program."""
    from jax.profiler import ProfileData

    base = tr.load(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events if e.name.startswith(PROGRAM_PREFIX))
    return ScopedTrace(devices=base.devices, spans=base.spans, tf_ops=tf_ops(path),
                       program_spans=spans)


def self_scoped(ops, tf_ops: dict, scopes, infer: bool = True) -> list:
    """[(name, self ns, scope)] of the operations: self time as
    :func:`bench.trace.self_times` gives it, and the first of ``scopes``
    that the operation's ``tf_op`` path holds, else ``UNSCOPED``. XLA gives
    the operations it adds itself (layout copies, loops, some multi-output
    fusions) no ``tf_op``; with ``infer`` such an operation takes the scope
    of the operations around it where the one before it and the one after
    it (in start order, among those with a ``tf_op``) hold the same scope."""
    ordered = sorted(ops, key=lambda o: (o[0], -o[1]))
    known = [None if name not in tf_ops else
             next((sc for sc in scopes if sc in tf_ops[name]), UNSCOPED)
             for _, _, name in ordered]
    after, later = [], None  # the known scope of the next operation with one
    for own in reversed(known):
        after.append(later)
        later = own if own is not None else later
    scope, before = [], None
    for own, later in zip(known, reversed(after)):
        if own is not None:
            before = own
        elif not (infer and before is not None and before == later):
            own = UNSCOPED
        scope.append(own if own is not None else before)
    # the labels are indices: self_times sorts as above, and keeps them apart
    return [(ordered[i][2], ns, scope[i])
            for i, ns in tr.self_times([(s, e, i) for i, (s, e, _) in enumerate(ordered)])]


def scope_split(trace: ScopedTrace, lo: float, hi: float, scopes, infer: bool = True) -> dict:
    """{scope: ns, ``UNSCOPED``: ns}: the self time in [lo, hi] of the
    operations of each scope (:func:`self_scoped`; an operation goes to the
    first scope it holds), and of the rest, averaged over the devices. Self
    time leaves out what a ``while`` operation's body holds, so the values
    add up to the device's busy time."""
    out = dict.fromkeys([*scopes, UNSCOPED], 0.0)
    for dev in trace.devices.values():
        for _, ns, scope in self_scoped(tr.clip(dev.ops, lo, hi), trace.tf_ops, scopes, infer):
            out[scope] += ns
    per_dev = max(len(trace.devices), 1)
    return {k: v / per_dev for k, v in out.items()}


def scope_ns(trace: ScopedTrace, lo: float, hi: float, scope: str) -> float:
    """Device self time in [lo, hi] of the operations inside ``scope``,
    averaged over the devices."""
    return scope_split(trace, lo, hi, [scope])[scope]


def scope_share(ctx, scope: str):
    """The per-layer reading of ``scope``: its device self time over the
    traced window, or None where no operation in the window carries it (a
    trace without the ``tf_op`` map, or a program without the scope)."""
    trace = ctx.trace
    if trace is None or not trace.devices or not getattr(trace, "tf_ops", None):
        return None
    lo, hi = ctx.trace_window_ns
    ns = scope_ns(trace, lo, hi, scope)
    return ns / 1e9 / ctx.trace_window_s if ns > 0 else None


def top_unscoped(trace: ScopedTrace, lo: float, hi: float, scopes, n: int = 5) -> list:
    """[name, self seconds per device, ``tf_op`` or ""] of the operations
    in [lo, hi] that lie in none of ``scopes`` and took most time."""
    totals: dict = {}
    for dev in trace.devices.values():
        for name, ns, scope in self_scoped(tr.clip(dev.ops, lo, hi), trace.tf_ops, scopes):
            if scope == UNSCOPED:
                key = (tr.op_name(name), trace.tf_ops.get(name, ""))
                totals[key] = totals.get(key, 0.0) + ns
    per_dev = max(len(trace.devices), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / per_dev / 1e9, path] for (name, path), ns in ranked]


def idle_gaps(trace: ScopedTrace, lo: float, hi: float, n: int = 10) -> list:
    """:func:`bench.trace.idle_gaps`, where a gap that no ``bench.`` span
    covers is named by the innermost ``repro.`` span covering its middle,
    and only then ``host.other``."""
    by_bench = tr.idle_gaps(trace, lo, hi, n=n)
    # the same gaps in the same order: they depend on the device's operations only
    by_program = tr.idle_gaps(tr.Trace(devices=trace.devices, spans=trace.program_spans),
                              lo, hi, n=n)
    return [[b if b != "host.other" else p, secs]
            for (b, secs), (p, _) in zip(by_bench, by_program)]
