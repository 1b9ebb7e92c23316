"""Device time per named scope, from a profiler trace.

An ``XLA Ops`` event carries the HLO ``op_name`` of its op, the path of
the jitted function and the named scopes it was traced under
(``jit(expand_step)/verify/while/body/...``), in the stat ``SCOPE_STAT``.
On a TPU v5e that stat sits in the event's metadata, which
``jax.profiler.ProfileData`` does not show, so :func:`op_scopes` reads it
from the serialized ``XSpace`` with ``google.protobuf`` and a description
of the few fields it needs.  An op's time is credited to (jitted
function, first named scope).  Ops nest (a ``while`` holds its body's
ops), so each instant is credited once, to the innermost op running then;
a module's scope seconds never exceed its module seconds.

    data = open(xplane_path, "rb").read()
    pd = ProfileData.from_serialized_xspace(data)
    secs = scope_s(pd, op_scopes(data), trace.reduce(pd).window)
    secs[("expand_step", "verify")]

``bench/trace.py`` does not call this yet: the per-layer metrics read a
``trace.Summary``, which holds no op events, so a metric of scope time
(``expand_verify_ms``) waits for ``trace.reduce`` to keep these seconds.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace

# the op-event stat that carries the HLO op_name path (TPU v5e traces)
SCOPE_STAT = "tf_op"
_JIT = re.compile(r"^jit\((.+)\)$")
Key = Tuple[str, str]


def scope_key(op_name: str) -> Optional[Key]:
    """``(function, scope)`` of an HLO op_name path ``jit(f)/a/.../op``:
    the outermost jitted function and the first path element below it,
    ``""`` for an op directly in the function (an unscoped control-flow
    op's body reads as its primitive, e.g. ``while``).  None for a path
    that does not start with a jitted function."""
    parts = op_name.split("/")
    m = _JIT.match(parts[0])
    if m is None:
        return None
    return m.group(1), (parts[1] if len(parts) > 2 else "")


def innermost(events: Iterable[Tuple[float, float, object]]
              ) -> List[Tuple[float, float, object]]:
    """Split possibly nested ``(start, end, key)`` events into disjoint
    pieces, each instant given to the latest-started event still open
    then (the innermost, for properly nested events)."""
    out: List[Tuple[float, float, object]] = []
    stack: List[Tuple[float, object]] = []   # open (end, key), inner last
    t = None                                 # out covers up to t

    def close_until(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, key = stack.pop()
            if end > t:
                out.append((t, end, key))
                t = end

    for s, e, key in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        if t is None or s > t:
            t = s
        stack.append((e, key))
    close_until(float("inf"))
    return out


def _xspace_class():
    """The message class of an ``XSpace`` reduced to the fields read here
    (field numbers of ``tsl/profiler/protobuf/xplane.proto``; its maps are
    read as their repeated key/value entries, which encode alike)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xspace.proto",
                                             package="bench_xspace")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".bench_xspace." + tname

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    message("XStat", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("str_value", 5, F.TYPE_STRING, one, None),
            ("ref_value", 7, F.TYPE_UINT64, one, None))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("stats", 4, F.TYPE_MESSAGE, rep, "XStat"))
    message("XLine", ("name", 2, F.TYPE_STRING, one, None),
            ("events", 4, F.TYPE_MESSAGE, rep, "XEvent"))
    message("XEventMetadata", ("stats", 5, F.TYPE_MESSAGE, rep, "XStat"))
    message("XStatMetadata", ("name", 2, F.TYPE_STRING, one, None))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
            ("value", 2, F.TYPE_MESSAGE, one, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
            ("value", 2, F.TYPE_MESSAGE, one, "XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_STRING, one, None),
            ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
            ("event_metadata", 4, F.TYPE_MESSAGE, rep, "EventMetadataEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def op_scopes(data: bytes) -> Dict[str, List[Optional[Key]]]:
    """Per device plane, the :func:`scope_key` of each of its ``XLA Ops``
    events in order (None where no ``SCOPE_STAT`` holds a jitted
    function's path), from the serialized ``XSpace``.  An event's own
    stats are read before its metadata's."""
    space = _xspace_class().FromString(data)
    out: Dict[str, List[Optional[Key]]] = {}
    for plane in space.planes:
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not plane.name.startswith("/device:") or not ops:
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}

        def key(stats) -> Optional[Key]:
            for st in stats:
                if names.get(st.metadata_id) == SCOPE_STAT:
                    path = st.str_value or names.get(st.ref_value, "")
                    return scope_key(path)
            return None

        by_meta = {e.key: key(e.value.stats) for e in plane.event_metadata}
        out[plane.name] = [
            (key(ev.stats) if ev.stats else None)
            or by_meta.get(ev.metadata_id) for ev in ops[0].events]
    return out


def scope_s(pd, scopes: Dict[str, Sequence[Optional[Key]]],
            window: trace.Interval) -> Dict[Key, float]:
    """Device seconds per scope key inside ``window`` (ns), summed over the
    device planes of ``pd``; ``scopes`` is :func:`op_scopes` of the same
    trace, whose events it follows in order."""
    lo, hi = window
    out: Dict[Key, float] = {}
    for plane in pd.planes:
        if plane.name not in scopes:
            continue
        line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
        events = list(line.events)
        keys = scopes[plane.name]
        if len(events) != len(keys):
            raise ValueError(f"{len(keys)} scope keys for {len(events)} ops")
        pieces = innermost((ev.start_ns, ev.start_ns + ev.duration_ns, k)
                           for ev, k in zip(events, keys))
        for s, e, k in pieces:
            c = trace.clip([(s, e)], lo, hi)
            if k is not None and c:
                out[k] = out.get(k, 0.0) + trace.length(c) * 1e-9
    return out
