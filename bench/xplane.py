"""The ``tf_op`` stat of each device operation in a profiler trace.

``jax.profiler.ProfileData`` names a device operation by its HLO text
but does not expose the stats on the operation's metadata.  One of them,
``tf_op``, holds the JAX name stack the operation was lowered from, for
example ``jit(_fixed_point)/while/body/AD/cond/branch_1_fun/WD/lanemap/
gather``: the program's ``jax.named_scope`` names appear in it, and they
do not change from one compile to the next the way HLO numbers do.

This module reads the stat straight from the ``.xplane.pb`` protobuf
wire format with the standard library: ``XSpace.planes`` (field 1);
``XPlane.name`` (2), ``event_metadata`` (4) and ``stat_metadata`` (5),
both maps of id to message; ``XEventMetadata.name`` (2) and ``stats``
(5); ``XStat.metadata_id`` (1) and its value, ``str_value`` (5) or
``ref_value`` (7, the name of another stat metadata entry).
"""

from __future__ import annotations

from pathlib import Path

STAT = "tf_op"


def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    a varint, a ``memoryview`` of the bytes for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _map_value(entry):
    """``(key, value)`` of one protobuf map entry (fields 1 and 2)."""
    key, value = 0, b""
    for num, v in fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def plane_ops(plane) -> dict:
    """``{event name: tf_op}`` of one serialized ``XPlane``."""
    stat_names: dict = {}
    events = []
    for num, v in fields(plane):
        if num == 5:
            sid, meta = _map_value(v)
            stat_names[sid] = next(
                (_text(x) for f, x in fields(meta) if f == 2), "")
        elif num == 4:
            events.append(_map_value(v)[1])
    out = {}
    for meta in events:
        name, value = None, None
        for num, v in fields(meta):
            if num == 2:
                name = _text(v)
            elif num == 5:
                stat = dict(fields(v))
                if stat_names.get(stat.get(1)) != STAT:
                    continue
                if 5 in stat:
                    value = _text(stat[5])
                elif 7 in stat:
                    value = stat_names.get(stat[7])
        if name is not None and value is not None:
            out[name] = value
    return out


def tf_ops(path) -> dict:
    """``{plane name: {event name: tf_op}}`` of the planes of the trace
    file ``path`` whose events carry the stat.  An event name is the
    ``ev.name`` that ``ProfileData`` gives the same event."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for num, plane in fields(data):
        if num != 1:
            continue
        name = next((_text(v) for f, v in fields(plane) if f == 2), "")
        ops = plane_ops(plane)
        if ops:
            out[name] = ops
    return out


def name_stack(tf_op: str) -> list:
    """The name stack of a ``tf_op`` value, split at ``/``; the value
    ends in ``:<op type>``, often empty, which is dropped."""
    stack = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    return [part for part in stack.split("/") if part]
