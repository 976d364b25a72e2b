"""Device operations of a profiler trace under the program's named scopes.

The program names its layers with `jax.named_scope` (`sim.arrivals`,
`sim.route`, `sim.private`, `sim.fill`, `sim.serve`).  A scope lands in the
compiled program as each operation's JAX name stack, which the profiler
writes into the `.xplane.pb` as the stat `tf_op` of the operation's event
metadata, e.g. `jit(chunk)/while/body/sim.route/sim.private/...`.
`jax.profiler.ProfileData` does not expose metadata stats, so this module
reads the few XSpace fields it needs from the file itself, with a schema
declared here (the wire format of tsl/profiler/protobuf/xplane.proto; maps
are read as their repeated entries), parsed by the protobuf runtime that
jax already depends on.  It imports no TensorFlow.

`read(path)` returns a `trace_reduce.Trace` whose device planes hold the
"XLA Ops" events with each operation named by the innermost `sim.*`
element of its name stack (operations under no scope keep their short
name), so `Trace.op_seconds` gives the self time per scope.
"""

from __future__ import annotations

import functools
import re

from trace_reduce import OPS, Trace, short

SCOPE = re.compile(r"sim\.[A-Za-z_]+")

_FIELDS = {   # message: [(name, number, type, label, message type)]
    "XSpace": [("planes", 1, "message", "repeated", "XPlane")],
    "XPlane": [("id", 1, "int64", "optional", None),
               ("name", 2, "string", "optional", None),
               ("lines", 3, "message", "repeated", "XLine"),
               ("event_metadata", 4, "message", "repeated", "EventMetaEntry"),
               ("stat_metadata", 5, "message", "repeated", "StatMetaEntry")],
    "EventMetaEntry": [("key", 1, "int64", "optional", None),
                       ("value", 2, "message", "optional", "XEventMetadata")],
    "StatMetaEntry": [("key", 1, "int64", "optional", None),
                      ("value", 2, "message", "optional", "XStatMetadata")],
    "XLine": [("id", 1, "int64", "optional", None),
              ("name", 2, "string", "optional", None),
              ("timestamp_ns", 3, "int64", "optional", None),
              ("events", 4, "message", "repeated", "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", "optional", None),
               ("offset_ps", 2, "int64", "optional", None),
               ("duration_ps", 3, "int64", "optional", None)],
    "XEventMetadata": [("id", 1, "int64", "optional", None),
                       ("name", 2, "string", "optional", None),
                       ("stats", 5, "message", "repeated", "XStat")],
    "XStatMetadata": [("id", 1, "int64", "optional", None),
                      ("name", 2, "string", "optional", None)],
    "XStat": [("metadata_id", 1, "int64", "optional", None),
              ("str_value", 5, "string", "optional", None),
              ("ref_value", 7, "uint64", "optional", None)],
}

@functools.cache
def _space_class():
    """The XSpace message class of the schema above, in a descriptor pool
    of its own."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for name, number, typ, label, ref in fields:
            f = m.field.add(name=name, number=number,
                            type=getattr(F, f"TYPE_{typ.upper()}"),
                            label=getattr(F, f"LABEL_{label.upper()}"))
            if ref:
                f.type_name = f".bench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def scope_of(tf_op: str) -> str | None:
    """The innermost `sim.*` element of a name stack, or None."""
    found = SCOPE.findall(tf_op)
    return found[-1] if found else None


def _op_names(plane) -> dict:
    """metadata id -> the operation's scope, else its short name."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    out = {}
    for entry in plane.event_metadata:
        meta = entry.value
        scope = None
        for stat in meta.stats:
            if stat.metadata_id == tf_op:
                text = stat.str_value or stat_names.get(stat.ref_value, "")
                scope = scope_of(text)
                break
        out[entry.key] = scope or short(meta.name)
    return out


def read(path) -> Trace:
    """Device operation events of `path`, named by scope (see above)."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = _op_names(plane)
        events = []
        for line in plane.lines:
            if line.name != OPS:
                continue
            base_ps = line.timestamp_ns * 1000
            for e in line.events:
                # whole nanoseconds, start and duration each cut down, as
                # jax.profiler.ProfileData gives them
                start = (base_ps + e.offset_ps) // 1000
                events.append((names.get(e.metadata_id, ""), float(start),
                               float(start + e.duration_ps // 1000)))
        devices[plane.name] = {OPS: events}
    return Trace(devices, [])

