"""Reads a profiler trace (``.xplane.pb``) as its ``XSpace`` message.

``jax.profiler.ProfileData`` gives events, their times and their own stats,
but not the stats of an event's *metadata*, where XLA keeps each device
op's ``tf_op``: the op's ``op_name``, which carries the program's
``jax.named_scope``s. The schema below is that of
``tsl/profiler/protobuf/xplane.proto`` (OpenXLA TSL, Apache 2.0), the
fields this reader needs written out, so that decoding needs only
``google.protobuf``: wire-compatible, with the proto's maps read as
repeated key/value entries (:func:`tables` makes them dicts).
"""
from __future__ import annotations

import functools

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_SCALARS = {"int64": _F.TYPE_INT64, "uint64": _F.TYPE_UINT64,
            "double": _F.TYPE_DOUBLE, "string": _F.TYPE_STRING,
            "bytes": _F.TYPE_BYTES}

# message: [(field, number, type, repeated)]
SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64", False), ("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, "int64", False), ("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("bytes_value", 6, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("id", 1, "int64", False),
                       ("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64", False),
                      ("name", 2, "string", False)],
}
_ONEOFS = {"XStat": ("value", 2)}    # message: (oneof, from field number)
_PACKAGE = "chipbench.xplane"


@functools.cache
def _space_class():
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench/xplane.proto", package=_PACKAGE, syntax="proto3")
    for msg, fields in SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        oneof = _ONEOFS.get(msg)
        if oneof:
            m.oneof_decl.add(name=oneof[0])
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=_F.LABEL_REPEATED if repeated
                            else _F.LABEL_OPTIONAL)
            if typ in _SCALARS:
                f.type = _SCALARS[typ]
            else:
                f.type, f.type_name = _F.TYPE_MESSAGE, f".{_PACKAGE}.{typ}"
            if oneof and number >= oneof[1]:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def read(path: str):
    """The ``XSpace`` in the file at ``path``."""
    with open(path, "rb") as f:
        return _space_class().FromString(f.read())


def tables(plane):
    """({event metadata id: XEventMetadata}, {stat metadata id: name}) of
    ``plane``."""
    return ({e.key: e.value for e in plane.event_metadata},
            {e.key: e.value.name for e in plane.stat_metadata})


def stats(stat_list, stat_names: dict) -> dict:
    """{stat name: value} of ``stat_list`` (an event's or a metadata's
    ``stats``); a ``ref_value`` is the name of the stat metadata it points
    to, as XPlane interns repeated strings there."""
    out = {}
    for st in stat_list:
        kind = st.WhichOneof("value")
        if kind is not None:
            value = getattr(st, kind)
            out[stat_names[st.metadata_id]] = \
                stat_names[value] if kind == "ref_value" else value
    return out
