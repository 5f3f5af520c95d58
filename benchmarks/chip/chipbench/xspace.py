"""Read a profiler `.xplane.pb` (an XSpace protobuf) without TensorFlow.

The message schema below is the subset of `tsl/profiler/protobuf/xplane.proto`
that the reduction needs; protobuf skips every other field.  Parsing runs in
the protobuf runtime's own (C) parser, so a trace of a few hundred MB reads in
seconds.
"""
from __future__ import annotations

import functools
import gzip

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_SCHEMA = {
    # message: [(field, number, type, repeated, message type)]
    "XSpace": [("planes", 1, _T.TYPE_MESSAGE, True, "XPlane")],
    "XPlane": [("id", 1, _T.TYPE_INT64, False, None),
               ("name", 2, _T.TYPE_STRING, False, None),
               ("lines", 3, _T.TYPE_MESSAGE, True, "XLine"),
               ("event_metadata", 4, _T.TYPE_MESSAGE, True, "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _T.TYPE_MESSAGE, True, "XPlane.StatMetadataEntry"),
               ("stats", 6, _T.TYPE_MESSAGE, True, "XStat")],
    "XLine": [("id", 1, _T.TYPE_INT64, False, None),
              ("name", 2, _T.TYPE_STRING, False, None),
              ("timestamp_ns", 3, _T.TYPE_INT64, False, None),
              ("events", 4, _T.TYPE_MESSAGE, True, "XEvent"),
              ("duration_ps", 9, _T.TYPE_INT64, False, None)],
    "XEvent": [("metadata_id", 1, _T.TYPE_INT64, False, None),
               ("offset_ps", 2, _T.TYPE_INT64, False, None),
               ("duration_ps", 3, _T.TYPE_INT64, False, None),
               ("stats", 4, _T.TYPE_MESSAGE, True, "XStat")],
    "XStat": [("metadata_id", 1, _T.TYPE_INT64, False, None),
              ("double_value", 2, _T.TYPE_DOUBLE, False, None),
              ("uint64_value", 3, _T.TYPE_UINT64, False, None),
              ("int64_value", 4, _T.TYPE_INT64, False, None),
              ("str_value", 5, _T.TYPE_STRING, False, None),
              ("ref_value", 7, _T.TYPE_UINT64, False, None)],
    "XEventMetadata": [("id", 1, _T.TYPE_INT64, False, None),
                       ("name", 2, _T.TYPE_STRING, False, None),
                       ("display_name", 4, _T.TYPE_STRING, False, None),
                       ("stats", 5, _T.TYPE_MESSAGE, True, "XStat")],
    "XStatMetadata": [("id", 1, _T.TYPE_INT64, False, None),
                      ("name", 2, _T.TYPE_STRING, False, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata", "StatMetadataEntry": "XStatMetadata"}
_PKG = "chipbench.xplane"


def _add_fields(msg, fields):
    for name, number, ftype, repeated, type_name in fields:
        f = msg.field.add(name=name, number=number, type=ftype,
                          label=_T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL)
        if type_name:
            f.type_name = f".{_PKG}.{type_name}"


@functools.cache
def _classes():
    fdp = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto", package=_PKG,
                                             syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fdp.message_type.add(name=name)
        _add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                nested = msg.nested_type.add(name=entry)
                nested.options.map_entry = True
                _add_fields(nested, [("key", 1, _T.TYPE_INT64, False, None),
                                     ("value", 2, _T.TYPE_MESSAGE, False, value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def read_xspace(path: str):
    """The XSpace message of one `.xplane.pb` file (gzipped where it ends in `.gz`)."""
    space = _classes()()
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat, names: dict):
    """A stat's value: a referenced string, else whichever value field is set."""
    if stat.ref_value:
        return names.get(stat.ref_value, stat.ref_value)
    if stat.str_value:
        return stat.str_value
    return stat.int64_value or stat.uint64_value or stat.double_value
