"""The fields of a profiler trace (an ``.xplane.pb``, an ``XSpace``)
that the benchmark reads, parsed by protobuf's own parser.

The schema below is a subset of ``tsl/profiler/protobuf/xplane.proto``,
field numbers as there; what it leaves out is skipped while parsing. A
map is read as the repeated key/value entries it is on the wire.
"""
from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_PACKAGE = "chipbench_xspace"
#: message -> (field, number, type, message type or None, repeated)
_SCHEMA = {
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, "XPlane", True)],
    "XPlane": [("name", 2, _F.TYPE_STRING, None, False),
               ("lines", 3, _F.TYPE_MESSAGE, "XLine", True),
               ("event_metadata", 4, _F.TYPE_MESSAGE, "EventMetadataEntry",
                True),
               ("stat_metadata", 5, _F.TYPE_MESSAGE, "StatMetadataEntry",
                True)],
    "XLine": [("name", 2, _F.TYPE_STRING, None, False),
              ("timestamp_ns", 3, _F.TYPE_INT64, None, False),
              ("events", 4, _F.TYPE_MESSAGE, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, None, False),
               ("offset_ps", 2, _F.TYPE_INT64, None, False),
               ("duration_ps", 3, _F.TYPE_INT64, None, False)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, None, False),
              ("str_value", 5, _F.TYPE_STRING, None, False),
              ("ref_value", 7, _F.TYPE_UINT64, None, False)],
    "XEventMetadata": [("name", 2, _F.TYPE_STRING, None, False),
                       ("stats", 5, _F.TYPE_MESSAGE, "XStat", True)],
    "XStatMetadata": [("name", 2, _F.TYPE_STRING, None, False)],
    "EventMetadataEntry": [("key", 1, _F.TYPE_INT64, None, False),
                           ("value", 2, _F.TYPE_MESSAGE, "XEventMetadata",
                            False)],
    "StatMetadataEntry": [("key", 1, _F.TYPE_INT64, None, False),
                          ("value", 2, _F.TYPE_MESSAGE, "XStatMetadata",
                           False)],
}


def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3")
    for message, fields in _SCHEMA.items():
        m = fd.message_type.add(name=message)
        for name, number, kind, type_name, repeated in fields:
            f = m.field.add(name=name, number=number, type=kind,
                            label=_F.LABEL_REPEATED if repeated
                            else _F.LABEL_OPTIONAL)
            if type_name:
                f.type_name = f".{_PACKAGE}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClassesForFiles(
        [fd.name], pool)[f"{_PACKAGE}.XSpace"]


XSpace = _space_class()


def read(path: str):
    """The ``XSpace`` in the file at ``path``."""
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def events(line, names):
    """``[name, start_ns, dur_ns]`` of each event on ``line`` whose
    metadata id is a key of ``names`` (id -> name)."""
    ts = line.timestamp_ns
    return [[names[e.metadata_id], ts + e.offset_ps // 1000,
             e.duration_ps // 1000]
            for e in line.events if e.metadata_id in names]
