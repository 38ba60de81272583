"""A reader of the profiler's ``.xplane.pb`` that keeps the metadata
stats of each event.

``jax.profiler.ProfileData`` gives an event's name, times and its own
stats, but not the stats of its metadata, where a TPU profile keeps an
op's ``tf_op`` (its ``op_name`` path).  The ``.trace.json.gz`` written
beside the profile carries ``tf_op`` but stops at a million events.  So
this module decodes the ``XSpace`` protocol buffer itself, with a
descriptor of the messages it reads (field numbers of TSL's
``xplane.proto``), through the installed ``protobuf``.
"""
from __future__ import annotations

import functools

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_I64 = descriptor_pb2.FieldDescriptorProto.TYPE_INT64
_U64 = descriptor_pb2.FieldDescriptorProto.TYPE_UINT64
_STR = descriptor_pb2.FieldDescriptorProto.TYPE_STRING
_MSG = descriptor_pb2.FieldDescriptorProto.TYPE_MESSAGE
_ONE = descriptor_pb2.FieldDescriptorProto.LABEL_OPTIONAL
_MANY = descriptor_pb2.FieldDescriptorProto.LABEL_REPEATED
_PKG = "benchxspace"


def _message(file, name, fields, nested=()):
    msg = file.message_type.add(name=name)
    for entry in nested:
        msg.nested_type.add().CopyFrom(entry)
    for number, fname, ftype, label, type_name in fields:
        f = msg.field.add(name=fname, number=number, type=ftype, label=label)
        if type_name:
            f.type_name = f".{_PKG}.{type_name}"
    return msg


def _map_entry(name, value_type):
    entry = descriptor_pb2.DescriptorProto(name=name)
    entry.options.map_entry = True
    entry.field.add(name="key", number=1, type=_I64, label=_ONE)
    entry.field.add(name="value", number=2, type=_MSG, label=_ONE,
                    type_name=f".{_PKG}.{value_type}")
    return entry


@functools.lru_cache(maxsize=None)
def _xspace_class():
    file = descriptor_pb2.FileDescriptorProto(
        name="benchxspace.proto", package=_PKG, syntax="proto3")
    # Only the fields read here; the others parse as unknown fields.
    _message(file, "XStat", [
        (1, "metadata_id", _I64, _ONE, None),
        (5, "str_value", _STR, _ONE, None),
        (7, "ref_value", _U64, _ONE, None)])
    _message(file, "XEvent", [
        (1, "metadata_id", _I64, _ONE, None),
        (2, "offset_ps", _I64, _ONE, None),
        (3, "duration_ps", _I64, _ONE, None),
        (4, "stats", _MSG, _MANY, "XStat")])
    _message(file, "XLine", [
        (2, "name", _STR, _ONE, None),
        (3, "timestamp_ns", _I64, _ONE, None),
        (4, "events", _MSG, _MANY, "XEvent")])
    _message(file, "XEventMetadata", [
        (2, "name", _STR, _ONE, None),
        (4, "display_name", _STR, _ONE, None),
        (5, "stats", _MSG, _MANY, "XStat")])
    _message(file, "XStatMetadata", [(2, "name", _STR, _ONE, None)])
    _message(file, "XPlane", [
        (2, "name", _STR, _ONE, None),
        (3, "lines", _MSG, _MANY, "XLine"),
        (4, "event_metadata", _MSG, _MANY, "XPlane.EventMetadataEntry"),
        (5, "stat_metadata", _MSG, _MANY, "XPlane.StatMetadataEntry")],
        nested=(_map_entry("EventMetadataEntry", "XEventMetadata"),
                _map_entry("StatMetadataEntry", "XStatMetadata")))
    _message(file, "XSpace", [(1, "planes", _MSG, _MANY, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def _stat_value(stat, names):
    """A string stat's value, stored inline or as a reference to the
    name of a stat metadata entry."""
    if stat.ref_value:
        return names.get(stat.ref_value, "")
    if stat.str_value:
        return stat.str_value
    return None


def read_lines(path: str, plane, lines: tuple[str, ...],
               stat: str) -> dict[str, list[tuple[str, str, int, int]]]:
    """The events of the named ``lines`` of the planes whose name
    ``plane(name)`` accepts, as
    ``(name, stat_value, start_ns, end_ns)`` per line: ``name`` the
    event metadata's display name (or its name), ``stat_value`` the
    string value of stat ``stat`` on the event or on its metadata
    (``""`` where neither has it).  Times in nanoseconds, rounded as
    ``bench.trace.read_events`` rounds ``ProfileData``'s."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: dict[str, list] = {name: [] for name in lines}
    for p in space.planes:
        if not plane(p.name):
            continue
        names = {k: v.name for k, v in p.stat_metadata.items()}
        wanted = {k for k, v in names.items() if v == stat}
        meta = {}
        for k, m in p.event_metadata.items():
            value = next((_stat_value(s, names) for s in m.stats
                          if s.metadata_id in wanted), "")
            meta[k] = (m.display_name or m.name, value or "")
        for line in p.lines:
            if line.name not in out:
                continue
            base_ps = line.timestamp_ns * 1000
            events = out[line.name]
            for e in line.events:
                name, value = meta.get(e.metadata_id, ("", ""))
                own = next((_stat_value(s, names) for s in e.stats
                            if s.metadata_id in wanted), None)
                start = (base_ps + e.offset_ps) // 1000
                events.append((name, own or value, start,
                               int(start + e.duration_ps / 1000)))
    return out
