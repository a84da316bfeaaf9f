"""Reads and writes the profiler's ``.xplane.pb`` with ``google.protobuf``
alone. ``jax.profiler.ProfileData`` shows an event's own statistics but not
those of its metadata, and that is where the compiler keeps an operation's
category (``hlo_category``) and the name stack it was traced under
(``tf_op``). The schema below is the part of tsl's ``xplane.proto`` that the
reduction reads; unknown fields survive a round trip.
"""

from __future__ import annotations

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64", False), ("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "map:XEventMetadata", True),
               ("stat_metadata", 5, "map:XStatMetadata", True),
               ("stats", 6, "XStat", True)],
    "XLine": [("id", 1, "int64", False), ("display_id", 10, "int64", False),
              ("name", 2, "string", False),
              ("display_name", 11, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("duration_ps", 9, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("num_occurrences", 5, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("bytes_value", 6, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "string", False),
                       ("display_name", 4, "string", False),
                       ("metadata", 3, "bytes", False),
                       ("stats", 5, "XStat", True),
                       ("child_id", 6, "int64", True)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "string", False),
                      ("description", 3, "string", False)],
}
_SCALARS = {"int64": _T.TYPE_INT64, "uint64": _T.TYPE_UINT64,
            "double": _T.TYPE_DOUBLE, "string": _T.TYPE_STRING,
            "bytes": _T.TYPE_BYTES}
_PACKAGE = "bench_xplane"


def _build():
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for msg_name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=msg_name)
        for name, number, kind, repeated in fields:
            f = msg.field.add(name=name, number=number)
            f.label = _T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL
            if kind in _SCALARS:
                f.type = _SCALARS[kind]
            elif kind.startswith("map:"):
                entry = msg.nested_type.add(
                    name="".join(p.title() for p in name.split("_")) + "Entry")
                entry.options.map_entry = True
                entry.field.add(name="key", number=1, type=_T.TYPE_INT64,
                                label=_T.LABEL_OPTIONAL)
                entry.field.add(name="value", number=2, type=_T.TYPE_MESSAGE,
                                label=_T.LABEL_OPTIONAL,
                                type_name=f".{_PACKAGE}.{kind[4:]}")
                f.type = _T.TYPE_MESSAGE
                f.type_name = f".{_PACKAGE}.{msg_name}.{entry.name}"
            else:
                f.type = _T.TYPE_MESSAGE
                f.type_name = f".{_PACKAGE}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


XSpace = _build()


def load(path: str):
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat, stat_names: dict, plane):
    for field in ("str_value", "double_value", "int64_value", "uint64_value"):
        v = getattr(stat, field)
        if v:
            return v
    if stat.ref_value:
        return plane.stat_metadata[stat.ref_value].name
    return 0


def events(plane, line):
    """``(start_s, end_s, name, stats)`` of every event of a line, ``stats``
    holding the event's own statistics over those of its metadata."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    meta_cache = {}
    base_ps = line.timestamp_ns * 1000
    for ev in line.events:
        cached = meta_cache.get(ev.metadata_id)
        if cached is None:
            md = plane.event_metadata[ev.metadata_id]
            cached = (md.name, {names.get(s.metadata_id, ""): stat_value(
                s, names, plane) for s in md.stats})
            meta_cache[ev.metadata_id] = cached
        name, stats = cached
        if ev.stats:
            stats = dict(stats)
            for s in ev.stats:
                stats[names.get(s.metadata_id, "")] = stat_value(
                    s, names, plane)
        start = (base_ps + ev.offset_ps) * 1e-12
        yield start, start + ev.duration_ps * 1e-12, name, stats


KEPT_STATS = ("hlo_category", "tf_op", "hlo_op")


def trim(path_in: str, path_out: str, begin_s: float, end_s: float,
         planes=("/device:TPU:", "/host:CPU")) -> None:
    """Keep the events that start inside ``[begin_s, end_s)`` of the planes
    named, and only the metadata they use, names cut to 160 characters and
    statistics to those the reduction reads: a small trace cut from a
    recorded one, for the self-check."""
    space = load(path_in)
    out = XSpace()
    for plane in space.planes:
        if not plane.name.startswith(tuple(planes)):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        used_meta, used_stats = set(), set()
        for line in plane.lines:
            base_ps = line.timestamp_ns * 1000
            keep = [ev for ev in line.events
                    if begin_s <= (base_ps + ev.offset_ps) * 1e-12 < end_s]
            if not keep:
                continue
            nl = new.lines.add(id=line.id, display_id=line.display_id,
                               name=line.name, display_name=line.display_name,
                               timestamp_ns=line.timestamp_ns,
                               duration_ps=line.duration_ps)
            for ev in keep:
                nl.events.add().CopyFrom(ev)
                used_meta.add(ev.metadata_id)
                used_stats.update(s.metadata_id for s in ev.stats)
        for mid in used_meta:
            md = plane.event_metadata[mid]
            nm = new.event_metadata[mid]
            nm.id, nm.name = md.id, md.name[:160]
            for s in md.stats:
                if plane.stat_metadata[s.metadata_id].name in KEPT_STATS:
                    nm.stats.add().CopyFrom(s)
                    used_stats.add(s.metadata_id)
                    if s.ref_value:
                        used_stats.add(s.ref_value)
        for sid in used_stats:
            if sid in plane.stat_metadata:
                new.stat_metadata[sid].CopyFrom(plane.stat_metadata[sid])
    with open(path_out, "wb") as f:
        f.write(out.SerializeToString())
