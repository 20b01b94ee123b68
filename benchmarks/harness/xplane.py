"""Reader of a profiler trace's ``.xplane.pb``, with only the protobuf
runtime.

``jax.profiler.ProfileData`` gives each event's own stats, but neither
its metadata's nor the HLO of the programs that ran, which the trace
keeps in its ``/host:metadata`` plane and where an op's ``pcg/*`` scope
(its ``op_name``) is.  So the messages are declared here, with the
fields this reader needs of tsl/profiler/protobuf/xplane.proto and
xla/service/hlo.proto under their own field numbers (a map is read as
its repeated key/value entries), and parsed directly.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_MESSAGES = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, _F.TYPE_INT64, False),
               ("name", 2, _F.TYPE_STRING, False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, _F.TYPE_INT64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _F.TYPE_INT64, False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, _F.TYPE_INT64, False),
              ("name", 2, _F.TYPE_STRING, False),
              ("timestamp_ns", 3, _F.TYPE_INT64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, False),
               ("offset_ps", 2, _F.TYPE_INT64, False),
               ("duration_ps", 3, _F.TYPE_INT64, False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, False),
              ("double_value", 2, _F.TYPE_DOUBLE, False),
              ("uint64_value", 3, _F.TYPE_UINT64, False),
              ("int64_value", 4, _F.TYPE_INT64, False),
              ("str_value", 5, _F.TYPE_STRING, False),
              ("bytes_value", 6, _F.TYPE_BYTES, False),
              ("ref_value", 7, _F.TYPE_UINT64, False)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, False),
                       ("name", 2, _F.TYPE_STRING, False),
                       ("display_name", 4, _F.TYPE_STRING, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, False),
                      ("name", 2, _F.TYPE_STRING, False)],
    "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    "HloModuleProto": [("name", 1, _F.TYPE_STRING, False),
                       ("computations", 3, "HloComputationProto", True)],
    "HloComputationProto": [("name", 1, _F.TYPE_STRING, False),
                            ("instructions", 2, "HloInstructionProto", True),
                            ("id", 5, _F.TYPE_INT64, False)],
    "HloInstructionProto": [("name", 1, _F.TYPE_STRING, False),
                            ("opcode", 2, _F.TYPE_STRING, False),
                            ("metadata", 7, "OpMetadata", False),
                            ("called_computation_ids", 38, _F.TYPE_INT64,
                             True)],
    "OpMetadata": [("op_type", 1, _F.TYPE_STRING, False),
                   ("op_name", 2, _F.TYPE_STRING, False)],
}
_PKG = "benchxplane"


@functools.lru_cache(maxsize=1)
def _classes():
    fd = descriptor_pb2.FileDescriptorProto(name="benchxplane.proto",
                                            package=_PKG, syntax="proto3")
    for msg, fields in _MESSAGES.items():
        m = fd.message_type.add(name=msg)
        for name, num, typ, rep in fields:
            f = m.field.add(name=name, number=num,
                            label=(_F.LABEL_REPEATED if rep
                                   else _F.LABEL_OPTIONAL))
            if isinstance(typ, str):
                f.type, f.type_name = _F.TYPE_MESSAGE, f".{_PKG}.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {m: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.{m}")) for m in _MESSAGES}


def load(path: str):
    with open(path, "rb") as f:
        space = _classes()["XSpace"]()
        space.ParseFromString(f.read())
    return space


def hlo_op_names(blob: bytes) -> Dict[str, str]:
    """{instruction name: op_name} of one program's serialized HloProto.
    An instruction whose own op_name is empty (a fusion, as a rule) takes
    the op_names of the computations it calls, joined."""
    hlo = _classes()["HloProto"]()
    hlo.ParseFromString(blob)
    comps = {c.id: c for c in hlo.hlo_module.computations}
    own = {i.name: i.metadata.op_name for c in comps.values()
           for i in c.instructions}

    def called(ins, depth=0):
        names = [ins.metadata.op_name] if ins.metadata.op_name else []
        if depth < 4:
            for cid in ins.called_computation_ids:
                for sub in comps[cid].instructions if cid in comps else ():
                    names += called(sub, depth + 1)
        return names

    out = {}
    for c in comps.values():
        for ins in c.instructions:
            out[ins.name] = own[ins.name] or " ".join(called(ins))
    return out


def _value(st, names: Dict[int, str]):
    if st.str_value:
        return st.str_value
    if st.ref_value:
        return names.get(st.ref_value, "")
    if st.bytes_value:
        return st.bytes_value
    for k in ("int64_value", "uint64_value", "double_value"):
        v = getattr(st, k)
        if v:
            return v
    return 0


def stats(stat_list, names: Dict[int, str]) -> dict:
    return {names.get(s.metadata_id, str(s.metadata_id)): _value(s, names)
            for s in stat_list}


class Plane:
    """One plane: its lines' events with their metadata resolved."""

    def __init__(self, plane):
        self.name = plane.name
        self._p = plane
        self.stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        self.meta = {e.key: e.value for e in plane.event_metadata}

    def lines(self):
        return [line.name for line in self._p.lines]

    def events(self, line_name: str = None
               ) -> Iterator[Tuple[str, int, int, int, object]]:
        """(line, start_ns, end_ns, metadata_id, event) of every event."""
        for line in self._p.lines:
            if line_name is not None and line.name != line_name:
                continue
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                s = t0 + ev.offset_ps
                yield (line.name, s // 1000, (s + ev.duration_ps) // 1000,
                       ev.metadata_id, ev)

    def event_name(self, metadata_id: int) -> str:
        m = self.meta.get(metadata_id)
        return m.name if m is not None else ""

    def metadata_stats(self, metadata_id: int) -> dict:
        m = self.meta.get(metadata_id)
        return stats(m.stats, self.stat_names) if m is not None else {}

    def event_stats(self, ev) -> dict:
        return stats(ev.stats, self.stat_names)


def planes(path: str):
    return [Plane(p) for p in load(path).planes]
