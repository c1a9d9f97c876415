"""The ``op_name`` of every compiled op, from the profile itself.

A TPU profile (``.xplane.pb``, an ``XSpace`` protocol buffer) keeps, on its
``/host:metadata`` plane, the optimized HLO of every program that ran: one
event metadata entry per module, named as the module's ``XLA Modules``
events are (``jit_serve_step(<id>)``), holding an ``HloProto`` under the
stat ``Hlo Proto``. Each HLO instruction carries the ``op_name`` JAX gave
it, which holds the ``jax.named_scope`` path. ``jax.profiler.ProfileData``
exposes event stats only, so this reads the few fields it needs straight
from the protobuf wire format, skipping everything else (the op events
are most of the file).

Field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto):
XSpace.planes 1; XPlane.name 2, event_metadata 4 (map entry: key 1, value
2), stat_metadata 5 (same); XEventMetadata.name 2, stats 5;
XStatMetadata.name 2; XStat.metadata_id 1, bytes_value 6; HloProto
.hlo_module 1; HloModuleProto.computations 3; HloComputationProto
.instructions 2; HloInstructionProto.name 1, metadata 7; OpMetadata
.op_name 2.
"""
from __future__ import annotations

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one message: varints as
    ints, everything else as a slice of ``buf`` (a ``memoryview``).
    Raises ValueError on a message it cannot read."""
    pos, end = 0, len(buf)
    while pos < end:
        try:
            key, pos = _varint(buf, pos)
            wire = key & 7
            if wire == 0:
                val, pos = _varint(buf, pos)
            elif wire == 2:
                n, pos = _varint(buf, pos)
                val, pos = buf[pos:pos + n], pos + n
            elif wire == 1:
                val, pos = buf[pos:pos + 8], pos + 8
            elif wire == 5:
                val, pos = buf[pos:pos + 4], pos + 4
            else:
                raise ValueError(f"unsupported protobuf wire type {wire}")
        except IndexError:
            raise ValueError("truncated protobuf message") from None
        if pos > end:
            raise ValueError("truncated protobuf message")
        yield key >> 3, val


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entries(buf) -> tuple[int, object]:
    key, value = 0, b""
    for num, val in fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _op_names(hlo_proto) -> dict[str, str]:
    out: dict[str, str] = {}
    for num, module in fields(hlo_proto):
        if num != 1:
            continue
        for num_c, comp in fields(module):
            if num_c != 3:
                continue
            for num_i, inst in fields(comp):
                if num_i != 2:
                    continue
                name, op_name = "", ""
                for num_f, val in fields(inst):
                    if num_f == 1:
                        name = _text(val)
                    elif num_f == 7:
                        op_name = next((_text(v) for n, v in fields(val)
                                        if n == 2), "")
                out[name] = op_name
    return out


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Module name -> HLO instruction name -> ``op_name``, for every module
    whose HLO the profile's metadata plane holds."""
    out: dict[str, dict[str, str]] = {}
    for num, plane in fields(memoryview(xspace)):
        if num != 1:
            continue
        name, stat_names, events = "", {}, []
        for num_p, val in fields(plane):
            if num_p == 2:
                name = _text(val)
                if name != METADATA_PLANE:
                    break
            elif num_p == 5:
                key, md = _map_entries(val)
                stat_names[key] = next((_text(v) for n, v in fields(md)
                                        if n == 2), "")
            elif num_p == 4:
                events.append(_map_entries(val)[1])
        if name != METADATA_PLANE:
            continue
        for md in events:
            module, protos = "", []
            for num_e, val in fields(md):
                if num_e == 2:
                    module = _text(val)
                elif num_e == 5:
                    stat = dict(fields(val))
                    protos.append(stat)
            for stat in protos:
                if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                    out[module] = _op_names(stat[6])
    return out
