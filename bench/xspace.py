"""The ``op_name`` of every HLO instruction a profile's modules hold, read
from the ``.xplane.pb`` wire format with nothing but the standard library.

``jax.profiler.ProfileData`` gives a device op's HLO text and its timing,
not its instruction's metadata, where ``jax.named_scope`` leaves the
scopes (``jit(episode)/vmap()/while/body/closed_call/learn/...``). The
profile keeps each XLA module's optimised HLO on its ``/host:metadata``
plane: one event metadata a module, named as the module's runs on the
device's ``XLA Modules`` line (``jit_episode(<program id>)``), with the
serialized ``HloProto`` in its ``Hlo Proto`` stat. Instruction names are
unique within a module, so (module, instruction) finds an op's metadata.

Field numbers, from ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``:

  XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5
  (map entries: key 1, value 2); XEventMetadata.name 2, .stats 5;
  XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6;
  HloProto.hlo_module 1; HloModuleProto.computations 3;
  HloComputationProto.instructions 2; HloInstructionProto.name 1,
  .metadata 7; OpMetadata.op_name 2.
"""

from __future__ import annotations

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf, span: tuple):
    """(field number, value) of one message in ``buf[span[0]:span[1]]``:
    an int for a varint, a (start, end) span for a length-delimited
    field; fixed-width fields are skipped."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span: tuple) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def _map_values(buf, plane: tuple, field: int):
    """The value spans of one map field of a plane."""
    for f, entry in _fields(buf, plane):
        if f == field:
            for g, value in _fields(buf, entry):
                if g == 2:
                    yield value


def _module_op_names(buf, hlo_proto: tuple) -> dict:
    out = {}
    for f, module in _fields(buf, hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(buf, module):
            if g != 3:
                continue
            for h, inst in _fields(buf, comp):
                if h != 2:
                    continue
                name, op_name = None, ""
                for k, v in _fields(buf, inst):
                    if k == 1:
                        name = _text(buf, v)
                    elif k == 7:
                        op_name = next((_text(buf, w) for m, w
                                        in _fields(buf, v) if m == 2), "")
                out[name] = op_name
    return out


def op_names(path: str) -> dict:
    """{module: {instruction: op_name}} of every module whose HLO the
    profile at ``path`` holds; an instruction with no metadata has the
    op_name ""."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, plane in _fields(buf, (0, len(buf))):
        if f != 1:
            continue
        name = next((_text(buf, v) for g, v in _fields(buf, plane)
                     if g == 2), "")
        if name != METADATA_PLANE:
            continue
        hlo_ids = set()
        for meta in _map_values(buf, plane, 5):
            stat = dict(_fields(buf, meta))
            if 2 in stat and _text(buf, stat[2]) == HLO_STAT:
                hlo_ids.add(stat.get(1, 0))
        for meta in _map_values(buf, plane, 4):
            module, hlo = None, None
            for g, v in _fields(buf, meta):
                if g == 2:
                    module = _text(buf, v)
                elif g == 5:
                    stat = dict(_fields(buf, v))
                    if stat.get(1, 0) in hlo_ids and 6 in stat:
                        hlo = stat[6]
            if module is not None and hlo is not None:
                out[module] = _module_op_names(buf, hlo)
    return out
