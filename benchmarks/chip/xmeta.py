"""The JAX op name (``op_name`` metadata) of every device op in a profiler
trace, read from the serialized ``XSpace`` with the standard library.

``jax.profiler.ProfileData`` gives each device op event its name and its
event stats, but not the stats of the event's metadata, where the
profiler keeps the op's ``tf_op``: the ``op_name`` path JAX gave the
HLO instruction (``jit(block)/while/body/.../fsgld.grad/...``), which
carries every ``jax.named_scope`` the op was traced under. This module
walks the protobuf wire format of the ``XSpace`` for those stats.

An op whose metadata has no ``tf_op`` (the profiler leaves it out where
the instruction has no ``op_name``, as for most fusions) is named from
the HLO module the trace keeps on its ``/host:metadata`` plane: by the
``op_name`` of its fusion's root, else of the first fused instruction
that has one (nested fusions followed). ``fusion.621`` of the recorded
danube trace, the embedding's gradient, is such an op: its root, a
scatter, has no name, and its first named instruction is the gradient's
``convert_element_type``.
"""
from __future__ import annotations

import collections

# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
META_NAME, META_STATS = 2, 5
STAT_METADATA_ID, STAT_STR, STAT_BYTES, STAT_REF = 1, 5, 6, 7
# field numbers of xla/service/hlo.proto
HLO_MODULE = 1
MODULE_COMPUTATIONS = 3
COMP_INSTRUCTIONS, COMP_ID, COMP_ROOT_ID = 2, 5, 6
INSTR_NAME, INSTR_OPCODE, INSTR_METADATA = 1, 2, 7
INSTR_ID, INSTR_CALLED = 35, 38
OPMETA_OP_NAME = 2

HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width ones skipped."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, v


def _message(buf):
    d = collections.defaultdict(list)
    for f, v in _fields(buf):
        d[f].append(v)
    return d


def _first(d, f, default=0):
    return d[f][0] if d.get(f) else default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(values):
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def _map_entries(values):
    """A proto map<int64, Message> field: (key, value message)."""
    for entry in values:
        e = _message(entry)
        yield _first(e, 1), _message(_first(e, 2, b""))


def _stat_names(plane) -> dict:
    return {k: _text(_first(m, META_NAME, b""))
            for k, m in _map_entries(plane.get(PLANE_STAT_METADATA, []))}


def _stats(meta, names) -> dict:
    """A metadata's stats by name: str, bytes, or the name a reference
    value points at. Numeric values are left out."""
    out = {}
    for raw in meta.get(META_STATS, []):
        s = _message(raw)
        name = names.get(_first(s, STAT_METADATA_ID))
        if s.get(STAT_STR):
            out[name] = _text(s[STAT_STR][0])
        elif s.get(STAT_BYTES):
            out[name] = s[STAT_BYTES][0]
        elif s.get(STAT_REF):
            out[name] = names.get(s[STAT_REF][0], "")
        elif name == "program_id":
            out[name] = _first(s, 3) or _first(s, 4)
    return out


def _tf_op_name(tf_op: str) -> str:
    """``tf_op`` is ``<op_name>:<op type>``; JAX leaves the type empty."""
    return tf_op[:tf_op.rindex(":")] if ":" in tf_op else tf_op


class _Module:
    """One HLO module: instructions by name, computations by id."""

    def __init__(self, hlo_proto):
        module = _message(_first(_message(hlo_proto), HLO_MODULE, b""))
        self.comps, self.by_name = {}, {}
        for raw in module.get(MODULE_COMPUTATIONS, []):
            c = _message(raw)
            instrs = [_message(r) for r in c.get(COMP_INSTRUCTIONS, [])]
            root = _first(c, COMP_ROOT_ID)
            instrs.sort(key=lambda i: _first(i, INSTR_ID) != root)
            self.comps[_first(c, COMP_ID)] = instrs
            for ins in instrs:
                self.by_name[_text(_first(ins, INSTR_NAME, b""))] = ins

    @staticmethod
    def own_name(ins) -> str:
        meta = ins.get(INSTR_METADATA)
        if not meta:
            return ""
        return _text(_first(_message(meta[0]), OPMETA_OP_NAME, b""))

    def fused_name(self, ins) -> str:
        """The op_name of a fusion's root, else of its first fused
        instruction that has one, nested fusions followed."""
        if _text(_first(ins, INSTR_OPCODE, b"")) != "fusion":
            return ""
        for cid in _ints(ins.get(INSTR_CALLED, [])):
            for sub in self.comps.get(cid, []):   # the root first
                name = self.own_name(sub) or self.fused_name(sub)
                if name.startswith("jit("):
                    return name
        return ""


def op_names(xspace: bytes) -> dict:
    """``{device plane name: {op event name: op_name}}`` for the planes
    named ``/device:...``, keyed by the event name ``ProfileData`` gives
    (the op's HLO text). An op with no name anywhere maps to ``""``."""
    buf = memoryview(xspace)
    device, hlo = {}, {}
    for f, raw in _fields(buf):
        if f != SPACE_PLANES:
            continue
        plane = _message(raw)
        pname = _text(_first(plane, PLANE_NAME, b""))
        if not (pname.startswith("/device:") or pname == "/host:metadata"):
            continue
        names = _stat_names(plane)
        metas = [(k, _text(_first(m, META_NAME, b"")), _stats(m, names))
                 for k, m in _map_entries(
                     plane.get(PLANE_EVENT_METADATA, []))]
        if pname == "/host:metadata":
            hlo.update({k: st[HLO_PROTO_STAT] for k, _, st in metas
                        if HLO_PROTO_STAT in st})
        else:
            device[pname] = metas
    modules = {}
    out = {}
    for pname, metas in device.items():
        ops = out[pname] = {}
        for _, name, st in metas:
            if "tf_op" in st:
                op = _tf_op_name(st["tf_op"])
            else:
                op = ""
                pid = st.get("program_id")
                if pid in hlo:
                    if pid not in modules:
                        modules[pid] = _Module(hlo[pid])
                    mod = modules[pid]
                    ins = mod.by_name.get(name.split(" = ", 1)[0]
                                          .lstrip("%"))
                    if ins is not None:
                        op = mod.fused_name(ins)
            if not ops.get(name):
                ops[name] = op
    return out
