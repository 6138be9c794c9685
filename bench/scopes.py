"""Device time and idle gaps of a traced window, put down to the round's
named phases.

The program names its phases (``src/repro/core/phases.py``): device work
with ``jax.named_scope`` (``round.*``), which lands in the ``op_name``
metadata of every HLO op traced under it, and the round engine's host
work with ``jax.profiler.TraceAnnotation`` spans (``engine.*``). The
profiler keeps each program's HLO, metadata and all, in the trace: its
``/host:metadata`` plane holds one ``Hlo Proto`` per program, under the
name the device's ``XLA Modules`` line gives the program's executions.
``jax.profiler.ProfileData`` does not show that plane, so ``hlo_protos``
reads it from the ``.xplane.pb`` with a small protobuf wire reader.

An op of the segment program (the one that ran longest in the window) is
put down to the innermost ``round.*`` scope of its ``op_name``; transform
wrappers (``vmap(transpose(jvp(...)))``) are looked through. A fusion
with no metadata of its own takes its fused computation's root's. An op
with no scope is "unscoped". Only ops that run inside an execution of the
segment program count: the carry copy's ``jit_copy`` programs run ops
with colliding names (``copy.1``).

The names are written out here, not imported from ``src/``: the readers
also run over a checkout whose program has no scopes, and there they find
nothing and return None.
"""
from __future__ import annotations

import bisect
import os
import re
from collections import defaultdict

from bench import trace as trace_lib

SCOPES = ("round.sample", "round.phase1", "round.phase2",
          "round.delta_average", "round.server_update")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "engine.")
COPY_PROGRAM = "jit_copy"             # jax.tree.map(jnp.copy, carry)
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_SCOPE = re.compile(r"round\.\w+")


# -- a protobuf wire reader, for the fields used here --------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for a varint, a ``(start, end)`` slice for a
    length-delimited field, None for a fixed-width one."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, value) -> list:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def hlo_protos(path: str) -> dict:
    """Program name -> its serialized ``HloProto`` (a memoryview), from
    the trace's metadata plane (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .bytes_value = 6). Empty where the
    trace holds no such plane."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stats = None, [], []
        for k, v in _fields(buf, *plane):
            if k == 2:
                name = _text(buf, v)
            elif k == 4:
                events.append(v)
            elif k == 5:
                stats.append(v)
        if name != METADATA_PLANE:
            continue
        proto_ids = set()
        for entry in stats:
            for k, v in _fields(buf, *entry):
                if k == 2:
                    meta = dict(_fields(buf, *v))
                    if 2 in meta and _text(buf, meta[2]) == HLO_PROTO_STAT:
                        proto_ids.add(meta.get(1, 0))
        out = {}
        for entry in events:
            for k, v in _fields(buf, *entry):
                if k != 2:
                    continue
                prog, blob = None, None
                for kk, vv in _fields(buf, *v):
                    if kk == 2:
                        prog = _text(buf, vv)
                    elif kk == 5:
                        stat = dict(_fields(buf, *vv))
                        if stat.get(1, 0) in proto_ids and 6 in stat:
                            blob = buf[stat[6][0]:stat[6][1]]
                if prog and blob is not None:
                    out[prog] = blob
        return out
    return {}


def op_names(hlo_proto) -> dict:
    """Instruction name -> ``op_name`` of every instruction of a
    serialized ``HloProto`` (hlo_module = 1; HloModuleProto.computations
    = 3; HloComputationProto.instructions = 2, .id = 5, .root_id = 6;
    HloInstructionProto.name = 1, .opcode = 2, .metadata = 7, .id = 35,
    .called_computation_ids = 38; OpMetadata.op_name = 2). A fusion with
    an empty ``op_name`` takes its fused computation's root's; an
    instruction still without one takes that of the instruction that calls
    its computation: the loops XLA writes for an op (a batched slice's
    ``dynamic-update-slice`` per row) carry no metadata of their own."""
    buf = hlo_proto
    module = dict(_fields(buf)).get(1)
    if module is None:
        return {}
    comps = {}                           # id -> (root id, {id: instr})
    for k, comp in _fields(buf, *module):
        if k != 3:
            continue
        cid, root, rows = 0, 0, []
        for kk, v in _fields(buf, *comp):
            if kk == 5:
                cid = v
            elif kk == 6:
                root = v
            elif kk == 2:
                name, opcode, op_name, iid, called = "", "", "", 0, []
                for f, x in _fields(buf, *v):
                    if f == 1:
                        name = _text(buf, x)
                    elif f == 2:
                        opcode = _text(buf, x)
                    elif f == 7:
                        meta = dict(_fields(buf, *x))
                        if 2 in meta:
                            op_name = _text(buf, meta[2])
                    elif f == 35:
                        iid = x
                    elif f == 38:
                        called += _ints(buf, x)
                rows.append((iid, (name, opcode, op_name, called)))
        comps[cid] = (root, {iid: row + (cid,) for iid, row in rows})
    caller = {}                          # computation id -> instruction
    for _, instrs in comps.values():
        for instr in instrs.values():
            for called in instr[3]:
                caller.setdefault(called, instr)

    def root_name(instr, depth=0):
        _, opcode, op_name, called, _ = instr
        if op_name or opcode != "fusion" or not called or depth > 16:
            return op_name
        root, instrs = comps.get(called[0], (None, {}))
        return root_name(instrs[root], depth + 1) if root in instrs else ""

    def own(instr, depth=0):
        op_name = root_name(instr)
        if op_name or depth > 16 or instr[4] not in caller:
            return op_name
        return own(caller[instr[4]], depth + 1)

    return {instr[0]: own(instr)
            for _, instrs in comps.values() for instr in instrs.values()}


def scope_of(op_name: str) -> str:
    """The innermost round scope of an ``op_name``, or "unscoped". Scopes
    nest outer to inner from left to right, and a transform wraps the
    names under it (``vmap(jvp(round.phase2))``), so the last known name
    found is the innermost."""
    found = [s for s in _SCOPE.findall(op_name or "") if s in SCOPES]
    return found[-1] if found else UNSCOPED


def host_spans(pd) -> list:
    """Sorted ``(start, end, name)`` of the ``bench.*`` and ``engine.*``
    host spans."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    out.append((e.start_ns, e.end_ns, e.name))
    return sorted(out)


def innermost_span(spans, lo, hi) -> str:
    """Of the spans that overlap [lo, hi] the most, the shortest: where an
    ``engine.*`` span lies inside the ``bench.segment`` span around it and
    both cover a gap whole, the gap is the engine span's."""
    best, key = "no span", (0, 0)
    for s, e, name in spans:
        ov = min(e, hi) - max(s, lo)
        if ov > 0 and (ov, -(e - s)) > key:
            best, key = name, (ov, -(e - s))
    return best


class Phases:
    """What the scope metrics read from one traced window: device seconds
    of the segment program per round scope, its busy seconds, its largest
    ops, the carry copy's programs and spans, and the idle gaps put down to
    the innermost host span.

    ``red`` is the window's ``bench.trace.Reduction``, ``names`` the
    segment program's instruction names -> ``op_name`` (``op_names``), and
    ``spans`` the ``bench.*`` and ``engine.*`` host spans."""

    def __init__(self, red, names: dict, spans: list):
        main = red.main_module()
        lo, hi = red.window
        n = max(len(red.devices), 1)
        seconds = defaultdict(float)
        per_op = defaultdict(float)
        self.busy_s = 0.0
        self.executions = self.copy_programs = 0
        for dev, (ops, mods) in sorted(red.devices.items()):
            runs = sorted((s, e) for s, e, name in mods
                          if name == main and e > lo and s < hi)
            if dev == sorted(red.devices)[0]:
                self.executions = len(runs)
                self.copy_programs = sum(
                    1 for s, e, name in mods
                    if name.startswith(COPY_PROGRAM) and e > lo and s < hi)
            starts = [s for s, _ in runs]
            inside = []
            for s, e, name in ops:
                # by its start: the clock's rounding can put an op's end a
                # nanosecond past its program's
                j = bisect.bisect_right(starts, s) - 1
                if j < 0 or s >= runs[j][1]:
                    continue
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                op = trace_lib.short_name(name)
                scope = scope_of(names.get(op, ""))
                seconds[scope] += (e - s) / 1e9 / n
                per_op[(op, scope)] += (e - s) / 1e9 / n
                inside.append((s, e))
            self.busy_s += trace_lib.covered(trace_lib.union(inside),
                                             lo, hi) / 1e9 / n
        self.seconds = dict(seconds)
        self.top_ops = sorted(((op, scope, v) for (op, scope), v
                               in per_op.items()), key=lambda t: -t[2])
        self.carry_copy_s = [(e - s) / 1e9 for s, e, name in spans
                             if name == "engine.carry_copy"
                             and e > lo and s < hi]
        self.idle_by_span = defaultdict(float)
        for g0, g1 in red.gaps():
            self.idle_by_span[innermost_span(spans, g0, g1)] += \
                (g1 - g0) / 1e9

    def ms_per_round(self, scope: str, rounds: int):
        """Device milliseconds a round under ``scope``; None where no op of
        the segment program carries it."""
        v = self.seconds.get(scope, 0.0)
        if v <= 0 or not rounds:
            return None
        return 1000.0 * v / rounds

    def table(self, rounds: int, top: int = 10) -> str:
        """The one-line scope table the traced run logs."""
        per = max(rounds, 1)
        parts = [f"{k} {1000 * self.seconds.get(k, 0.0) / per:.3f}"
                 for k in SCOPES + (UNSCOPED,)]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        copy_ms = (1000 * sum(self.carry_copy_s) / len(self.carry_copy_s)
                   if self.carry_copy_s else None)
        return ("scopes (ms/round over " + str(rounds) + " rounds): "
                + ", ".join(parts)
                + f"; segment busy {1000 * self.busy_s / per:.3f}"
                + f"; segment executions {self.executions}, jit_copy "
                f"executions {self.copy_programs}"
                + (f" ({self.copy_programs / self.executions:.1f} a "
                   f"boundary)" if self.executions else "")
                + "; carry copy ms "
                + ("none" if copy_ms is None else f"{copy_ms:.3f}")
                + "; idle s by span " + ", ".join(
                    f"{k} {v:.4f}" for k, v in idle)
                + "; top ops s " + ", ".join(
                    f"{op} [{scope}] {v:.4f}"
                    for op, scope, v in self.top_ops[:top])
                + "; top unscoped ops s " + ", ".join(
                    [f"{op} {v:.4f}" for op, scope, v in self.top_ops
                     if scope == UNSCOPED][:top // 2]))


def of(ctx) -> Phases:
    """The window's ``Phases``, built once per traced run and kept in
    ``ctx``; the first build logs the scope table. The trace is the one
    ``bench/run.py`` wrote for the cell and reduced into
    ``ctx["reduction"]``."""
    if "phases" not in ctx:
        from bench.run import TRACE_DIR
        red = ctx["reduction"]
        path = trace_lib.find_xplane(os.path.join(TRACE_DIR,
                                                  ctx["spec"]["name"]))
        protos = hlo_protos(path)
        main = red.main_module()
        names = op_names(protos[main]) if main in protos else {}
        spans = host_spans(trace_lib.load(path))
        ctx["phases"] = Phases(red, names, spans)
        print(ctx["phases"].table(ctx["rounds"]))
    return ctx["phases"]
