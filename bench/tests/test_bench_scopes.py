"""bench/scopes.py: the round's named phases, read back from the compiled
program and from a profiler trace.

The scope names the readers look for have to be the ones the program
writes: a tiny engine of each round body the cells run is compiled here
on the CPU and its HLO ``op_name`` metadata searched for every name.
"""
import importlib
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from bench import run, scopes, trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata")
READERS = ("sampler_ms_per_round", "phase1_ms_per_round",
           "phase2_ms_per_round", "delta_average_ms_per_round",
           "server_update_ms_per_round", "carry_copy_ms")


def _reader(name):
    return importlib.import_module("bench.metrics." + name)


# -- names --------------------------------------------------------------------

@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/closed_call/round.phase2/vmap(transpose(jvp()))/"
     "conv_general_dilated", "round.phase2"),
    ("round.phase2/vmap(transpose(jvp()))/reduce_sum", "round.phase2"),
    ("jit(f)/round.phase2/while/body/round.sample/gather", "round.sample"),
    ("jit(f)/vmap(jvp(round.phase2))/round.delta_average/dot_general",
     "round.delta_average"),
    ("transpose(jvp(round.phase1))/mul", "round.phase1"),
    ("jit(f)/while/body/closed_call/reduce_sum", scopes.UNSCOPED),
    ("jit(f)/round.somewhere_else/add", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_innermost_span_takes_the_shortest_of_the_largest_overlap():
    spans = sorted([(0, 100, "bench.segment"), (10, 40, "engine.carry_copy"),
                    (40, 60, "engine.dispatch"), (90, 130, "bench.pull")])
    assert scopes.innermost_span(spans, 12, 30) == "engine.carry_copy"
    assert scopes.innermost_span(spans, 41, 59) == "engine.dispatch"
    # more of the gap under the segment span than under the copy span
    assert scopes.innermost_span(spans, 30, 80) == "bench.segment"
    assert scopes.innermost_span(spans, 101, 120) == "bench.pull"
    assert scopes.innermost_span(spans, 140, 150) == "no span"


# -- the protobuf wire reader ---------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _int(num, value):
    return _varint(num << 3) + _varint(value)


def _msg(num, *parts):
    body = b"".join(p.encode() if isinstance(p, str) else p for p in parts)
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _instr(name, opcode, op_name, iid, called=()):
    meta = _msg(7, _msg(1, "op_type"), _msg(2, op_name)) if op_name else b""
    packed = _msg(38, b"".join(_varint(c) for c in called)) if called else b""
    return _msg(2, _msg(1, name), _msg(2, opcode), _msg(3, "shape"), meta,
                _int(35, iid), packed)


def _hlo_proto():
    fused = _msg(3, _msg(1, "fused_computation"),
                 _instr("param_0", "parameter", "", 1),
                 _instr("multiply.1", "multiply",
                        "jit(f)/round.phase2/vmap(jvp())/mul", 2),
                 _int(5, 7), _int(6, 2))
    # the loop XLA writes for a gather: its ops carry no metadata
    loop = _msg(3, _msg(1, "gather_loop_body"),
                _instr("dynamic-update-slice.9", "dynamic-update-slice", "",
                       10),
                _int(5, 9), _int(6, 10))
    entry = _msg(3, _msg(1, "main"),
                 _instr("fusion.3", "fusion", "", 3, called=[7]),
                 _instr("dot.4", "dot", "jit(f)/round.delta_average/dot", 4),
                 _instr("copy.5", "copy", "", 5),
                 _instr("while.6", "while",
                        "jit(f)/round.sample/gather", 6, called=[9]),
                 _int(5, 8), _int(6, 4))
    return _msg(1, _msg(1, "jit_f"), fused, loop, entry)


def test_wire_reader_finds_programs_and_op_names(tmp_path):
    proto = _hlo_proto()
    stat_md = _msg(5, _int(1, 1), _msg(2, _int(1, 1), _msg(2, "Hlo Proto")))
    event_md = _msg(4, _int(1, 9), _msg(2, _int(1, 9), _msg(2, "jit_f(42)"),
                                        _msg(5, _int(1, 1), _msg(6, proto))))
    other = _msg(1, _int(1, 1), _msg(2, "/device:TPU:0"),
                 _msg(4, _int(1, 3), _msg(2, _msg(2, "jit_g(7)"))))
    meta = _msg(1, _int(1, 2), _msg(2, "/host:metadata"), event_md, stat_md)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(other + meta)
    protos = scopes.hlo_protos(str(path))
    assert list(protos) == ["jit_f(42)"]
    names = scopes.op_names(protos["jit_f(42)"])
    # the fusion has no metadata of its own: its root's
    assert names["fusion.3"] == "jit(f)/round.phase2/vmap(jvp())/mul"
    assert names["dot.4"] == "jit(f)/round.delta_average/dot"
    # an op of a loop body: the loop's
    assert names["dynamic-update-slice.9"] == "jit(f)/round.sample/gather"
    # nothing calls the entry computation
    assert names["copy.5"] == ""
    assert scopes.hlo_protos(os.path.join(TESTDATA,
                                          "tiny_r14.xplane.pb")) == {}


# -- a trace without scopes: the readers find nothing -------------------------

def test_readers_return_none_without_scopes():
    """The recorded trace of a program with no scopes or engine spans
    (the program before they were named): every new reader returns None
    and none raises."""
    red = trace.Reduction(trace.load(os.path.join(TESTDATA,
                                                  "tiny_r14.xplane.pb")))
    spans = scopes.host_spans(trace.load(os.path.join(
        TESTDATA, "tiny_r14.xplane.pb")))
    ph = scopes.Phases(red, {}, spans)
    assert set(ph.seconds) == {scopes.UNSCOPED} and ph.executions == 3
    assert set(ph.idle_by_span) == {"bench.segment"}
    ctx = {"reduction": red, "rounds": 3, "phases": ph}
    for name in READERS:
        assert _reader(name).read(ctx) is None


# -- the compiled program carries every name the readers look for -------------

def _tiny(name):
    spec = run.load_cell(name)
    spec["model"].update(resnet_stages=[1, 1], resnet_channels=[16, 32],
                         resnet_groups=8, image_size=16, proj_dims=[64, 64])
    spec["traffic"]["dataset"].update(num_samples=600, num_classes=5)
    spec["traffic"]["clients_per_round"] = 8
    spec["cell"]["chunk_rounds"] = 1
    if spec["cell"]["cohort_chunk"]:
        spec["cell"]["cohort_chunk"] = 4
    return spec


def _segment_op_names(spec, sharded=False):
    from jax.sharding import Mesh

    from repro.core import round_engine
    data, _ = run.make_inputs(spec, 2**31 + 3)
    apply, opt, ecfg, ds, sampler = run.build_engine(spec, data)
    mesh = None
    if sharded:
        ecfg = ecfg._replace(cohort_axis="data", stats_kernel="off")
        mesh = Mesh(jax.devices()[:1], ("data",))
    engine = round_engine.RoundEngine(apply, opt, sampler, ecfg, mesh=mesh)
    params = run.make_params(jax, spec, 3)
    opt_state = jax.jit(opt.init)(params)
    rng = jnp.asarray(run.rounds_key(spec, 3))
    compiled = run.compiled_segment(jax, jnp, engine, params, opt_state, rng)
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


@pytest.mark.parametrize("cell,sharded", [
    ("r14-k256-n2", False),                  # materialized, stats kernel
    ("r18-k256-q1to6-stream", False),        # streaming body
    ("r14-k256-n2", True),                   # sharded cohort, one device
], ids=["materialized", "streaming", "sharded"])
def test_compiled_segment_names_every_scope(cell, sharded):
    names = _segment_op_names(_tiny(cell), sharded)
    found = Counter(scopes.scope_of(n) for n in names)
    for scope in scopes.SCOPES:
        assert found[scope] > 0, (scope, found)
    # the backward pass of the clients' local step is phase 2's
    backward = [n for n in names if "transpose(jvp(" in n]
    assert backward and {scopes.scope_of(n) for n in backward} \
        >= {"round.phase2"}
    if not sharded and cell.startswith("r14"):
        assert any("cco_stats_pallas" in n and
                   scopes.scope_of(n) == "round.phase1" for n in names)


def test_reader_names_are_the_scopes():
    for name, scope in zip(READERS, scopes.SCOPES):
        src = open(_reader(name).__file__).read()
        assert f'"{scope}"' in src


# -- a recorded chip trace of a program with the scopes -----------------------

SCOPED = os.path.join(TESTDATA, "tiny_r14_scoped.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    """``tiny_r14_scoped.xplane.pb``: recorded on one TPU v5e (``TPU v5
    lite``) from the r14-k256-n2 cell at the size of
    ``tiny_r14.xplane.pb`` (two stages of 16/32 channels, 16x16 images, 8
    clients a round, one round a segment), three segments after a first
    one, inside the harness's ``bench.segment`` / ``bench.pull`` spans.
    Pruned to the device's ``XLA Ops`` and ``XLA Modules`` lines in the
    window (each op's HLO text cut to its name), the ``bench.*`` and
    ``engine.*`` host spans, and the metadata plane's programs cut to the
    instructions that ran (and their fused roots): name, opcode,
    ``op_name``, ids and called computations. The expected numbers were
    read from the full recording."""
    red = trace.Reduction(trace.load(SCOPED))
    protos = scopes.hlo_protos(SCOPED)
    names = scopes.op_names(protos[red.main_module()])
    return red, scopes.Phases(red, names, scopes.host_spans(
        trace.load(SCOPED))), protos


def test_scoped_trace_programs(scoped):
    red, ph, protos = scoped
    assert red.main_module() == "jit__unknown(11683950352262543664)"
    assert red.main_module() in protos
    assert sum(p.startswith("jit_copy") for p in protos) == 7
    assert red.window_s == pytest.approx(0.0648281, abs=1e-9)
    assert red.busy_s == pytest.approx(0.000968578, abs=1e-9)
    assert ph.executions == 3 and ph.copy_programs == 187


def test_scopes_and_unscoped_add_up_to_the_segment_busy_time(scoped):
    red, ph, _ = scoped
    expected = {"round.sample": 0.000112407, "round.phase1": 1.057e-06,
                "round.phase2": 0.000709106, "round.delta_average": 8.729e-06,
                "round.server_update": 4.883e-06, "unscoped": 1.9156e-05}
    assert set(ph.seconds) == set(expected)
    for k, v in expected.items():
        assert ph.seconds[k] == pytest.approx(v, abs=1e-12), k
    assert ph.busy_s == pytest.approx(0.000855338, abs=1e-12)
    assert sum(ph.seconds.values()) == pytest.approx(ph.busy_s, abs=1e-12)
    assert ph.top_ops[0][1] == "round.phase2"


def test_copy_programs_are_never_attributed(scoped):
    """The ops of the ``jit_copy`` programs (named ``copy.1`` too) take the
    rest of the busy time and none of the scope time."""
    red, ph, _ = scoped
    ops, mods = red.devices["/device:TPU:0"]
    copies = [(s, e) for s, e, name in mods if name.startswith("jit_copy")]
    in_copy = sum(e - s for s, e, _ in ops
                  if any(a <= s < b for a, b in copies)) / 1e9
    assert in_copy > 0
    assert in_copy + sum(ph.seconds.values()) == pytest.approx(
        red.busy_s, abs=1e-12)
    assert "copy.1" not in {op for op, _, _ in ph.top_ops}


def test_engine_spans_name_the_boundary_gaps(scoped):
    red, ph, _ = scoped
    assert ph.carry_copy_s == pytest.approx(
        [0.02017599, 0.01800613, 0.01852991], abs=1e-9)
    assert max(ph.idle_by_span, key=ph.idle_by_span.get) == \
        "engine.carry_copy"
    assert ph.idle_by_span["engine.carry_copy"] == pytest.approx(
        0.053000927, abs=1e-9)
    assert sum(ph.idle_by_span.values()) == pytest.approx(
        red.window_s - red.busy_s, abs=1e-9)


def test_readers_on_the_scoped_trace(scoped):
    red, ph, _ = scoped
    ctx = {"reduction": red, "rounds": 3, "phases": ph}
    for name, scope in zip(READERS, scopes.SCOPES):
        assert _reader(name).read(ctx) == pytest.approx(
            1000.0 * ph.seconds[scope] / 3)
    assert _reader("carry_copy_ms").read(ctx) == pytest.approx(
        1000.0 * (0.02017599 + 0.01800613 + 0.01852991) / 3, abs=1e-6)
    line = ph.table(3)
    assert line.startswith("scopes (ms/round over 3 rounds): ")
    assert "\n" not in line and "62.3 a boundary" in line
