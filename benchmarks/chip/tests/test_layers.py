"""Op names from the serialized trace (``xmeta``) and the per-layer
readings of the program's scopes and spans (``layers``), on synthetic
planes and on a recorded chip trace."""
import gzip
import pathlib
import types

import pytest

import layers
import xmeta
import xtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = DATA / "danube-2L.b4x1024.xplane.pb.gz"


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=e) for n, e in lines.items()])


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    raw = gzip.open(RECORDED).read()
    return raw, list(ProfileData.from_serialized_xspace(raw).planes)


def test_op_names_of_the_recorded_trace(recorded):
    """The profiler's tf_op where it has one; a fusion without one by its
    fused instructions (fusion.621: the embedding's gradient)."""
    names = xmeta.op_names(recorded[0])["/device:TPU:0"]
    by_short = {}
    for k, v in names.items():
        by_short.setdefault(xtrace.short(k), set()).add(v)
    loop = "jit(block)/while/body/closed_call/while/body/closed_call"
    assert by_short["pad.197"] == {f"{loop}/dynamic_update_slice"}
    assert by_short["fusion.621"] == {
        f"{loop}/vmap(transpose(jvp()))/convert_element_type"}
    assert by_short["fsgld_update_packed.15"] == {
        f"{loop}/jit(fsgld_update_packed)/pallas_call"}
    # the shape probe's slice programs carry no name
    assert by_short["copy.1"] == {""}


def test_recorded_trace_without_scopes_or_spans(recorded):
    """A program from before the scopes and spans: every reading is
    missing, the programs and the idle time are still read."""
    raw, planes = recorded
    lay = layers.reduce(planes, xmeta.op_names(raw))
    base = xtrace.reduce(planes)
    assert set(layers.readings(lay, 7, 2).values()) == {None}
    # 7 rounds: one jit_block each (~431 ms), 35 slice programs each
    assert lay.programs["jit_block"][0] == 7
    assert 420e6 < lay.block_ns / 7 < 440e6
    assert lay.programs["jit_dynamic_slice"][0] == 35 * 7
    assert sum(lay.unscoped_ns.values()) == pytest.approx(lay.block_ns)
    assert sum(n for _, n in lay.gaps) == sum(n for _, n in base.gaps)


def synthetic():
    """Two rounds of 2 steps. Round 1 builds its executor; idle gaps
    fall in engine.layout (round 1), engine.stage (round 2) and
    bench.wait."""
    host = plane("/host:CPU", {"python": [
        ev("bench.round", 1000, 1000), ev("bench.dispatch", 1000, 300),
        ev("engine.run", 1000, 290, rounds=1, executor_built=1),
        ev("engine.layout", 1010, 100), ev("engine.stage", 1120, 60,
                                           bytes=64),
        ev("engine.segment", 1200, 80), ev("bench.wait", 1300, 700),
        ev("bench.round", 2000, 1000), ev("bench.dispatch", 2000, 300),
        ev("engine.run", 2000, 290, rounds=1, executor_built=0),
        ev("engine.layout", 2010, 50), ev("engine.stage", 2070, 110,
                                          bytes=64),
        ev("engine.segment", 2200, 80), ev("bench.wait", 2300, 700)]})
    grad = "jit(block)/while/body/fsgld.grad/transpose(jvp())/dot_general"
    ops = {
        "%while.1 = ()": "jit(block)/while",
        "%fusion.1 = f": grad,
        "%fusion.2 = f": "jit(block)/while/body/fsgld.pack/"
                         "dynamic_update_slice",
        "%kernel.3 = f": "jit(block)/while/body/fsgld.update/pallas_call",
        "%gather.4 = f": "jit(block)/fsgld.conducive/gather",
        "%copy.1 = f": ""}
    dev = plane("/device:TPU:0", {
        "XLA Modules": [ev("jit_dynamic_slice(7)", 1030, 10),
                        ev("jit_block(9)", 1290, 700),
                        ev("jit_dynamic_slice(7)", 2030, 10),
                        ev("jit_block(9)", 2290, 700)],
        "XLA Ops": [
            ev("%copy.1 = f", 1030, 10),
            ev("%gather.4 = f", 1290, 10), ev("%while.1 = ()", 1300, 690),
            ev("%fusion.1 = f", 1300, 200), ev("%fusion.2 = f", 1500, 50),
            ev("%kernel.3 = f", 1550, 400),
            ev("%copy.1 = f", 2030, 10),
            ev("%gather.4 = f", 2290, 10), ev("%while.1 = ()", 2300, 690),
            ev("%fusion.1 = f", 2300, 200), ev("%fusion.2 = f", 2500, 50),
            ev("%kernel.3 = f", 2550, 400)]})
    return [host, dev], {"/device:TPU:0": ops}


def test_readings_on_synthetic_planes():
    planes, names = synthetic()
    lay = layers.reduce(planes, names)
    assert lay.scope_ns == {"fsgld.grad": 400, "fsgld.pack": 100,
                            "fsgld.update": 800, "fsgld.conducive": 20}
    assert lay.programs == {"jit_block": [2, 1400.0],
                            "jit_dynamic_slice": [2, 20.0]}
    # the while loop's own time (690 - 650 per round) has no scope
    assert lay.unscoped_ns == {"while.1": 80}
    # run's self time is what its three children leave of it
    assert lay.span_ns == {"engine.run": 2 * 290 - 480,
                           "engine.layout": 150, "engine.stage": 170,
                           "engine.segment": 160}
    assert lay.span_count == {"engine.run": 2, "engine.layout": 2,
                              "engine.stage": 2, "engine.segment": 2}
    assert lay.executor_built == 1 and lay.staged_bytes == [64, 64]
    # idle: 1000-1030 and 1040-1290 (layout, then stage: its midpoint),
    # 1990-2030 (round 1's wait, then round 2's layout: midpoint 2010),
    # 2040-2290 (stage), 2990-3000 (wait)
    assert sorted(lay.gaps) == sorted([
        ("engine.layout", 30), ("engine.stage", 250), ("engine.layout", 40),
        ("engine.stage", 250), ("bench.wait", 10)])
    got = layers.readings(lay, rounds=2, steps_per_round=2)
    assert got == pytest.approx({
        "grad_pass_ms_per_step": 400e-6 / 4,
        "pack_ms_per_step": 100e-6 / 4,
        "conducive_ms_per_round": 20e-6 / 2,
        "engine_layout_ms_per_round": 150e-6 / 2,
        "engine_stage_ms_per_round": 170e-6 / 2,
        "engine_idle_ms_per_round": 570e-6 / 2})
    s = layers.summary(lay, 2, 2)
    assert s["block_unscoped_share"] == pytest.approx(80 / 1400)
    assert s["programs_per_round"]["jit_dynamic_slice"] == pytest.approx(
        {"launches": 1.0, "device_ms": 10e-6})
    assert s["staged_bytes_per_round"] == 64


def test_scope_of_takes_the_innermost():
    assert layers.scope_of("jit(b)/fsgld.pack/x/fsgld.grad/dot") == \
        "fsgld.grad"
    assert layers.scope_of("jit(b)/while/body/dot") is None
    assert layers.scope_of("") is None
