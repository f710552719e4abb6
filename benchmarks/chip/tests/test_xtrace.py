"""The trace reduction, on synthetic planes and on a recorded chip trace."""
import pathlib
import types

import pytest

import xtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=e) for n, e in lines.items()])


def synthetic():
    host = plane("/host:CPU", {"python": [
        ev("bench.round", 1000, 1000), ev("bench.dispatch", 1000, 100),
        ev("bench.wait", 1100, 900),
        ev("bench.round", 2000, 1000), ev("bench.dispatch", 2000, 300),
        ev("bench.wait", 2300, 700), ev("other", 0, 5000)]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("%fusion.1 = f32[8] fusion(x)", 1150, 500),
                    ev("%kernel_a.3 = f32[8] custom-call(y)", 1650, 250),
                    ev("%fusion.1 = f32[8] fusion(z)", 2350, 600),
                    ev("early", 0, 1100),
                    ev("late", 2990, 100)],
        "XLA Modules": [ev("jit_round", 1100, 2000)]})
    return [host, dev, plane("/host:metadata", {}),
            plane("/device:CUSTOM:Megascale Trace", {})]


def test_busy_ops_and_gaps_on_synthetic_planes():
    r = xtrace.reduce(synthetic())
    assert r.window_ns == (1000, 3000)
    # ops clipped to the window: early 1000-1100, fusion 1150-1650,
    # kernel 1650-1900, fusion 2350-2950, late 2990-3000
    assert r.busy_ns == 100 + 750 + 600 + 10
    assert r.op_ns == {"early": 100, "fusion.1": 1100, "kernel_a.3": 250,
                       "late": 10}
    assert r.op_s("kernel_a") == pytest.approx(250e-9)
    assert r.self_ns == r.op_ns  # nothing nests here
    # gaps: 1100-1150 (in wait 1), 1900-2350 (round 1's wait until
    # 2000, then dispatch 2: the midpoint 2125 is in dispatch 2),
    # 2950-2990 (wait 2)
    assert r.gaps == [("bench.dispatch", 450), ("bench.wait", 50),
                      ("bench.wait", 40)]
    assert r.window_s == pytest.approx(2e-6)


def test_nested_ops_keep_their_own_time_apart():
    host = plane("/host:CPU", {"python": [ev("bench.round", 0, 100)]})
    dev = plane("/device:TPU:0", {"XLA Ops": [
        ev("%while.1 = (f32[]) while(a)", 10, 80), ev("%fusion.2 = f", 20, 30),
        ev("%kernel.3 = f", 50, 20), ev("%copy.4 = f", 95, 5)]})
    r = xtrace.reduce([host, dev])
    assert r.busy_ns == 85
    assert r.op_ns == {"while.1": 80, "fusion.2": 30, "kernel.3": 20,
                       "copy.4": 5}
    assert r.self_ns == {"while.1": 30, "fusion.2": 30, "kernel.3": 20,
                         "copy.4": 5}


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xtrace.reduce(synthetic()[:1])


def test_recorded_chip_trace():
    """7 rounds of danube-2L.b4x1024 traced on a TPU v5 lite."""
    r = xtrace.reduce_file(str(DATA / "danube-2L.b4x1024.xplane.pb.gz"))
    assert r.chips == 1
    assert 0.9 * r.window_s < r.busy_s <= r.window_s
    # the update kernel: 14 steps of about 99 ms
    assert 1.3 < r.op_s("fsgld_update_packed") < 1.45
    assert sum(r.self_ns.values()) == pytest.approx(r.busy_ns, rel=1e-6)
