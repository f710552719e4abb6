"""Host-side observability primitives: ``repro.obs.trace`` spans/events
and the ``MetricsFrame`` exporters.

Contracts:

  * span nesting is recorded (depth + parent from a thread-local stack)
    and the JSONL sink round-trips every record;
  * a disabled tracer is a true no-op — shared null span, no file, no
    output — so instrumented code paths cost nothing by default;
  * JSONL records are kept in memory and written at ``close()``;
  * a profiler tracer puts each span, with its attributes, into the
    ``jax.profiler`` trace and builds no record;
  * ``MeshChainEngine.run`` nests ``engine.layout``/``engine.stage``/
    ``engine.segment`` in ``engine.run`` and counts executors built and
    bytes staged;
  * ``configure()`` swaps the process tracer and back;
  * MetricsFrame JSONL round-trips bitwise at fp32, the Prometheus
    textfile parses back to floats, concat/summary/last_round behave.
"""
import json
import time

import numpy as np
import pytest

from repro.obs import (MetricsFrame, Telemetry, parse_prometheus,
                       read_metrics_jsonl, trace, write_metrics_jsonl,
                       write_prometheus)


# ---------------------------------------------------------------------------
# tracing spans + events
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = trace.Tracer(path)
    with tr.span("outer", run="x"):
        time.sleep(0.01)
        with tr.span("inner", step=1):
            pass
        tr.event("tick", round=3)
    tr.close()
    recs = trace.read_jsonl(path)
    by = {}
    for r in recs:
        by.setdefault(r["name"], []).append(r)
    # spans are emitted at EXIT: inner closes before outer
    assert [r["name"] for r in recs] == ["inner", "tick", "outer"]
    inner, tick, outer = by["inner"][0], by["tick"][0], by["outer"][0]
    assert outer["type"] == "span" and outer["depth"] == 0
    assert outer["parent"] is None and outer["run"] == "x"
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert inner["step"] == 1
    assert tick["type"] == "event" and tick["parent"] == "outer"
    assert tick["round"] == 3 and "dur_s" not in tick
    # monotonic durations: the outer span contains the sleep
    assert outer["dur_s"] >= 0.01 > inner["dur_s"] >= 0.0
    assert outer["ts"] <= inner["ts"]


def test_disabled_tracer_is_noop(tmp_path, capsys):
    tr = trace.Tracer(None, echo=False, profiler=False)
    assert not tr.enabled
    s1 = tr.span("a")
    s2 = tr.span("b", k=1)
    assert s1 is s2  # the shared null span: zero allocation per call
    with s1 as s:
        s.set(count=1)
        tr.event("nothing", x=1)
    assert capsys.readouterr().out == ""
    assert list(tmp_path.iterdir()) == []


def test_records_are_written_at_close(tmp_path):
    path = tmp_path / "late.jsonl"
    tr = trace.Tracer(str(path))
    with tr.span("seg") as s:
        s.set(bytes=12)
        tr.event("e")
    assert not path.exists()  # kept in memory until close()
    tr.close()
    tr.close()  # nothing left to write twice
    recs = trace.read_jsonl(str(path))
    assert [r["name"] for r in recs] == ["e", "seg"]
    assert recs[1]["bytes"] == 12


def test_profiler_tracer_annotates_and_writes_nothing(tmp_path, capsys,
                                                      monkeypatch):
    """A profiler-only tracer enters one jax.profiler.TraceAnnotation per
    span, with the span's attributes and those set inside it, and builds
    no record."""
    import jax
    seen = []

    class Annotation:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, dict(attrs)
            self.entered = self.exited = False
            seen.append(self)

        def __enter__(self):
            self.entered = True

        def __exit__(self, *exc):
            self.exited = True

        def set_metadata(self, **attrs):
            self.attrs.update(attrs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.chdir(tmp_path)
    tr = trace.Tracer(profiler=True)
    assert tr.enabled and not tr.recording
    with tr.span("engine.run", rounds=1) as s:
        with tr.span("engine.stage"):
            pass
        s.set(executor_built=1)
        tr.event("ignored", x=1)
    tr.close()
    assert [(a.name, a.attrs, a.entered, a.exited) for a in seen] == [
        ("engine.run", {"rounds": 1, "executor_built": 1}, True, True),
        ("engine.stage", {}, True, True)]
    assert tr._lines == []
    assert capsys.readouterr().out == ""
    assert list(tmp_path.iterdir()) == []


def test_profiler_spans_reach_the_profile(tmp_path):
    """Spans of a profiler tracer land in a jax.profiler trace as host
    events, their attributes (set ones too) as event stats."""
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = trace.Tracer(profiler=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("engine.run", rounds=2) as s:
            with tr.span("engine.stage"):
                pass
            s.set(executor_built=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = {e.name: dict(e.stats)
              for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events
              if e.name.startswith("engine.")}
    assert events == {"engine.run": {"rounds": 2, "executor_built": 1},
                      "engine.stage": {}}


def test_echo_tracer_prints_compact_lines(capsys):
    tr = trace.Tracer(echo=True)
    assert tr.enabled
    tr.event("engine.progress", round=4, steps_per_s=123.0)
    out = capsys.readouterr().out
    assert "engine.progress" in out
    assert "round=4" in out and "steps_per_s=123.0" in out
    assert out.startswith("[")  # [HH:MM:SS] prefix


def test_configure_swaps_module_tracer(tmp_path):
    path = str(tmp_path / "mod.jsonl")
    assert not trace.enabled()
    try:
        trace.configure(path)
        assert trace.enabled()
        with trace.span("seg", i=0):
            trace.event("e")
    finally:
        trace.configure()
    assert not trace.enabled()
    names = [r["name"] for r in trace.read_jsonl(path)]
    assert names == ["e", "seg"]
    # back to disabled: nothing more is written
    trace.event("after")
    assert [r["name"] for r in trace.read_jsonl(path)] == ["e", "seg"]


def test_span_exception_still_emits_and_pops(tmp_path):
    path = str(tmp_path / "exc.jsonl")
    tr = trace.Tracer(path)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    with tr.span("next"):
        pass
    tr.close()
    recs = trace.read_jsonl(path)
    assert [r["name"] for r in recs] == ["boom", "next"]
    assert all(r["depth"] == 0 for r in recs)  # stack popped on error


def test_engine_run_spans_nest_and_count(tmp_path):
    """Round-at-a-time driving of the packed executor: each engine.run
    span holds engine.layout, engine.stage and engine.segment; only the
    first call builds an executor; a stage stages the whole chain state."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import SamplerConfig
    from repro.core import MeshChainEngine

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 3))
    cfg = SamplerConfig(method="dsgld", step_size=1e-4, num_shards=4,
                        local_updates=2, prior_precision=1.0)
    eng = MeshChainEngine(
        lambda th, b: -0.5 * jnp.sum((b["x"] - th) ** 2), cfg, {"x": x},
        minibatch=4, use_kernel=True)
    state = jnp.zeros((2, 3))
    path = str(tmp_path / "engine.jsonl")
    trace.configure(path)
    try:
        for i in range(2):
            state = eng.run(jax.random.PRNGKey(i), state, 1, n_chains=2,
                            stacked=True, collect=False,
                            reassign="permutation")
    finally:
        trace.configure()
    recs = trace.read_jsonl(path)
    # spans close inner first: one call's three children, then its run
    assert [r["name"] for r in recs] == 2 * [
        "engine.layout", "engine.stage", "engine.segment", "engine.run"]
    for r in recs:
        inner = r["name"] != "engine.run"
        assert r["depth"] == int(inner)
        assert r["parent"] == ("engine.run" if inner else None)
    runs = [r for r in recs if r["name"] == "engine.run"]
    assert [r["executor_built"] for r in runs] == [1, 0]
    assert all(r["rounds"] == 1 for r in runs)
    stages = [r for r in recs if r["name"] == "engine.stage"]
    assert [r["bytes"] for r in stages] == [state.nbytes] * 2 == [24] * 2
    # a (3,) leaf: one 8-row block per chain, two chains
    layouts = [r for r in recs if r["name"] == "engine.layout"]
    assert [(r["block_rows"], r["grid_steps"]) for r in layouts] == \
        [(8, 2)] * 2


# ---------------------------------------------------------------------------
# MetricsFrame + exporters
# ---------------------------------------------------------------------------

def _frame(rounds=3, chains=2, names=("a_norm", "b_rate")):
    rng = np.random.RandomState(0)
    return MetricsFrame({
        n: rng.rand(rounds, chains).astype(np.float32) for n in names})


def test_metrics_jsonl_roundtrip_bitwise(tmp_path):
    fr = _frame()
    path = str(tmp_path / "m.jsonl")
    write_metrics_jsonl(fr, path)
    back = read_metrics_jsonl(path)
    assert back.names == fr.names
    for n in fr.names:
        np.testing.assert_array_equal(back.metrics[n], fr.metrics[n])
        assert back.metrics[n].dtype == np.float32
    head = json.loads(open(path).readline())
    assert head["schema"] == "repro-metrics-v1"
    assert head["rounds"] == 3 and head["chains"] == 2


def test_prometheus_export_parses(tmp_path):
    fr = _frame()
    path = str(tmp_path / "m.prom")
    write_prometheus(fr, path)
    got = parse_prometheus(path)
    assert got["fsgld_rounds_total"] == fr.rounds
    for n in fr.names:
        for c in range(fr.n_chains):
            key = f'fsgld_{n}{{chain="{c}"}}'
            assert got[key] == pytest.approx(
                float(fr.metrics[n][-1, c]), rel=1e-6)
        assert got[f"fsgld_{n}_mean"] == pytest.approx(
            float(fr.metrics[n].mean()), rel=1e-6)
    # textfile format: HELP/TYPE comment pairs present
    text = open(path).read()
    assert "# HELP fsgld_a_norm" in text and "# TYPE fsgld_a_norm gauge" \
        in text


def test_frame_summary_last_round_concat():
    fr = _frame(rounds=4)
    assert fr.rounds == 4 and fr.n_chains == 2
    s = fr.summary()
    assert set(s) == set(fr.names)
    assert s["a_norm"] == pytest.approx(float(fr.metrics["a_norm"].mean()))
    np.testing.assert_array_equal(fr.last_round()["b_rate"],
                                  fr.metrics["b_rate"][-1])
    cat = MetricsFrame.concat([_frame(rounds=2), _frame(rounds=3)])
    assert cat.rounds == 5 and cat.names == fr.names


def test_frame_shape_validation():
    with pytest.raises(AssertionError):
        MetricsFrame({})
    with pytest.raises(AssertionError):
        MetricsFrame({"a": np.zeros((2, 2), np.float32),
                      "b": np.zeros((3, 2), np.float32)})


def test_telemetry_spec_names_sorted_and_validated():
    full, lean = Telemetry(), Telemetry(probe=False)
    assert full.names == tuple(sorted(full.names))
    assert set(full.names) - set(lean.names) == {"grad_norm", "log_post"}
    assert "bytes_per_round" in lean.names
    with pytest.raises(ValueError, match="log_every"):
        Telemetry(log_every=0)
    assert hash(Telemetry()) == hash(Telemetry())  # executor cache key
