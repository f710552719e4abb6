"""Per-layer readings of one traced window, from the program's own spans
and named scopes: the sampler's device layers (``fsgld.*`` scopes) and
``engine.run``'s host work (``engine.*`` spans).

    python3 benchmarks/chip/layers.py --workload <cell> --seed <n> \
        --seconds <s>

runs the cell as ``run.py --trace 1`` does, with the program's tracer in
its profiler mode (``repro.obs.trace.configure(profiler=True)``), so
that its host spans and their counters land in the trace beside the
benchmark's ``bench.*`` spans. Its last line is one JSON object: the
window's rate, ``correct``, the readings below and what makes them up.

- ``grad_pass_ms_per_step``, ``pack_ms_per_step``: device self time of
  the ops under ``fsgld.grad`` / ``fsgld.pack``, per chain step;
- ``conducive_ms_per_round``: the same under ``fsgld.conducive``, per
  round;
- ``engine_layout_ms_per_round``, ``engine_stage_ms_per_round``: host
  self time of the ``engine.layout`` / ``engine.stage`` spans, per round;
- ``engine_idle_ms_per_round``: device idle time whose innermost open
  host span is an ``engine.*`` span, per round.

An op belongs to the innermost ``fsgld.*`` scope of its ``op_name``
(``xmeta``); a fused op to that of its fusion's root. A span's self time
is its duration less that of the ``engine.*`` spans nested in it. Every
reading is of the window alone and missing (None) where the trace holds
no such scope or span, as in a trace of a program without them.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()

import xtrace  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SCOPE = "fsgld."
SPAN = "engine."
BLOCK = "jit_block"      # the executor's program: one per round
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Layers:
    scope_ns: dict       # fsgld.* scope -> device self ns (all chips)
    programs: dict       # program name -> [launches, device self ns]
    unscoped_ns: dict    # op short name -> its self ns in BLOCK, no scope
    span_ns: dict        # engine.* span -> host self ns
    span_count: dict     # engine.* span -> spans in the window
    executor_built: int  # engine.run spans that built an executor
    staged_bytes: list   # each engine.stage span's ``bytes``
    gaps: list           # [(innermost bench.*/engine.* span, gap ns)]

    @property
    def block_ns(self) -> float:
        return self.programs.get(BLOCK, [0, 0.0])[1]

    @property
    def engine_idle_ns(self) -> float:
        return sum(ns for name, ns in self.gaps if name.startswith(SPAN))


def scope_of(op_name: str):
    """The innermost ``fsgld.*`` component of an op's name path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE):
            return part
    return None


def _program(name: str) -> str:
    """``jit_block(6098607097657042829)`` -> ``jit_block``."""
    return name.split("(", 1)[0]


def _host_spans(host, w0, w1):
    """(name, start, end, stats) of every ``bench.*`` and ``engine.*``
    host span that starts inside the window."""
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for p in host for ln in p.lines for e in ln.events
            if (e.name.startswith(SPAN) or e.name.startswith("bench."))
            and w0 <= e.start_ns < w1]


def _span_self(spans):
    """Host self ns of each ``engine.*`` span name: duration less that of
    the ``engine.*`` spans nested in it (spans of one thread nest)."""
    own = collections.Counter()
    stack = []   # [end, name, children ns]
    for name, s, e, _ in sorted((x for x in spans if x[0].startswith(SPAN)),
                                key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, nm, kids, dur = stack.pop()
            own[nm] += dur - kids
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, e - s])
    while stack:
        end, nm, kids, dur = stack.pop()
        own[nm] += dur - kids
    return dict(own)


def reduce(planes, names: dict, window_span: str = "bench.round") -> Layers:
    """``planes`` as for ``xtrace.reduce``; ``names`` is
    ``xmeta.op_names`` of the same trace."""
    planes = list(planes)
    device = [p for p in planes if p.name.startswith("/device:")
              and any(ln.name == xtrace.OPS_LINE for ln in p.lines)]
    host = [p for p in planes if p.name.startswith("/host:")]
    rounds = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in host for ln in p.lines for e in ln.events
              if e.name == window_span]
    if not rounds or not device:
        raise ValueError(f"trace holds {len(rounds)} {window_span!r} spans "
                         f"and {len(device)} device planes")
    w0, w1 = min(s for s, _ in rounds), max(e for _, e in rounds)
    op_ns, self_ns = collections.Counter(), collections.Counter()
    launches = collections.Counter()
    busy0 = None
    for p in device:
        ops = names.get(p.name, {})
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _program(e.name))
                      for ln in p.lines if ln.name == MODULES_LINE
                      for e in ln.events
                      if e.start_ns < w1 and e.start_ns + e.duration_ns > w0)
        launches.update(m[2] for m in mods if m[0] >= w0)
        starts = [m[0] for m in mods]
        ivs = []
        for ln in p.lines:
            if ln.name != xtrace.OPS_LINE:
                continue
            for e in ln.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= w0 or s >= w1:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                prog = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                ivs.append((max(s, w0), min(t, w1),
                            (prog, e.name, ops.get(e.name, ""))))
        xtrace._self_times(ivs, op_ns, self_ns)
        if busy0 is None:
            busy0 = xtrace._union([(s, t) for s, t, _ in ivs])
    scope_ns = collections.Counter()
    programs = {name: [n, 0.0] for name, n in launches.items()}
    unscoped = collections.Counter()
    for (prog, ev, op), ns in self_ns.items():
        scope = scope_of(op)
        if scope is not None:
            scope_ns[scope] += ns
        programs.setdefault(prog, [0, 0.0])[1] += ns
        if prog == BLOCK and scope is None:
            unscoped[xtrace.short(ev)] += ns
    spans = _host_spans(host, w0, w1)
    inner = [s for s in spans if s[0] != window_span]
    gaps = []
    edges = [[w0, w0]] + busy0 + [[w1, w1]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            mid = (a + b) / 2
            open_ = [s for s in inner if s[1] <= mid <= s[2]]
            name = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                    else "no span")
            gaps.append((name, b - a))
    gaps.sort(key=lambda g: -g[1])
    count = collections.Counter(s[0] for s in spans
                                if s[0].startswith(SPAN))
    return Layers(
        dict(scope_ns), programs, dict(unscoped), _span_self(spans),
        dict(count),
        sum(1 for s in spans
            if s[0] == "engine.run" and s[3].get("executor_built", 0)),
        [s[3]["bytes"] for s in spans
         if s[0] == "engine.stage" and "bytes" in s[3]], gaps)


def readings(lay: Layers, rounds: int, steps_per_round: int) -> dict:
    """The six per-layer readings, in ms; None where the trace holds no
    such scope or span."""
    steps = rounds * steps_per_round
    idle = lay.engine_idle_ns if lay.span_count else None

    def per(ns, n):
        return None if ns is None else 1e-6 * ns / n

    return {
        "grad_pass_ms_per_step": per(lay.scope_ns.get("fsgld.grad"), steps),
        "pack_ms_per_step": per(lay.scope_ns.get("fsgld.pack"), steps),
        "conducive_ms_per_round": per(lay.scope_ns.get("fsgld.conducive"),
                                      rounds),
        "engine_layout_ms_per_round": per(lay.span_ns.get("engine.layout"),
                                          rounds),
        "engine_stage_ms_per_round": per(lay.span_ns.get("engine.stage"),
                                         rounds),
        "engine_idle_ms_per_round": per(idle, rounds),
    }


def summary(lay: Layers, rounds: int, steps_per_round: int) -> dict:
    """The readings with what makes them up, per round."""
    out = {"readings": readings(lay, rounds, steps_per_round)}
    out["scope_ms_per_round"] = {k: 1e-6 * v / rounds
                                 for k, v in sorted(lay.scope_ns.items())}
    block = lay.block_ns
    out["block_ms_per_round"] = 1e-6 * block / rounds
    out["block_unscoped_share"] = (sum(lay.unscoped_ns.values()) / block
                                   if block else None)
    out["block_unscoped_ops_ms"] = [
        [k, 1e-6 * v] for k, v in sorted(lay.unscoped_ns.items(),
                                         key=lambda kv: -kv[1])[:12]]
    out["programs_per_round"] = {
        k: {"launches": n / rounds, "device_ms": 1e-6 * ns / rounds}
        for k, (n, ns) in sorted(lay.programs.items())}
    out["span_ms_per_round"] = {k: 1e-6 * v / rounds
                                for k, v in sorted(lay.span_ns.items())}
    out["span_count"] = lay.span_count
    out["executor_built_spans"] = lay.executor_built
    out["staged_bytes_per_round"] = (sum(lay.staged_bytes) / rounds
                                     if lay.staged_bytes else None)
    out["idle_gaps_ms"] = [[k, 1e-6 * v] for k, v in lay.gaps[:10]]
    by = collections.Counter()
    for k, v in lay.gaps:
        by[k] += v
    out["idle_ms_per_round_by_span"] = {k: 1e-6 * v / rounds
                                        for k, v in by.most_common()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import run
    run.enable_cache()
    import jax
    from jax.profiler import ProfileData

    import cell as cellmod
    import checks
    import xmeta
    from repro.obs import trace as obs_trace

    devices = jax.devices()
    if devices[0].platform == "cpu":
        return run.fail("layers.py reads a device trace: no chip found")
    cell = cellmod.Cell(args.workload, args.seed)
    trace_dir = ROOT / ".bench_trace" / f"{args.workload}.layers"
    shutil.rmtree(trace_dir, ignore_errors=True)
    obs_trace.configure(profiler=True)
    try:
        res = cell.run(args.seconds, t_start=T_START, devices=devices,
                       trace_dir=trace_dir)
    finally:
        obs_trace.configure()
    raw = pathlib.Path(xtrace.find_xplane(str(trace_dir))).read_bytes()
    shutil.rmtree(trace_dir, ignore_errors=True)
    planes = list(ProfileData.from_serialized_xspace(raw).planes)
    base = xtrace.reduce(planes)
    lay = reduce(planes, xmeta.op_names(raw))
    w = res.window
    out = {"workload": args.workload, "seed": args.seed,
           "correct": bool(checks.judge(res.checks, cell.limits)),
           "rounds": w.rounds, "chain_steps_per_s": w.steps_per_s(),
           "device_idle_share": 100.0 * (1.0 - base.busy_s / base.window_s),
           "device": {"kind": devices[0].device_kind,
                      "count": len(devices)}}
    out.update(summary(lay, w.rounds, cell.steps_per_round))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
