"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, per-operation device time, and idle gaps with
the host span that was open in each.

Planes named ``/device:...`` that hold a line named ``XLA Ops`` carry a
chip's work (ops nest there: a loop's event spans its body's); the host
plane carries the benchmark's own
spans (``bench.*``, from ``jax.profiler.TraceAnnotation``). Both are on
the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_ns: tuple          # (start, end) of the traced window
    busy_ns: float            # union of device-op intervals, per chip
    chips: int
    op_ns: dict               # op name -> summed device ns (all chips)
    self_ns: dict             # op name -> the same less nested ops' time
    gaps: list                # [(host span name, gap ns)], longest first

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_s(self, needle: str) -> float:
        """Device seconds of every op whose name contains ``needle``."""
        return sum(v for k, v in self.op_ns.items() if needle in k) / 1e9


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(ivs, op_ns, self_ns):
    """Add each op's time, and its time less that of the ops nested in
    it (an op inside a loop lies within the loop's event), by name."""
    stack = []   # [end, name, time of children]
    for s, t, name in sorted(ivs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _pop(stack, self_ns)
        if stack:
            stack[-1][2] += t - s
        stack.append([t, name, 0.0, t - s])
        op_ns[name] += t - s
    while stack:
        _pop(stack, self_ns)


def _pop(stack, self_ns):
    _, name, children, dur = stack.pop()
    self_ns[name] += dur - children


def short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.12 = f32[...] ...``
    -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory: str) -> str:
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    if len(hits) != 1:
        raise FileNotFoundError(f"{len(hits)} .xplane.pb files under "
                                f"{directory}")
    return hits[0]


def reduce(planes, window_span: str = "bench.round") -> Reduced:
    """``planes``: iterable of objects with ``name`` and ``lines``, each
    line with ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them. The
    window runs from the first ``window_span`` host span's start to the
    last one's end."""
    device, host = [], []
    for p in planes:
        if p.name.startswith("/device:") and any(
                ln.name == OPS_LINE for ln in p.lines):
            device.append(p)
        elif p.name.startswith("/host:"):
            host.append(p)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in host for ln in p.lines for e in ln.events
             if e.name.startswith("bench.")]
    rounds = [s for s in spans if s[0] == window_span]
    if not rounds or not device:
        raise ValueError(f"trace holds {len(rounds)} {window_span!r} spans "
                         f"and {len(device)} device planes")
    w0, w1 = min(s[1] for s in rounds), max(s[2] for s in rounds)
    op_ns, self_ns = collections.Counter(), collections.Counter()
    busy = 0.0
    per_chip = []
    for p in device:
        ivs = []
        for ln in p.lines:
            if ln.name != OPS_LINE:
                continue
            for e in ln.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= w0 or s >= w1:
                    continue
                ivs.append((max(s, w0), min(t, w1), short(e.name)))
        _self_times(ivs, op_ns, self_ns)
        merged = _union([(s, t) for s, t, _ in ivs])
        busy += sum(t - s for s, t in merged)
        per_chip.append(merged)
    # idle gaps of the first chip, named by the innermost host span open
    # at the gap's midpoint
    inner = [s for s in spans if s[0] != window_span]
    gaps = []
    edges = [[w0, w0]] + per_chip[0] + [[w1, w1]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            mid = (a + b) / 2
            open_ = [s for s in inner if s[1] <= mid <= s[2]]
            name = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                    else "no span")
            gaps.append((name, b - a))
    gaps.sort(key=lambda g: -g[1])
    return Reduced((w0, w1), busy / len(device), len(device), dict(op_ns),
                   dict(self_ns), gaps)


def reduce_file(path: str) -> Reduced:
    """Reduce an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return reduce(ProfileData.from_serialized_xspace(f.read()).planes)
    return reduce(ProfileData.from_file(path).planes)
