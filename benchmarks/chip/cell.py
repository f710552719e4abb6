"""One benchmark cell: set-up, the measured window and the check.

Set-up makes the traffic (token shards of every client), the weights and
the surrogate bank from the seed, on the device; builds the sampler
through ``repro.api.FSGLD`` on the packed executor; and drives its first
two rounds through the window's own call, keeping each state on the host
for the check. The window then carries on from that state with the same
call. After the window the program's state is freed and the plain
reference (``refmodel``, ``refsampler``) replays the first two rounds in
float32.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import math
import pathlib
import sys
import time
import types
import typing

import jax
import jax.numpy as jnp
import numpy as np

import checks
import refmodel
import refsampler
import window as win

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CHECKED_ROUNDS = 2
ROUNDS_PER_S = 1000     # more than any cell completes: no round is under 1 ms


def load(workload: str) -> dict:
    """The cell's entry, configuration, traffic and limits, by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits}


class Frozen(dict):
    """A mapping that hashes by its items, so that a configuration holding
    one is a ``jit`` static argument; built once and never changed."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def freeze(value):
    """``value`` with every mapping made ``Frozen`` and every list a tuple,
    all the way down."""
    if isinstance(value, dict):
        return Frozen((k, freeze(v)) for k, v in value.items())
    if isinstance(value, (list, tuple)):
        return tuple(freeze(v) for v in value)
    return value


def arch_of(config: dict) -> dict:
    """The reference's view of a configuration: its ``arch``, frozen (its
    ``layer_pattern`` a tuple of layer kinds, any number of them)."""
    return dict(freeze(config["arch"]))


def from_json(cls, value: dict):
    """The dataclass ``cls`` from a JSON object: each field converted to
    the type it is annotated with (a nested dataclass from an object, a
    tuple from a list, through ``Optional``); keys that are not fields
    are dropped."""
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: _typed(hints[k], v) for k, v in value.items()
                  if k in names})


def _typed(hint, value):
    if typing.get_origin(hint) in (typing.Union, types.UnionType):
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        if len(args) == 1:
            hint = args[0]
    if isinstance(value, dict) and dataclasses.is_dataclass(hint):
        return from_json(hint, value)
    if isinstance(value, list):
        return tuple(value)
    return value


def program_config(arch: dict):
    """The program's ``ArchConfig`` of a configuration's JSON ``arch``."""
    from repro.configs.base import ArchConfig
    return from_json(ArchConfig, arch)


def seed_key(seed: int):
    """A key from any non-negative integer below 2**64 (PRNGKey keeps the
    low 32 bits only; the high ones are folded in)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@functools.partial(jax.jit, static_argnames=("S", "N", "seq", "vocab"))
def make_shards(key, alpha, *, S, N, seq, vocab):
    """Non-IID token clients: client s draws every token from its own
    Dirichlet(alpha)-skewed unigram distribution (by inverse CDF); labels
    are the next token."""
    k_dir, k_tok = jax.random.split(key)
    g = jax.random.gamma(k_dir, alpha, (S, vocab))
    cdf = jnp.cumsum(g / g.sum(-1, keepdims=True), axis=-1)
    u = jax.random.uniform(k_tok, (S, N * (seq + 1)))
    toks = jax.vmap(jnp.searchsorted)(cdf, u)
    toks = jnp.minimum(toks, vocab - 1).astype(jnp.int32)
    toks = toks.reshape(S, N, seq + 1)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


@functools.partial(jax.jit, static_argnames=("arch_items",))
def make_weights(key, *, arch_items):
    return refmodel.init_params(dict(arch_items), key)


class Compiles:
    """Backend compilations, counted from JAX's monitoring events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


class GCLog:
    """Python garbage collections by generation, with their pauses."""

    def __init__(self):
        self.events = []
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.events.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)
        return False


@dataclasses.dataclass
class Result:
    window: win.Window
    setup_s: float
    peak_bytes: int
    checks: dict
    compiles_in_window: int
    trace: object = None


class Cell:
    """Everything one run of one workload needs, named in BENCHMARK.json."""

    def __init__(self, workload: str, seed: int, spec: dict = None):
        spec = spec or load(workload)
        self.name = workload
        self.seed = seed
        self.bench = spec["bench"]
        self.entry = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.limits = spec["limits"]
        self.arch = arch_of(self.config)
        self.arch_items = tuple(sorted(self.arch.items()))
        t = self.traffic
        if t["surrogate"] != "scalar" or t["dynamics"] != "sgld":
            raise ValueError("the reference covers scalar surrogates and "
                             "Langevin dynamics only")
        self.S, self.N = t["clients"], t["sequences_per_client"]
        self.m, self.T = t["minibatch"], t["local_steps"]
        self.h = float(t["step_size"])
        self.steps_per_round = self.T

    # -- set-up --------------------------------------------------------

    def make_inputs(self, seconds: float):
        """Traffic, weights, surrogate bank and one key for every round
        that ``seconds`` can hold, from the seed. Returns (theta0, the
        seconds the bank's fit took)."""
        key = seed_key(self.seed)
        k_w, k_data, k_fit, k_run = jax.random.split(key, 4)
        t = self.traffic
        self.k_w, self.k_run = k_w, k_run
        self.data = make_shards(
            k_data, jnp.float32(t["dirichlet_alpha"]), S=self.S, N=self.N,
            seq=t["seq_len"], vocab=self.arch["vocab_size"])
        theta0 = self.weights()
        fit = t["fit"]
        t0 = time.perf_counter()
        self.bank = jax.block_until_ready(refsampler.fit_bank(
            theta0, self.data, k_fit, arch=self.arch, h=self.h,
            burn=fit["burn"], minibatch=fit["minibatch"]))
        fit_s = time.perf_counter() - t0
        self.keys = np.asarray(jax.random.split(
            k_run, CHECKED_ROUNDS + 1 + math.ceil(seconds * ROUNDS_PER_S)))
        return theta0, fit_s

    def weights(self):
        return make_weights(self.k_w, arch_items=self.arch_items)

    def program(self, devices):
        """The system under test: an FSGLD sampler on the packed executor
        over the cell's clients, with the bank above."""
        from repro import api
        from repro.core.surrogate import Gaussian, SurrogateBank
        from repro.launch.mesh import make_sim_mesh
        from repro.models import log_lik_fn

        cfg = program_config(self.config["arch"])
        means, lam_s, mu_g, lam_g = self.bank
        td = jax.tree.structure(mu_g)
        L = td.num_leaves
        bank = SurrogateBank(
            means, jax.tree.unflatten(td, [lam_s[:, i] for i in range(L)]),
            Gaussian(mu_g, jax.tree.unflatten(td, [lam_g[i]
                                                   for i in range(L)]),
                     "scalar"), "scalar")
        t = self.traffic
        fsgld = api.FSGLD(
            api.Posterior(lambda p, b: log_lik_fn(p, cfg, b),
                          prior_precision=t["prior_precision"],
                          temperature=t["temperature"]),
            self.data, minibatch=self.m, step_size=self.h,
            method=t["method"], kernel=t["dynamics"], alpha=t["alpha"],
            surrogate=api.SurrogateSpec(kind=t["surrogate"], bank=bank),
            schedule=api.Schedule(rounds=1, local_steps=self.T, n_chains=1,
                                  reassign=t["reassign"]),
            execution=api.Execution(
                mesh=make_sim_mesh(1, 1, devices=devices[:1]),
                executor=t["executor"], collect=False),
            federation=t["federation"])
        engine = fsgld.engine
        fed = fsgld.federation

        def round_fn(i, state):
            return engine.run(self.keys[i], state, 1, n_chains=1,
                              reassign=t["reassign"], collect=False,
                              stacked=True, federation=fed)
        return round_fn

    # -- the run --------------------------------------------------------

    def start(self, devices, seconds: float = 0.0):
        """Set-up up to a window of ``seconds``: inputs, the program and
        its first rounds through the window's own call. Returns (round_fn,
        state, the states after each first round on the host, seconds
        spent on the benchmark's own work: the bank's fit and those host
        copies)."""
        theta0, own_s = self.make_inputs(seconds)
        round_fn = self.program(devices)
        state = jax.tree.map(lambda t: t[None], theta0)
        del theta0
        held = []
        for i in range(CHECKED_ROUNDS):
            state = round_fn(i, state)
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            held.append(jax.tree.map(lambda t: np.asarray(t[0]), state))
            own_s += time.perf_counter() - t0
        return round_fn, state, held, own_s

    def run(self, seconds: float, *, t_start: float, devices,
            trace_dir=None, log=print) -> Result:
        round_fn, state, held, own_s = self.start(devices, seconds)
        compiles = Compiles()
        gc.collect()
        gc.freeze()
        first = CHECKED_ROUNDS
        step = lambda i, s: round_fn(first + i, s)  # noqa: E731
        wait = jax.block_until_ready
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
            step, wait = annotated(step, wait)
        setup_s = time.perf_counter() - t_start - own_s
        c0 = compiles.count
        with GCLog() as gcl:
            state, w = win.run_window(step, wait, state, seconds,
                                      self.steps_per_round)
        in_window = compiles.count - c0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        gc.unfreeze()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:1])
        report_window(w, gcl.events, in_window, own_s, log)
        del state, round_fn
        gc.collect()
        values = self.check(held)
        return Result(w, setup_s, peak, values, in_window)

    # -- the check ------------------------------------------------------

    def reference_rounds(self, theta, *, mode="fp32", store=None,
                         fault=None, zero_grad=False, rounds=CHECKED_ROUNDS):
        """The reference's states after each of the first ``rounds``."""
        means, lam_s, mu_g, lam_g = self.bank
        scale, f_s = refsampler.estimator_scale(self.N, self.S, self.m)
        L = len(jax.tree.leaves(theta))
        out = []
        for r in range(rounds):
            client, steps = refsampler.round_streams(
                jnp.asarray(self.keys[r]), num_clients=self.S,
                local_steps=self.T, minibatch=self.m, shard_size=self.N,
                num_leaves=L)
            mu_s = jax.tree.map(lambda x: x[client], means)
            scal = {"h": jnp.float32(self.h), "scale": scale, "f_s": f_s,
                    "lam_g": lam_g, "lam_s": lam_s[client]}
            for rows, seeds in steps:
                batch = jax.tree.map(lambda d: d[client][rows], self.data)
                theta = refsampler.step(
                    theta, batch, seeds, mu_g, mu_s, scal,
                    arch_items=self.arch_items, mode=mode, store=store,
                    fault=fault, zero_grad=zero_grad)
            out.append(theta)
        return out

    def grad_unit(self) -> float:
        scale, _ = refsampler.estimator_scale(self.N, self.S, self.m)
        return float(self.h / 2 * scale)

    def check(self, held) -> dict:
        """The numbers compared, program against the float32 reference."""
        with jax.default_matmul_precision("highest"):
            theta0 = self.weights()
            r2, r4 = self.reference_rounds(theta0)
            z2 = self.reference_rounds(theta0, zero_grad=True, rounds=1)[0]
            return checks.numbers(theta0, held[0], held[1], r2, r4, z2,
                                  self.grad_unit())


def annotated(step, wait):
    """``step`` and ``wait`` inside host spans on the profiler's clock:
    ``bench.round`` (dispatch to completion), ``bench.dispatch`` and
    ``bench.wait``."""
    open_ = []

    def step_(i, s):
        r = jax.profiler.TraceAnnotation("bench.round")
        r.__enter__()
        open_.append(r)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return step(i, s)

    def wait_(s):
        with jax.profiler.TraceAnnotation("bench.wait"):
            wait(s)
        open_.pop().__exit__(None, None, None)
    return step_, wait_


def report_window(w: win.Window, gc_events, compiles: int, own_s: float,
                  log=print):
    """Per-round lines for the stall hunt (never the last line)."""
    rs = w.round_s()
    med = float(np.median(rs))
    slow = [(i, round(r * 1e3, 3)) for i, r in enumerate(rs)
            if r > 1.5 * med]
    btw = w.between_s()
    by_gen = {}
    for gen, dur in gc_events:
        n, tot, mx = by_gen.get(gen, (0, 0.0, 0.0))
        by_gen[gen] = (n + 1, tot + dur, max(mx, dur))
    log(f"window: {w.rounds} rounds in {w.seconds:.6f} s; round ms median "
        f"{med * 1e3:.3f} max {max(rs) * 1e3:.3f} p90 "
        f"{win.percentile(rs, 90) * 1e3:.3f}; rounds over 1.5x median: "
        f"{slow}")
    log(f"window: host ms between rounds: median "
        f"{np.median(btw) * 1e3 if btw else 0:.3f} max "
        f"{max(btw) * 1e3 if btw else 0:.3f} sum {sum(btw) * 1e3:.3f}; "
        f"dispatch ms median {np.median(w.dispatch_s()) * 1e3:.3f} max "
        f"{max(w.dispatch_s()) * 1e3:.3f}")
    log(f"window: compilations inside the window: {compiles}; gc "
        f"collections (generation: count, total ms, max ms): "
        + ", ".join(f"{g}: {n}, {t * 1e3:.3f}, {m * 1e3:.3f}"
                    for g, (n, t, m) in sorted(by_gen.items()))
        + f"; benchmark's fit and host copies in set-up {own_s:.3f} s "
        "(not in setup_s)")
    log("window: round ms " + " ".join(f"{r * 1e3:.2f}" for r in rs))
    sys.stdout.flush()
