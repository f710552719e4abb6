"""One FSGLD front door: the declarative sampler facade.

The paper's pitch is that conducive gradients are a *drop-in* correction
to DSGLD — one algorithm family parameterized by surrogate, schedule, and
execution (cf. FA-LD, arXiv:2112.05120; ELF, arXiv:2303.04622). This
module is that family's single entry point: every workload — the Sec 5.1
Gaussian toy, the BNN benchmarks, and the billion-parameter transformer
posterior — routes through the SAME mesh-parallel chain engine
(``repro.core.engine.MeshChainEngine``), so a new variant lands once, not
once per scale.

Four declarative pieces:

  * :class:`Posterior`     — log-likelihood + Gaussian prior + temperature.
  * :class:`SurrogateSpec` — the conducive-gradient surrogates q_s: kind
    (``none``/``diag``/``scalar``/``linear``/``full``), how to fit them
    (a prefit bank, gradient-matching ``refresh``, Fisher–Laplace
    ``fisher``, or per-client ``local_sgld`` runs), and the adaptive
    refresh schedule.
  * :class:`Schedule`      — rounds, local steps T, chain count,
    reassignment rule, trace thinning.
  * :class:`Execution`     — mesh, executor (``vmap``/``per_leaf``/
    ``packed``/``auto``), surrogate storage dtype (bf16 at scale),
    whether to collect a trace or return final states.
  * :class:`Federation`    — the scenario (``repro.fed``): non-IID
    partitioner, communication schedule (delayed rounds / partial
    participation / stragglers), compressed round payloads — passed as
    a spec or a registry name (``'dirichlet-0.1'``, ``'delayed-5x'``,
    ``'topk-1%'``, ...), and executed INSIDE the engine's jitted scan.

and one verb::

    fsgld = FSGLD(posterior, data, minibatch=10, surrogate=spec,
                  schedule=Schedule(rounds=300, local_steps=100))
    samples = fsgld.sample(jax.random.PRNGKey(0), theta0)

``sample`` preserves the engine's bit-exactness contract: with the
default executor on the host mesh it equals the legacy
``FederatedSampler.run_vmap`` oracle at fp32, noise included.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SamplerConfig
from repro.core.engine import MeshChainEngine, pad_shards
from repro.core.federated import fit_bank_fisher, refresh_bank
from repro.core.health import Recovery, RunHealth
from repro.core.surrogate import SurrogateBank, fit_scalar_tree, make_bank
from repro.fed import Federation, Stream, SyntheticClientSource, get_scenario
from repro.obs import MetricsFrame, Telemetry
from repro.fed.partition import (is_client_source,
                                 partition as partition_clients,
                                 resolve_shard_probs)
from repro.rivals.methods import get_method

PyTree = Any
LogLikFn = Callable[[PyTree, PyTree], jax.Array]

__all__ = [
    "Posterior", "SurrogateSpec", "Schedule", "Execution", "Federation",
    "Stream", "SyntheticClientSource", "Recovery", "RunHealth", "Serving",
    "Telemetry", "MetricsFrame",
    "FSGLD", "fit_bank_local_sgld", "get_scenario",
]

_COLLECT_SIGNALS = ("mean", "entropy", "mutual_info", "variance")

_EXECUTORS = ("auto", "vmap", "per_leaf", "packed")


@dataclasses.dataclass(frozen=True)
class Posterior:
    """The target: log p(theta | x) ∝ prior * likelihood.

    ``log_lik(theta, batch) -> scalar`` is the minibatch log-likelihood
    (summed over the batch); the prior is N(0, prior_precision^-1 I).
    ``temperature`` scales the injected noise (0 -> MAP/SGD limit).
    """
    log_lik: LogLikFn
    prior_precision: float = 1.0
    temperature: float = 1.0


@dataclasses.dataclass(frozen=True)
class SurrogateSpec:
    """How the conducive-gradient surrogates q_s are built and refreshed.

    kind:
      'none'   — no surrogate: the sampler runs DSGLD (or centralized
                 SGLD, see ``FSGLD`` method resolution).
      'diag'   — per-dimension Gaussian precisions (flat-vector params).
      'scalar' — per-tensor isotropic Gaussians (pytree params; the
                 billion-parameter format).
      'linear' — control-variate surrogates (bounded conducive term).
      'full'   — dense precision (paper-scale models only).

    fit (used when ``bank`` is None):
      'auto'       — 'refresh' for diag, 'local_sgld' for scalar.
      'refresh'    — gradient-matching Fisher fit at theta0
                     (``repro.core.refresh_bank``; no RNG, diag only).
      'fisher'     — Fisher–Laplace fit at theta0 (diag only).
      'local_sgld' — short per-client SGLD runs against the local
                     likelihood + moment fits (paper Sec 3.1; the
                     large-model phase 1). Uses fit_steps/fit_minibatch/
                     fit_step_size.

    ``refresh_every`` re-fits the bank every that many rounds at the
    current chain mean (adaptive refresh — diag banks only).
    """
    kind: str = "diag"
    bank: Optional[SurrogateBank] = None
    fit: str = "auto"
    refresh_every: Optional[int] = None
    fit_steps: int = 200
    fit_minibatch: int = 32
    fit_step_size: Optional[float] = None

    def __post_init__(self):
        assert self.kind in ("none", "diag", "scalar", "linear", "full"), \
            self.kind
        assert self.fit in ("auto", "refresh", "fisher", "local_sgld"), \
            self.fit


@dataclasses.dataclass(frozen=True)
class Schedule:
    """The communication schedule of Algorithm 1.

    rounds x local_steps Langevin updates per chain; ``reassign`` is the
    chain->client rule ('categorical' = the paper's i.i.d. draw,
    'permutation' = the collision-free SPMD variant); ``thin`` keeps every
    thin-th local step in the trace.
    """
    rounds: int
    local_steps: int = 40
    n_chains: int = 1
    reassign: str = "categorical"
    thin: int = 1

    def __post_init__(self):
        assert self.reassign in ("categorical", "permutation"), self.reassign


@dataclasses.dataclass(frozen=True)
class Execution:
    """Where and how the chains run.

    mesh: a ('data', 'model') jax mesh (None -> the 1x1 host mesh).
    executor:
      'vmap'     — the reference executor (pure-jnp update, vmapped chain
                   blocks inside shard_map; bit-identical to the legacy
                   ``run_vmap`` oracle).
      'per_leaf' — chain-batched fused Pallas kernel, one pallas_call per
                   leaf per step.
      'packed'   — single-launch packed executor: ONE pallas_call per step
                   for the whole chain block (any floating param dtypes;
                   non-fp32 leaves quantize back per step).
      'auto'     — 'packed' on TPU backends, 'vmap' elsewhere (the Pallas
                   kernels run interpreted off-TPU, which is for
                   correctness work, not speed).
    dtype: surrogate STORAGE dtype override (e.g. jnp.bfloat16): the bank
      means are stored at this dtype — the large-model memory format.
    collect: False returns final chain states instead of a trace (the
      trace of a billion-parameter posterior does not fit anywhere).
    recovery: a :class:`Recovery` policy (``repro.core.health``) — turns
      on the in-scan chain health check; ``sample`` then returns
      ``(result, RunHealth)``. None = no health tracking (bit-identical
      to before).
    snapshot_every / snapshot_path: atomically checkpoint the full scan
      carry every that many rounds into the directory (preemption-safe;
      resumable). resume: continue from the newest valid snapshot in
      ``snapshot_path`` — traces are bitwise identical to an
      uninterrupted run.
    stream: a :class:`repro.fed.Stream` — the streamed client axis: only
      ``stream.resident`` clients live on device, with host prefetch of
      the next window's shards overlapping the scan. Fault-free streamed
      runs are bitwise identical to the resident path; requires
      ``Schedule(reassign='permutation')`` and does not compose with
      refresh_every / snapshots / recovery (the engine refuses loudly).
    telemetry: a :class:`repro.obs.Telemetry` spec — per-round per-chain
      metric rows (grad/drift/conducive norms, noise scale,
      participation, wire bytes, health words) lowered into the scanned
      round body; ``sample`` then additionally returns a
      :class:`repro.obs.MetricsFrame`. Telemetry-off runs stay bitwise
      identical, and telemetry probes draw from a salted key stream so
      telemetry-on traces are bitwise identical too. Does not compose
      with ``stream``.
    """
    mesh: Any = None
    executor: str = "auto"
    dtype: Any = None
    collect: bool = True
    recovery: Optional[Recovery] = None
    snapshot_every: Optional[int] = None
    snapshot_path: Optional[str] = None
    resume: bool = False
    stream: Optional[Stream] = None
    telemetry: Optional[Telemetry] = None

    def __post_init__(self):
        assert self.executor in _EXECUTORS, self.executor
        if (self.snapshot_every or self.resume) \
                and not self.snapshot_path:
            raise ValueError(
                "Execution.snapshot_every/resume need snapshot_path")


@dataclasses.dataclass(frozen=True)
class Serving:
    """How the posterior is SERVED: K draws as one Bayesian ensemble.

    The sampler's product is a posterior, not a point estimate;
    :meth:`FSGLD.serve` turns this spec plus a draw source into a running
    :class:`repro.serve.EnsembleServer` — one shared prefill per request,
    per-token decode fan-out over the ``draws`` axis, next token from the
    predictive mean. ``draws=1`` is bit-identical to the legacy
    single-draw path (tests/test_serving.py pins this).

    arch / smoke: which transformer config the draws parameterize
    (``repro.configs``); draw banks record their arch and the server
    REFUSES a mismatched bank instead of shape-erroring.
    batch / prompt_len / gen: the request shape drivers default to.
    mesh: optional ('data', 'model') mesh — the draw axis rides 'data'
    (``repro.sharding.rules.ensemble_shardings``) when K divides it.
    collect: which per-token uncertainty signals drivers report —
    subset of ('mean', 'entropy', 'mutual_info', 'variance'). Every
    signal is always computed (they share one softmax); ``collect`` is
    the declared output contract, mirroring ``Execution.collect``.
    """
    draws: int = 1
    arch: str = "qwen3-1.7b"
    smoke: bool = True
    batch: int = 4
    prompt_len: int = 32
    gen: int = 16
    mesh: Any = None
    collect: tuple = ("mean", "entropy", "mutual_info", "variance")

    def __post_init__(self):
        if self.draws < 1:
            raise ValueError(f"draws must be >= 1, got {self.draws}")
        bad = [c for c in self.collect if c not in _COLLECT_SIGNALS]
        if bad:
            raise ValueError(
                f"unknown collect signals {bad}; pick from "
                f"{_COLLECT_SIGNALS}")


class FSGLD:
    """The unified sampler: one constructor, one ``sample``.

    data: client shards — either a pytree with stacked (S, n, ...) leaves
    or a list of per-client pytrees (ragged clients are padded with
    ``pad_shards`` and the pad rows are provably dead). ``method``
    selects the sampling method from the ``repro.rivals`` table:
    'fsgld' (the source paper; needs a surrogate kind other than
    'none'), 'dsgld'/'sgld' (baselines, surrogates ignored), or 'fald'
    (FA-LD, arXiv:2112.05120 — DSGLD clients whose states the engine
    server-averages at every communication round, each client's noise
    amplified sqrt(C); Langevin kernel only). ``kernel`` selects
    the transition dynamics: 'sgld' (the Langevin family above) or
    'sghmc' (federated SGHMC with the SAME conducive estimator stack —
    see repro.core.sghmc; ``friction`` is its alpha_f knob). Both
    dynamics compose with every executor — packed SGHMC carries the
    momenta in a second chain-major buffer and is bit-identical to the
    run_vmap oracle (tests/test_parity_matrix.py).

    ``federation`` selects the federation scenario (``repro.fed``): a
    :class:`Federation` spec or a registry name. With a partition spec
    the ``data`` argument is POOLED (N, ...) arrays and the partitioner
    splits it onto clients; the schedule/compression axes lower into the
    engine's scanned round body (identity == the oracle, bitwise).
    """

    def __init__(self, posterior: Posterior, data: PyTree, *,
                 minibatch: int, step_size: float = 1e-4,
                 method: str = "fsgld", kernel: str = "sgld",
                 alpha: float = 1.0, friction: float = 0.1,
                 surrogate: Optional[SurrogateSpec] = None,
                 schedule: Optional[Schedule] = None,
                 execution: Optional[Execution] = None,
                 shard_probs: Optional[tuple] = None,
                 sizes: Optional[tuple] = None,
                 federation: Any = None):
        meth = get_method(method)
        if kernel not in ("sgld", "sghmc"):
            raise ValueError(kernel)
        if meth.aggregation == "fald" and kernel == "sghmc":
            raise ValueError(
                "method='fald' is a Langevin algorithm (FA-LD averages "
                "overdamped clients); it does not compose with "
                "kernel='sghmc'")
        self.method = meth
        self.posterior = posterior
        self.surrogate = surrogate if surrogate is not None \
            else (SurrogateSpec() if meth.needs_surrogate
                  else SurrogateSpec(kind="none"))
        if meth.needs_surrogate and self.surrogate.kind == "none":
            raise ValueError("method='fsgld' needs a surrogate kind other "
                             "than 'none' (that's DSGLD)")
        self.schedule = schedule if schedule is not None \
            else Schedule(rounds=100)
        self.execution = execution if execution is not None else Execution()
        self.kernel = kernel
        self.friction = friction
        self.federation = (get_scenario(federation)
                           if federation is not None else None)

        if is_client_source(data):
            # lazy per-client source (the streamed-scale data contract):
            # the engine materializes only the clients a run touches
            if self.federation is not None and \
                    self.federation.partition is not None:
                raise ValueError(
                    "a ClientSource is already partitioned per client; "
                    "it does not compose with a Federation partition "
                    "spec (wrap the pooled data in PartitionedSource "
                    "instead)")
            if sizes is not None:
                raise ValueError("a ClientSource carries its own sizes")
            num_shards = int(data.num_clients)
        else:
            if self.federation is not None and \
                    self.federation.partition is not None:
                # with a partition spec the data contract flips:
                # ``data`` is POOLED (pytree of (N, ...) leaves) and the
                # partitioner splits it onto clients (padded + masked,
                # ragged ok). The partition RNG comes from the spec's
                # own seed — changing the scenario never perturbs the
                # sampling stream.
                data, sizes = partition_clients(
                    None, data, self.federation.partition)
            elif isinstance(data, (list, tuple)):
                data, inferred = pad_shards(list(data))
                sizes = sizes if sizes is not None else inferred
            num_shards = jax.tree.leaves(data)[0].shape[0]
        self.data = data
        self.sizes = sizes
        if isinstance(shard_probs, str):
            # partition-aware preset ('uniform', 'size-proportional',
            # 'sqrt-size') resolved against the true client sizes via
            # the hierarchical (cross-silo) host reductions
            if is_client_source(data):
                true_sizes = np.asarray(data.sizes)
            elif sizes is not None:
                true_sizes = np.asarray(sizes)
            else:
                true_sizes = np.full(
                    (num_shards,), jax.tree.leaves(data)[0].shape[1])
            shard_probs = resolve_shard_probs(shard_probs, true_sizes)
        self.cfg = SamplerConfig(
            method=meth.cfg_method, step_size=step_size,
            num_shards=num_shards,
            shard_probs=shard_probs,
            local_updates=self.schedule.local_steps, alpha=alpha,
            surrogate=(self.surrogate.kind
                       if self.surrogate.kind != "none" else "diag"),
            prior_precision=posterior.prior_precision,
            temperature=posterior.temperature)
        self.minibatch = minibatch
        self.bank = (self.surrogate.bank if self.execution.dtype is None
                     or self.surrogate.bank is None
                     else self.surrogate.bank.astype(self.execution.dtype))
        self._engine = None

    # -- surrogate fitting (phase 1: computed once, communicated once) ----

    def fit(self, key: jax.Array, theta0: PyTree) -> SurrogateBank:
        """Fit the surrogate bank per the spec and install it. Called
        automatically by ``sample`` when needed; exposed so drivers can
        time / inspect phase 1. ``key`` feeds only the stochastic fit
        methods ('local_sgld'); deterministic fits ignore it."""
        spec = self.surrogate
        if spec.kind == "none":
            raise ValueError("surrogate kind 'none': nothing to fit")
        if is_client_source(self.data):
            raise ValueError(
                "surrogate fitting needs materialized (S, n, ...) shard "
                "data; with a ClientSource pass a prefit bank "
                "(SurrogateSpec(bank=...)) or a surrogate-free method "
                "('dsgld')")
        fit = spec.fit
        if fit == "auto":
            fit = "local_sgld" if spec.kind == "scalar" else "refresh"
        if fit == "refresh":
            bank = refresh_bank(self.posterior.log_lik, self.data, theta0)
        elif fit == "fisher":
            S = self.cfg.num_shards
            means = jnp.broadcast_to(theta0[None], (S,) + theta0.shape)
            bank = fit_bank_fisher(self.posterior.log_lik, self.data, means)
        elif fit == "local_sgld":
            bank = fit_bank_local_sgld(
                self.posterior.log_lik, self.data, theta0, key,
                fit_steps=spec.fit_steps, minibatch=spec.fit_minibatch,
                step_size=(spec.fit_step_size if spec.fit_step_size
                           is not None else self.cfg.step_size),
                kind=spec.kind)
        else:
            raise ValueError(fit)
        if self.execution.dtype is not None:
            bank = bank.astype(self.execution.dtype)
        self.bank = bank
        self._engine = None
        return bank

    # -- engine resolution -------------------------------------------------

    def _resolve_executor(self) -> tuple[bool, Optional[bool]]:
        """executor name -> (use_kernel, packed) engine knobs. Every
        executor composes with both transition kernels ('sgld'/'sghmc');
        the packed executor takes any mix of floating parameter dtypes
        (non-fp32 leaves quantize back per step)."""
        ex = self.execution.executor
        if ex == "auto":
            if jax.default_backend() == "tpu":
                # engine auto mode: packed for floating params, silent
                # per-leaf fallback for non-float leaves (packed=None) —
                # 'auto' must never crash on an exotic parameter tree
                return True, None
            ex = "vmap"
        if ex == "vmap":
            return False, None
        if ex == "per_leaf":
            return True, False
        return True, True  # 'packed' (strict: raises on non-float leaves)

    @property
    def engine(self) -> MeshChainEngine:
        """The (cached) chain engine every workload routes through."""
        if self._engine is None:
            use_kernel, packed = self._resolve_executor()
            sghmc = None
            if self.kernel == "sghmc":
                from repro.core.sghmc import SGHMCConfig
                sghmc = SGHMCConfig(friction=self.friction,
                                    temperature=self.posterior.temperature)
            self._engine = MeshChainEngine(
                self.posterior.log_lik, self.cfg, self.data,
                self.minibatch,
                bank=self.bank if self.cfg.method == "fsgld" else None,
                use_kernel=use_kernel, mesh=self.execution.mesh,
                sizes=self.sizes, packed=packed,
                dynamics=("sghmc" if self.kernel == "sghmc"
                          else "langevin"),
                sghmc=sghmc, aggregation=self.method.aggregation)
        return self._engine

    # -- phase 2: sampling -------------------------------------------------

    def sample(self, key: jax.Array, theta0: PyTree, *,
               rounds: Optional[int] = None,
               n_chains: Optional[int] = None,
               federation: Any = None,
               stream: Optional[Stream] = None,
               telemetry: Optional[Telemetry] = None):
        """Run the full schedule and return stacked samples with leading
        axes (n_chains, rounds * local_steps / thin, ...) — or the final
        chain states when ``Execution.collect`` is False.

        ``key`` drives sampling only (surrogate fitting, if still needed,
        uses a folded sub-key), so a prefit-bank run consumes exactly the
        oracle's RNG stream. ``rounds``/``n_chains`` override the
        schedule for sweep drivers; everything else is fixed at
        construction.

        ``federation`` — a ``repro.fed.Federation`` spec or a registry
        name (``'delayed-5x'``, ``'topk-1%'``, ...) — overrides the
        constructor's scenario for this run. Only the ENGINE axes
        (communication schedule, compression) can change per call: the
        partition fixed the data at construction, so an override whose
        partition differs is refused. The identity scenario is
        bit-identical to ``federation=None`` on every executor.

        ``stream`` — a ``repro.fed.Stream`` — overrides
        ``Execution.stream`` for this run (the streamed client axis:
        only ``resident`` clients on device, host prefetch overlapping
        the scan, bitwise identical to the resident path).

        ``telemetry`` — a ``repro.obs.Telemetry`` — overrides
        ``Execution.telemetry`` for this run; the return value then
        gains a trailing ``repro.obs.MetricsFrame`` of per-round
        per-chain metric rows.
        """
        if (self.cfg.method == "fsgld" and self.bank is None):
            self.fit(jax.random.fold_in(key, 0x5357), theta0)
        fed = self.federation
        if federation is not None:
            fed = get_scenario(federation)
            base = (self.federation.partition
                    if self.federation is not None else None)
            if fed.partition is not None and fed.partition != base:
                raise ValueError(
                    "sample(federation=...) cannot re-partition: the "
                    "data was split at construction; pass the partition "
                    "scenario to the FSGLD constructor instead")
        sched = self.schedule
        exe = self.execution
        return self.engine.run(
            key, theta0, rounds if rounds is not None else sched.rounds,
            n_chains=(n_chains if n_chains is not None
                      else sched.n_chains),
            reassign=sched.reassign, collect_every=sched.thin,
            refresh_every=self.surrogate.refresh_every,
            collect=exe.collect, federation=fed,
            recovery=exe.recovery, snapshot_every=exe.snapshot_every,
            snapshot_path=exe.snapshot_path, resume=exe.resume,
            stream=stream if stream is not None else exe.stream,
            telemetry=(telemetry if telemetry is not None
                       else exe.telemetry))

    # -- phase 3: serving the posterior ------------------------------------

    @staticmethod
    def serve(spec: Serving, *, bank: Optional[str] = None,
              draws: Any = None, seed: int = 0):
        """Stand up an ensemble server for this posterior (phase 3).

        Exactly one draw source: ``bank=`` a draw-bank directory written
        by ``repro.launch.train --draw-bank`` (a legacy single-checkpoint
        dir also works, served as one draw) — the server keeps tracking
        it and ``refresh()`` hot-swaps fresh draws in between requests;
        ``draws=`` an already-stacked (K, ...) params pytree (e.g. from
        :meth:`load_bank`); neither — ``spec.draws`` fresh inits (shape
        smoke, no posterior). Static: serving needs draws, not the
        sampler's data, so no FSGLD instance is required."""
        from repro.configs import get_config, get_smoke_config
        from repro.serve import EnsembleServer
        cfg = (get_smoke_config(spec.arch) if spec.smoke
               else get_config(spec.arch))
        n = None if (bank is None and draws is not None) else spec.draws
        return EnsembleServer(cfg, bank=bank, draws=draws, n_draws=n,
                              mesh=spec.mesh, seed=seed)

    @staticmethod
    def load_bank(path: str, like: PyTree, *, k: Optional[int] = None,
                  expect_arch: Optional[str] = None):
        """Load the freshest ``k`` draws from a draw bank as one stacked
        (K, ...) pytree plus their :class:`repro.checkpoint.DrawMeta`
        provenance. Fingerprint-checks every draw against ``like`` (and
        ``expect_arch`` when given) — a mismatched bank is refused with
        a ValueError, never a shape error."""
        from repro import checkpoint
        return checkpoint.load_bank(path, like, k=k,
                                    expect_arch=expect_arch)


# ---------------------------------------------------------------------------
# generic per-client local-SGLD surrogate fitting (paper Sec 3.1 phase 1)
# ---------------------------------------------------------------------------

def fit_bank_local_sgld(log_lik_fn: LogLikFn, shard_data: PyTree,
                        theta0: PyTree, key: jax.Array, *,
                        fit_steps: int, minibatch: int, step_size: float,
                        kind: str = "scalar",
                        lam_floor: float = 1e-8) -> SurrogateBank:
    """Short SGLD runs per client against the LOCAL likelihood + moment
    fits — the generic form of the large-model phase 1 (previously a
    private helper in launch/train.py). Works on any parameter pytree;
    ``kind='scalar'`` fits per-tensor isotropic Gaussians from the second
    half of each local trace, ``kind='diag'`` per-dimension ones (flat
    vector params only).

    Clients are fitted one after another (``lax.map``), each reduced to
    its moments before the next starts: device memory holds one client's
    trace, not S of them — at billion-parameter widths S stacked traces
    do not fit one chip."""
    leaf = jax.tree.leaves(shard_data)[0]
    S, n_s = leaf.shape[0], leaf.shape[1]
    if kind == "diag":
        flat = jax.tree.leaves(theta0)
        assert len(flat) == 1 and flat[0].ndim == 1, \
            "diag fits need flat-vector parameters"
    elif kind != "scalar":
        raise ValueError(kind)

    def local_fit(theta0, data_s, k):

        def body(theta, kk):
            k1, k2 = jax.random.split(kk)
            idx = jax.random.randint(k1, (minibatch,), 0, n_s)
            batch = jax.tree.map(lambda d: d[idx], data_s)
            g = jax.grad(log_lik_fn)(theta, batch)
            leaves, tdef = jax.tree.flatten(theta)
            gl = jax.tree.leaves(g)
            ks = jax.random.split(k2, len(leaves))
            new = [t + (step_size / 2) * (n_s / minibatch)
                   * gg.astype(t.dtype)
                   + jnp.sqrt(step_size)
                   * jax.random.normal(nk, t.shape, t.dtype)
                   for t, gg, nk in zip(leaves, gl, ks)]
            return jax.tree.unflatten(tdef, new)

        # burn-in (the first half) is run without keeping its states
        ks = jax.random.split(k, fit_steps)
        burn = fit_steps // 2
        theta = jax.lax.fori_loop(0, burn, lambda i, t: body(t, ks[i]),
                                  theta0)
        _, trace = jax.lax.scan(lambda t, kk: (body(t, kk),) * 2, theta,
                                ks[burn:])
        if kind == "scalar":
            # per-tensor isotropic fit
            return fit_scalar_tree(trace, jitter=lam_floor)
        tr = jax.tree.leaves(trace)[0]
        return tr.mean(0), 1.0 / (tr.var(0) + lam_floor)

    # theta0 is an argument, not a closure: closed over, its whole
    # parameter tree would be baked into the program as constants
    means, precs = jax.jit(lambda th, d, ks: jax.lax.map(
        lambda a: local_fit(th, *a), (d, ks)))(
            theta0, shard_data, jax.random.split(key, S))
    return make_bank(means, precs, kind)
