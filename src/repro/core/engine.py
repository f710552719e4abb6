"""Mesh-parallel FSGLD chain runtime (the production multi-chain engine).

The paper's parallel regime (Ahn et al.-style parallel chains; the FA-LD
follow-ups in PAPERS.md) needs MANY posterior chains resident on MANY
clients at once. The simulator in ``core/federated.py`` ran chains with a
single-host ``vmap``; this module replaces that execution path with a
``shard_map`` executor over the (``data``, ``model``) mesh from
``launch/mesh.py``:

  * ``data``  — the CHAIN axis. Chains are sharded over it; each data group
    runs its chain block locally (vmapped inside the block, so the 1x1 host
    mesh is bit-identical to the legacy vmap path).
  * ``model`` — SHARD-parallel surrogate work. The bank refresh / Fisher
    fitting pass splits the client-shard axis S over ``model`` and
    all-gathers the fitted naturals (``refresh_bank_mesh``).

Chain->client reassignment:

  * ``categorical`` — the paper's Algorithm 1: i.i.d. s ~ Categorical(f)
    per chain (chains may collide on a client).
  * ``permutation`` — the collision-free SPMD variant (DESIGN.md Sec 4.1):
    every device derives the SAME random permutation from the replicated
    round key inside the shard_map block and slices its own chain block by
    ``axis_index('data')`` — device-side, no host round-trip, and
    bit-identical to the legacy host-side ``permutation(key, S)[:C]``.

Non-uniform clients: shard data leaves are (S, max_n, ...) padded along the
sample axis; ``ShardScheme.sizes`` carries the true N_s and minibatch
indices are drawn in [0, N_s) only, so pad rows are never touched (tests
fill them with NaN to prove it).

The fused Pallas kernel path (``use_kernel=True``) routes the whole chain
block through the PACKED single-launch executor (PR 2): the entire
parameter pytree of the block lives in one chain-major
``(C * rows_total, 128)`` buffer (``kernels.ops.PackedChains``), packed
ONCE per run, and every step issues exactly ONE ``pallas_call`` covering
all leaves of all chains via static per-leaf block counts. ``packed=False``
falls back to the PR 1 per-leaf chain-batched entry
(``kernels.ops.fused_update_chains_tree`` — one ``pallas_call`` per leaf
per step).

``run`` itself is a single jitted ``lax.scan`` over communication rounds
(per mode/shape, cached): reassignment (categorical + SPMD permutation;
block-cyclic client visiting when n_chains > S), round key-splitting, and
thinned trace collection all happen inside the scan, chain state is
donated instead of copied, and the trace comes back preallocated as
``(C, R * T/collect_every, ...)`` — no host dispatch and no trailing
concatenate in the hot loop.

Federation scenarios (``repro.fed``, PR 5): ``run(...,
federation=spec)`` lowers the scenario's communication schedule (delayed
rounds, partial participation, stragglers) and round-boundary payload
compression (top-k / rand-k / qsgd with error feedback) INTO the scanned
round body — the carry gains the resident client assignment and the
compression's (server-view, error-feedback) state, still one scan and
one dispatch. The engine-identity spec lowers to None and shares the
oracle executor bit-for-bit.

Fault tolerance (PR 7): ``run(..., recovery=Recovery(...))`` lowers an
in-scan per-chain HEALTH word into the same round bodies — a finite-state
check on theta (and SGHMC momentum) plus an optional log-posterior
divergence detector probed with a ``fold_in``-derived key, so enabling
health never perturbs the sampling stream. Diverged chains are
quarantined (frozen, masked out of federation exchange and traces) or
respawned from the block's first healthy chain — both per-chain
``where`` masks, so the surviving chains' trajectories stay bitwise
identical to a fault-free run. ``chaos=`` accepts a static fault plan
(``repro.testing.ChaosSpec``, duck-typed — the engine never imports the
test harness) that NaN-poisons chosen chains' post-round state or their
compressed payloads at chosen absolute rounds. ``snapshot_every=``
atomically checkpoints the FULL scan carry (chain state, RNG key,
federation carry, health words, trace-so-far) between segments through
``repro.checkpoint.snapshot``; ``resume=True`` continues from the newest
valid snapshot with traces bitwise identical to an uninterrupted run —
the executor takes the absolute starting round and the federation carry
as inputs, so segmentation never resets in-scan state.

Rival samplers (PR 8): ``aggregation='fald'`` lowers FA-LD (federated
averaging Langevin dynamics, Deng et al. 2021) into the SAME scanned
round body — at every communication round the participating chains'
states are averaged in flat fp32 space (a masked ``psum`` over the
``data`` axis, so multi-device blocks agree), and each client's injected
noise is amplified by ``sqrt(n_chains)`` (temperature × C) so the
AVERAGED iterate targets the correct posterior temperature. ELF-style
bidirectional compression (``Compression(direction='dual'|'bidir')``)
compresses the server→client broadcast as a delta against the shared
reference with its OWN error-feedback residual riding the carry next to
the primal one — primal-only runs keep today's carry and ops bitwise.
Both lower into the one-scan/one-pallas_call/no-pad round body; the
pure-JAX FA-LD oracle lives in ``repro.rivals.fald``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SamplerConfig
from repro.core.health import HEALTH_PROBE_SALT, RunHealth
from repro.core.sampler import (LogLikFn, ShardScheme, chain_scales,
                                make_step_fn)
from repro.core.surrogate import SurrogateBank, make_bank
from repro.kernels import ops as kops
from repro.obs import trace as obs_trace
from repro.obs.telemetry import TELEMETRY_PROBE_SALT, MetricsFrame
from repro.sharding.rules import (chain_spec, fed_carry_spec,
                                  stream_window_spec)

PyTree = Any


# ---------------------------------------------------------------------------
# padding non-uniform clients
# ---------------------------------------------------------------------------

def pad_shards(per_shard: list, fill: float = jnp.nan):
    """Stack a list of per-client pytrees (each with leading axis N_s) into
    padded (S, max_n, ...) leaves + the true sizes tuple.

    Float leaves pad with NaN by default: any estimator that touches a
    pad row poisons the chain immediately instead of silently biasing it.
    Integer leaves (token ids) cannot carry NaN — jnp.pad would silently
    coerce it to 0, a VALID id — so they get the dtype's minimum as an
    extreme out-of-range sentinel instead.
    """
    sizes = tuple(int(jax.tree.leaves(t)[0].shape[0]) for t in per_shard)
    max_n = max(sizes)

    def pad_one(leaf):
        pad = [(0, max_n - leaf.shape[0])] + [(0, 0)] * (leaf.ndim - 1)
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            value = fill
        else:
            value = jnp.iinfo(leaf.dtype).min
        return jnp.pad(leaf, pad, constant_values=value)

    stacked = jax.tree.map(
        lambda *leaves: jnp.stack([pad_one(l) for l in leaves]), *per_shard)
    return stacked, sizes


# ---------------------------------------------------------------------------
# per-chain round bodies
# ---------------------------------------------------------------------------

def _make_batch_sampler(cfg: SamplerConfig, scheme: ShardScheme,
                        minibatch: int):
    """Returns sample(k_batch, shard_id, shard_data) -> minibatch pytree.

    DSGLD/FSGLD draw m indices with replacement from the LIVE prefix
    [0, N_s) of the resident shard. Centralized SGLD draws from the virtual
    ragged concatenation of all shards: a global index u in [0, N) maps to
    (shard, offset) via the size prefix sums — for uniform shards this
    selects exactly the elements of the legacy pooled-reshape path.

    ``sizes_rt`` overrides the closed-over (S,) size table with the
    streamed path's RESIDENT (K,) int32 rows (``shard_id`` is then
    resident-local); the rows are host-gathers of the same table, so the
    randint bound — and hence the draw — is bitwise unchanged.
    """
    sizes = scheme.sizes_array()
    total = scheme.total
    m = minibatch
    if cfg.method == "sgld":
        starts = scheme.starts_array()
        ends = jnp.cumsum(sizes)

    def sample(k_batch, shard_id, shard_data, sizes_rt=None):
        if cfg.method == "sgld":
            u = jax.random.randint(k_batch, (m,), 0, total)
            sh = jnp.searchsorted(ends, u, side="right").astype(jnp.int32)
            off = u - starts[sh]
            return jax.tree.map(lambda d: d[sh, off], shard_data)
        sz = sizes if sizes_rt is None else sizes_rt
        idx = jax.random.randint(k_batch, (m,), 0, sz[shard_id])
        return jax.tree.map(lambda d: d[shard_id][idx], shard_data)

    return sample


def make_round_fn(log_lik_fn: LogLikFn, cfg: SamplerConfig,
                  scheme: ShardScheme, step_fn, minibatch: int,
                  collect: bool = True, collect_state=None):
    """Client-side Update(T, theta_0, s) for ONE chain — the same math as
    the legacy ``FederatedSampler._round`` generalised to ragged shards.
    Returns round(state, key, shard_id, shard_data, bank_rt).

    ``state`` is whatever pytree ``step_fn`` carries: the parameter pytree
    for Langevin dynamics, the (theta, momentum) pair for SGHMC.
    ``collect_state`` projects the carried state to the traced sample
    (identity by default; SGHMC traces theta only)."""
    sample = _make_batch_sampler(cfg, scheme, minibatch)
    if collect_state is None:
        collect_state = lambda s: s  # noqa: E731

    def round_fn(state, key, shard_id, shard_data, bank_rt=None,
                 sp_rt=None):
        sizes_rt = None if sp_rt is None else sp_rt[0]

        def body(carry, k):
            state = carry
            k_batch, k_step = jax.random.split(k)
            batch = sample(k_batch, shard_id, shard_data, sizes_rt)
            state = step_fn(state, k_step, batch, shard_id, minibatch,
                            bank_rt=bank_rt, sp_rt=sp_rt)
            return state, collect_state(state) if collect else None

        keys = jax.random.split(key, cfg.local_updates)
        state, trace = jax.lax.scan(body, state, keys)
        return state, trace

    return round_fn


def make_masked_grad_vmap(grad_fn, *, per: int, n_chains: int, d_size: int):
    """Per-block gradient pass that SKIPS pad-chain work.

    Odd chain counts pad the block to ``n_total = d_size * per`` resident
    chains; the pad chains live at the global tail, so each data group i
    holds ``real_i = clip(n_chains - i*per, 0, per)`` real chains. With no
    padding this is a plain ``vmap(grad_fn)``. Otherwise the round body
    switches on ``axis_index('data')`` into a branch that vmaps the
    gradient over ONLY the group's real chains and concatenates zeros for
    the pad slots — the branches are per-device programs inside shard_map,
    so only the taken one executes and the pad chains' gradient FLOPs are
    genuinely skipped (asserted on the branch jaxprs in
    tests/test_packed_executor.py), not computed-and-discarded. The pad
    chains' elementwise kernel-update rows remain (they are ~pad/C of the
    cheap update cost; the gradient pass is the expensive part).
    """
    n_pad = d_size * per - n_chains
    if n_pad == 0:
        return lambda thetas, batches: jax.vmap(grad_fn)(thetas, batches)

    def branch(real):
        def go(args):
            thetas, batches = args
            if real == 0:
                return jax.tree.map(jnp.zeros_like, thetas)
            head = jax.vmap(grad_fn)(
                jax.tree.map(lambda t: jax.lax.slice_in_dim(t, 0, real),
                             thetas),
                jax.tree.map(lambda t: jax.lax.slice_in_dim(t, 0, real),
                             batches))
            if real == per:
                return head
            # concatenate, not `pad`: scan bodies carry a no-pad-jaxpr
            # guarantee (see _executor.pad_tail)
            return jax.tree.map(
                lambda g: jnp.concatenate(
                    [g, jnp.zeros((per - real,) + g.shape[1:], g.dtype)]),
                head)

        return go

    branches = [branch(min(max(n_chains - i * per, 0), per))
                for i in range(d_size)]

    def masked(thetas, batches):
        return jax.lax.switch(jax.lax.axis_index("data"), branches,
                              (thetas, batches))

    return masked


def make_chain_round_fn(log_lik_fn: LogLikFn, cfg: SamplerConfig,
                        scheme: ShardScheme, minibatch: int,
                        bank_kind: Optional[str], collect: bool = True,
                        dynamics: str = "langevin", sghmc=None,
                        grad_vmap=None):
    """CHAIN-BATCHED round for the fused-kernel path: gradients are vmapped
    over the local chain block, then the whole block goes through ONE
    chain-batched Pallas update per leaf per step.

    Returns round(state, keys, sids, shard_data, bank) operating on
    (C_blk, ...)-stacked chain states — the parameter pytree for Langevin
    dynamics, the (thetas, momenta) pair for SGHMC (``sghmc``: the
    SGHMCConfig supplying friction/temperature). ``grad_vmap`` overrides
    the block gradient pass (pad-chain masking, ``make_masked_grad_vmap``).
    """
    sample = _make_batch_sampler(cfg, scheme, minibatch)
    if grad_vmap is None:
        grad_fn = jax.grad(log_lik_fn)
        grad_vmap = lambda th, b: jax.vmap(grad_fn)(th, b)  # noqa: E731
    # only FSGLD carries the conducive correction — mirror the gating in
    # make_step_fn's kernel path, else a resident bank would silently add
    # the surrogate term to DSGLD/SGLD updates.
    use_surrogate = cfg.method == "fsgld"
    if not use_surrogate:
        bank_kind = None
    hmc = dynamics == "sghmc"
    dyn_kw = (dict(dynamics="sghmc", friction=sghmc.friction,
                   temperature=sghmc.temperature) if hmc
              else dict(temperature=cfg.temperature))

    def round_fn(state, keys, sids, shard_data, bank=None, sp_rt=None):
        if not use_surrogate:
            bank = None
        scale, f_s = chain_scales(cfg, scheme, sids, minibatch, sp_rt)
        sizes_rt = None if sp_rt is None else sp_rt[0]

        def body(carry, ks):
            thetas, r = carry if hmc else (carry, None)
            kk = jax.vmap(jax.random.split)(ks)       # (C, 2, 2)
            k_batch, k_step = kk[:, 0], kk[:, 1]
            with jax.named_scope("fsgld.batch"):
                batches = jax.vmap(
                    lambda k, s: sample(k, s, shard_data, sizes_rt))(
                    k_batch, sids)
            with jax.named_scope("fsgld.grad"):
                glls = grad_vmap(thetas, batches)
            with jax.named_scope("fsgld.update"):
                out = kops.fused_update_chains_tree(
                    thetas, glls, k_step, h=cfg.step_size, scale=scale,
                    f_s=f_s, prior_prec=cfg.prior_precision,
                    alpha=cfg.alpha, bank=bank, sids=sids,
                    surrogate_kind=bank_kind, momentum=r, **dyn_kw)
            thetas = out[0] if hmc else out
            carry = out if hmc else thetas
            return carry, thetas if collect else None

        keys_t = jax.vmap(lambda k: jax.random.split(
            k, cfg.local_updates))(keys)              # (C, T, 2)
        state, trace = jax.lax.scan(body, state,
                                    jnp.swapaxes(keys_t, 0, 1))
        if collect and trace is not None:
            # (T, C, ...) -> (C, T, ...) to match the vmap-of-scan layout
            trace = jax.tree.map(lambda t: jnp.swapaxes(t, 0, 1), trace)
        return state, trace

    return round_fn


def _perm_sids_slice(k_assign: jax.Array, num_shards: int, start,
                     per: int, n_total: Optional[int] = None) -> jax.Array:
    """Collision-free reassignment, SPMD: every device derives the SAME
    permutation of [0, S) from the replicated round key and slices its own
    chain block. Equals the host-side ``permutation(k, S)[:C]`` bitwise.
    Shared by the scanned round body and ``_permute_sids``.

    ``n_total > num_shards`` switches to BLOCK-CYCLIC client visiting:
    the round's permutation is tiled so chain c sits at client
    ``perm[c % S]`` — every client hosts floor/ceil(C/S) chains and the
    only collisions are the cyclic wrap (host-side equivalent:
    ``tile(permutation(k, S), ceil(C/S))[:C]``)."""
    perm = jax.random.permutation(k_assign, num_shards)
    if n_total is not None and n_total > num_shards:
        perm = jnp.concatenate([perm] * (-(-n_total // num_shards)))
    return jax.lax.dynamic_slice_in_dim(perm, start, per)


def pack_bank(layout: kops.PackedChains, bank: Optional[SurrogateBank]):
    """SurrogateBank -> operands for the single-launch round body.

    The shared (global) surrogate is packed ONCE here. Per-client means
    (and diag precisions) stay in the bank's storage dtype with their
    leading S axis: each round packs only the C resident clients' rows
    (``resident_surrogates``), so the device never holds an fp32 copy of
    all S clients — at billion-parameter widths that copy alone is
    S x 4 bytes per parameter.
    """
    if bank is None:
        return None
    if bank.kind == "diag":
        return {
            "mu_g": layout.pack_shared(bank.global_.mean),
            "lam_g": layout.pack_shared(bank.global_.prec),
            "means": bank.means,
            "precs": bank.precs,
        }
    if bank.kind == "scalar":
        return {
            "mu_g": layout.pack_shared(bank.global_.mean),
            "means": bank.means,
            # per-leaf scalar precisions ride in the (C, L, 8) scalar rows
            "lam_g_leaf": jnp.stack([
                jnp.asarray(p, jnp.float32)
                for p in jax.tree.leaves(bank.global_.prec)]),
            "lam_s_leaf": jnp.stack([
                jnp.asarray(p, jnp.float32)
                for p in jax.tree.leaves(bank.precs)], axis=1),
        }
    raise ValueError(bank.kind)


def resident_surrogates(layout: kops.PackedChains, pbank, sids):
    """Packed (C * rows_total, 128) resident-client operands for chains at
    clients ``sids``: (mu_s, lam_s), lam_s None for a 'scalar' bank."""
    def take(tree):
        return layout.pack(jax.tree.map(lambda m: m[sids], tree))
    return (take(pbank["means"]),
            take(pbank["precs"]) if "precs" in pbank else None)


def make_packed_round_fn(log_lik_fn: LogLikFn, cfg: SamplerConfig,
                         scheme: ShardScheme, minibatch: int,
                         bank_kind: Optional[str],
                         layout: kops.PackedChains, collect: bool = True,
                         dynamics: str = "langevin", sghmc=None,
                         grad_vmap=None):
    """SINGLE-LAUNCH round for the packed executor: the chain block's whole
    parameter pytree lives in one chain-major packed buffer and every step
    issues exactly one ``pallas_call`` (kernels.ops.packed_step).

    State is ``(packed, thetas)`` — or ``(packed, momenta_packed, thetas)``
    for ``dynamics='sghmc'``, the momenta riding a SECOND chain-major
    buffer over the same layout: the packed buffers are
    authoritative; the unpacked pytree mirror feeds the gradient pass and
    trace collection, so the scan body contains NO pad/ravel work — leaf
    gradients are written into the packed gradient buffer by static
    update-slices, and the only per-round (not per-step) work is packing
    the resident-client surrogate rows and prebuilding the scalar rows.
    Non-fp32 leaves quantize back to their storage dtype after every step
    (``layout.quantize``, identity for all-fp32 trees), replaying the
    per-leaf kernel's dtype round-trip. RNG streams (batch draws,
    per-(chain, leaf) noise seeds) are derived exactly as the per-leaf
    chain-batched round derives them, so results are bit-identical to it —
    and therefore to the ``run_vmap`` oracle.
    """
    sample = _make_batch_sampler(cfg, scheme, minibatch)
    if grad_vmap is None:
        grad_fn = jax.grad(log_lik_fn)
        grad_vmap = lambda th, b: jax.vmap(grad_fn)(th, b)  # noqa: E731
    use_surrogate = cfg.method == "fsgld"
    if not use_surrogate:
        bank_kind = None
    L = layout.num_leaves
    hmc = dynamics == "sghmc"

    def round_fn(state, keys, sids, shard_data, pbank=None, sp_rt=None):
        if not use_surrogate:
            pbank = None
        scale, f_s = chain_scales(cfg, scheme, sids, minibatch, sp_rt)
        sizes_rt = None if sp_rt is None else sp_rt[0]
        mu_g = mu_s = lam_gp = lam_sp = None
        lam_g_leaf = lam_s_leaf = None
        with jax.named_scope("fsgld.conducive"):
            if bank_kind is None:
                variant = "plain"
            elif bank_kind == "diag":
                variant = "diag"
                mu_g, lam_gp = pbank["mu_g"], pbank["lam_g"]
                mu_s, lam_sp = resident_surrogates(layout, pbank, sids)
            elif bank_kind == "scalar":
                variant = "scalar"
                mu_g = pbank["mu_g"]
                mu_s, _ = resident_surrogates(layout, pbank, sids)
                lam_g_leaf = pbank["lam_g_leaf"]
                lam_s_leaf = pbank["lam_s_leaf"][sids]
            else:
                raise ValueError(bank_kind)
            scalars = kops.packed_scalar_rows(
                layout, h=cfg.step_size, scale=scale, f_s=f_s,
                prior_prec=cfg.prior_precision, alpha=cfg.alpha,
                temperature=(sghmc.temperature if hmc
                             else cfg.temperature),
                lam_g_leaf=lam_g_leaf, lam_s_leaf=lam_s_leaf,
                friction=(sghmc.friction if hmc else 0.0))

        def body(carry, ks):
            if hmc:
                th_p, r_p, thetas = carry
            else:
                (th_p, thetas), r_p = carry, None
            kk = jax.vmap(jax.random.split)(ks)       # (C, 2, 2)
            k_batch, k_step = kk[:, 0], kk[:, 1]
            with jax.named_scope("fsgld.batch"):
                batches = jax.vmap(
                    lambda k, s: sample(k, s, shard_data, sizes_rt))(
                    k_batch, sids)
            with jax.named_scope("fsgld.grad"):
                glls = grad_vmap(thetas, batches)
            with jax.named_scope("fsgld.pack"):
                g_p = layout.pack(glls)
            with jax.named_scope("fsgld.update"):
                seeds = kops.chain_leaf_seeds(k_step, L)
                out = kops.packed_step(
                    layout, th_p, g_p, seeds, scalars, variant=variant,
                    mu_g=mu_g, mu_s=mu_s, lam_g=lam_gp, lam_s=lam_sp,
                    r_p=r_p, dynamics=dynamics)
            with jax.named_scope("fsgld.pack"):
                th_p = layout.quantize(out[0] if hmc else out)
                thetas = layout.unpack(th_p)
                if hmc:
                    carry = (th_p, layout.quantize(out[1]), thetas)
                else:
                    carry = (th_p, thetas)
            return carry, thetas if collect else None

        keys_t = jax.vmap(lambda k: jax.random.split(
            k, cfg.local_updates))(keys)              # (C, T, 2)
        state, trace = jax.lax.scan(body, state,
                                    jnp.swapaxes(keys_t, 0, 1))
        if collect and trace is not None:
            trace = jax.tree.map(lambda t: jnp.swapaxes(t, 0, 1), trace)
        return state, trace

    return round_fn


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _traced_run(run):
    """``run`` inside an ``engine.run`` span whose ``executor_built``
    counts the executors this call built (their cache misses)."""
    @functools.wraps(run)
    def traced(self, key, theta0, num_rounds, **opts):
        with obs_trace.span("engine.run", rounds=int(num_rounds)) as span:
            built = len(self._executors)
            out = run(self, key, theta0, num_rounds, **opts)
            span.set(executor_built=len(self._executors) - built)
        return out
    return traced


@dataclasses.dataclass
class MeshChainEngine:
    """shard_map-based multi-chain FSGLD runtime.

    shard_data: pytree with leaves (S, max_n, ...) — shards padded to the
    longest client; ``sizes`` carries true per-client counts (None =>
    uniform, no padding). ``mesh`` must expose ('data', 'model') axes;
    n_chains must divide by the data-axis size.

    ``use_kernel=True`` + ``packed`` (default: auto) selects the
    single-launch packed executor — one ``pallas_call`` per step for the
    whole chain block, for ANY mix of floating parameter-leaf dtypes
    (non-fp32 leaves quantize back to their storage dtype each step,
    replaying the per-leaf kernel's round-trip bit-exactly).
    ``packed=False`` keeps the PR 1 per-leaf chain-batched kernel path;
    auto falls back to it only for non-float leaves.

    ``dynamics='sghmc'`` swaps the per-step update for federated SGHMC
    (core/sghmc.py) over (theta, momentum) chain state — same estimator
    stack, reassignment, and collective path; the trace carries theta
    only. SGHMC composes with every executor: the reference vmap path
    runs the pure-jnp integrator, the fused-kernel paths route through
    the SGHMC variant of the Pallas kernels (the packed layout carries
    the momenta in a second chain-major buffer over the same segment
    table).

    ``n_chains`` no longer needs to divide the mesh data axis: odd chain
    counts are padded with dummy chains up to the next multiple (the pad
    chains run on the last data group(s) and are sliced out of every
    output). The REAL chains' RNG streams are derived from the true
    ``n_chains``, so a padded run stays bit-identical to the
    ``run_vmap`` oracle with the same chain count.

    ``aggregation='fald'`` turns the engine into FA-LD: participating
    chains' states are server-averaged at every communication round
    (inside the scan, a masked psum over the ``data`` axis) and each
    chain's injected noise is scaled so the AVERAGE has the configured
    temperature (per-client temperature × n_chains — FA-LD's
    ``sqrt(N/p_c)`` noise with uniform weights). Composes with every
    executor, Federation schedule/compression (including dual/bidir),
    health/recovery, and snapshots; Langevin dynamics only. The rounds
    always take the federated round body (even with no Federation spec),
    so FA-LD runs share one RNG stream layout with scheduled runs and
    the ``repro.rivals.fald`` oracle mirrors it bitwise.
    """
    log_lik_fn: LogLikFn
    cfg: SamplerConfig
    shard_data: PyTree
    minibatch: int
    bank: Optional[SurrogateBank] = None
    use_kernel: bool = False
    mesh: Any = None
    sizes: Optional[tuple] = None
    packed: Optional[bool] = None
    dynamics: str = "langevin"
    sghmc: Any = None  # Optional[SGHMCConfig]; None -> defaults
    aggregation: str = "none"  # 'none' | 'fald' (server-averaged rounds)
    stream_hook: Any = None  # callable(window_idx, StreamWindow) | None;
    # fires after each streamed window's dispatch (bench memory sampling)

    def __post_init__(self):
        if self.mesh is None:
            from repro.launch.mesh import make_host_mesh
            self.mesh = make_host_mesh()
        from repro.fed.partition import is_client_source
        self._source = (self.shard_data
                        if is_client_source(self.shard_data) else None)
        self._resident_cache = None
        if self._source is not None:
            # lazy per-client source: only the clients a run actually
            # touches are ever materialized (the streamed path gathers
            # resident windows; the resident path materializes all S
            # on first use — small-S only, by construction).
            s = int(self._source.num_clients)
            assert s == self.cfg.num_shards, (s, self.cfg.num_shards)
            assert self.sizes is None, \
                "a ClientSource carries its own sizes"
            sizes = np.asarray(self._source.sizes, np.int64)
            assert sizes.shape == (s,), sizes.shape
            assert int(sizes.max()) == int(self._source.max_size)
        else:
            leaf = jax.tree.leaves(self.shard_data)[0]
            s, max_n = leaf.shape[0], leaf.shape[1]
            assert s == self.cfg.num_shards, (s, self.cfg.num_shards)
            sizes = ((max_n,) * s if self.sizes is None
                     else tuple(int(n) for n in self.sizes))
            assert len(sizes) == s and max(sizes) == max_n, (sizes, max_n)
        self.scheme = ShardScheme(sizes=sizes, probs=self.cfg.probs())
        if self.aggregation not in ("none", "fald"):
            raise ValueError(
                f"unknown aggregation {self.aggregation!r}; "
                f"available: none, fald")
        if self.aggregation == "fald" and self.dynamics != "langevin":
            raise NotImplementedError(
                "aggregation='fald' is a Langevin-dynamics algorithm "
                "(FA-LD averages overdamped clients); it does not "
                f"compose with dynamics={self.dynamics!r}")
        if self.dynamics == "sghmc":
            from repro.core.sghmc import SGHMCConfig, make_sghmc_step
            if self.sghmc is None:
                self.sghmc = SGHMCConfig()
            # the pure-jnp integrator backs the reference vmap executor;
            # the kernel executors route through the fused SGHMC kernels
            self.step_fn = make_sghmc_step(
                self.log_lik_fn, self.cfg, self.scheme, self.bank,
                self.sghmc)
        elif self.dynamics == "langevin":
            self.step_fn = make_step_fn(self.log_lik_fn, self.cfg,
                                        self.scheme, self.bank,
                                        use_kernel=False)
        else:
            raise ValueError(self.dynamics)
        self._executors = {}

    # -- executors ---------------------------------------------------------

    def _chain_spec(self):
        return chain_spec()

    # -- client-axis materialization ---------------------------------------

    def _data(self):
        """The FULL (S, max_n, ...) shard stack for resident-path runs.
        Materialized (and cached) from a lazy ClientSource on first use —
        the streamed path never calls this."""
        if self._source is None:
            return self.shard_data
        if self._resident_cache is None:
            ids = np.arange(self.cfg.num_shards)
            self._resident_cache = jax.tree.map(
                jnp.asarray, self._source.rows(ids))
        return self._resident_cache

    def _client_rows(self, ids):
        """(K, max_n, ...) rows for one resident window. From a
        ClientSource this builds ONLY the requested clients; from a
        materialized stack it gathers rows of identical values — either
        way a streamed lookup reads the exact bytes the resident path
        reads, which is what makes streamed runs bitwise identical."""
        if self._source is not None:
            return jax.tree.map(jnp.asarray, self._source.rows(ids))
        idx = jnp.asarray(np.asarray(ids, np.int32))
        return jax.tree.map(lambda d: d[idx], self.shard_data)

    def _layout_for(self, theta0: PyTree) -> Optional[kops.PackedChains]:
        """Resolve the packed layout for this run, or None for the
        per-leaf paths. Mixed floating dtypes pack (non-fp32 leaves
        quantize back each step); non-float leaves cannot ride the fp32
        buffer — auto falls back to the per-leaf path, explicit
        packed=True refuses."""
        if not self.use_kernel:
            if self.packed:
                raise ValueError("packed=True requires use_kernel=True")
            return None
        if self.packed is False:
            return None
        floating = all(jnp.issubdtype(l.dtype, jnp.floating)
                       for l in jax.tree.leaves(theta0))
        if not floating:
            if self.packed is None:
                return None
            raise ValueError("packed executor requires floating-point "
                             "parameter leaves (state rides an fp32 "
                             "buffer with per-leaf quantize-back)")
        return kops.make_packed_layout(theta0)

    def _executor(self, *, num_rounds: int, n_chains: int,
                  n_total: Optional[int] = None, reassign: str,
                  collect: bool, collect_every: int,
                  layout: Optional[kops.PackedChains], federation=None,
                  recovery=None, chaos=None,
                  stream: Optional[int] = None, telemetry=None):
        """jit(shard_map(scan-over-rounds)) executor: ONE dispatch runs
        ``num_rounds`` communication rounds — reassignment, round key
        splitting, local updates, and thinned trace collection all live
        inside the scan. Chain state is donated, the trace comes back as
        a preallocated (C, num_rounds * ceil(T/collect_every), ...) block,
        and the final round key is returned so chunked callers (adaptive
        refresh, snapshot segments) continue the same stream. Cached per
        configuration.

        Signature: ``execute(key, chains, shard_data, bank_rt, r0,
        fed_carry, health) -> (chains, traces, key, fed_carry, health)``.
        ``r0`` is the absolute index of the first round this dispatch
        runs (traced — resegmenting a run never retraces); ``fed_carry``
        is ``(sids, (ref, err[, derr]) | None)`` for a lowered
        federation scenario or FA-LD aggregation (``derr`` rides along
        for dual/bidir compression) and None otherwise; ``health`` is
        ``(word, lp_window)`` when a recovery policy is active and None
        otherwise. Threading
        both through the executor I/O is what makes segment boundaries
        (snapshots, resume) invisible to the scanned state.

        ``n_chains`` is the REAL chain count (the RNG fan-out width — it
        must match the oracle's); ``n_total`` >= n_chains is the padded
        count actually resident on the mesh (a data-axis multiple). Pad
        chains get sid 0 (categorical; their permutation slot otherwise)
        and a zero key; their trajectories are computed and discarded by
        ``run``'s output slice.

        ``federation`` (a ``repro.fed.Federation``, or None) lowers the
        scenario's communication schedule and payload compression INTO
        the scanned round body: the carry gains the resident sids (kept
        across delayed/non-participating rounds) and, with compression,
        the per-chain (server-view, error-feedback) flat state — still
        one scan, one dispatch, no retrace per scenario. An
        engine-identity spec lowers to None and shares the oracle
        executor bit-for-bit.

        ``recovery`` (``repro.core.health.Recovery``, or None) lowers the
        per-chain health check + quarantine/respawn masking into the
        round bodies; ``chaos`` (duck-typed ``repro.testing.ChaosSpec``)
        lowers the static fault plan. Both are per-chain ``where`` masks:
        a fault-free run with them enabled is bitwise identical to one
        without, and a faulted chain never touches its neighbours.

        ``telemetry`` (``repro.obs.Telemetry``, or None) lowers per-round
        per-chain metric rows into the same round bodies as EXTRA scan
        outputs; the executor then returns a sixth value — a dict of
        (C, num_rounds) fp32 metric arrays. Probe metrics draw their
        minibatch from ``fold_in(k_run, TELEMETRY_PROBE_SALT)`` (the
        health-detector isolation pattern), so telemetry never perturbs
        the sampling stream: a telemetry-on run's chains and trace are
        bitwise identical to a telemetry-off run's."""
        if n_total is None:
            n_total = n_chains
        fed = (federation if federation is not None
               and not federation.engine_identity else None)
        chaos = chaos if chaos is not None and chaos.active else None
        rec = recovery
        tel = telemetry
        cache_key = (num_rounds, n_chains, n_total, reassign, collect,
                     collect_every, layout, fed, rec, chaos, stream, tel)
        if cache_key in self._executors:
            return self._executors[cache_key]

        cfg = self.cfg
        S = cfg.num_shards
        per = n_total // self.mesh.shape["data"]
        n_pad = n_total - n_chains
        if reassign == "categorical" and cfg.method != "sgld":
            # built lazily: at streamed-client scale probs() is None
            # (implicit uniform) and categorical reassignment is refused
            # before ever reaching an executor
            log_probs = jnp.log(jnp.asarray(self.scheme.probs_array()))
        bank_kind = self.bank.kind if self.bank is not None else None

        # FA-LD noise calibration: averaging C clients shrinks the
        # injected-noise variance by C, so each client samples at
        # temperature * C and the AVERAGED iterate targets cfg.temperature
        # (Deng et al. 2021's sqrt(N/p_c) client noise, uniform weights).
        agg = self.aggregation == "fald"
        cfg_dyn = (dataclasses.replace(
            cfg, temperature=cfg.temperature * n_chains) if agg else cfg)

        grad_vmap = make_masked_grad_vmap(
            jax.grad(self.log_lik_fn), per=per, n_chains=n_chains,
            d_size=self.mesh.shape["data"]) if n_pad else None
        if layout is not None:
            round_fn = make_packed_round_fn(
                self.log_lik_fn, cfg_dyn, self.scheme, self.minibatch,
                bank_kind, layout, collect=collect, dynamics=self.dynamics,
                sghmc=self.sghmc, grad_vmap=grad_vmap)
        elif self.use_kernel:
            round_fn = make_chain_round_fn(
                self.log_lik_fn, cfg_dyn, self.scheme, self.minibatch,
                bank_kind, collect=collect, dynamics=self.dynamics,
                sghmc=self.sghmc, grad_vmap=grad_vmap)
        else:
            step_fn = self.step_fn if not agg else make_step_fn(
                self.log_lik_fn, cfg_dyn, self.scheme, self.bank,
                use_kernel=False)
            one_chain = make_round_fn(
                self.log_lik_fn, cfg_dyn, self.scheme, step_fn,
                self.minibatch, collect=collect,
                collect_state=((lambda s: s[0])
                               if self.dynamics == "sghmc" else None))

            def round_fn(thetas, keys, sids, shard_data, bank_rt,
                         sp_rt=None):
                return jax.vmap(
                    one_chain, in_axes=(0, 0, 0, None, None, None))(
                    thetas, keys, sids, shard_data, bank_rt, sp_rt)

        def pad_tail(arr):
            """Extend a (n_chains, ...) per-chain operand to n_total rows
            with zeros for the dummy pad chains (concatenate, not `pad`:
            the scan bodies carry a no-pad-primitive jaxpr guarantee)."""
            if n_pad == 0:
                return arr
            tail = jnp.zeros((n_pad,) + arr.shape[1:], arr.dtype)
            return jnp.concatenate([arr, tail])

        hmc = self.dynamics == "sghmc"

        # federation lowering: the schedule/compression hooks operate on
        # the canonical per-chain (theta, momentum) view of whatever state
        # form the executor carries, and write back through set_view
        # (repacking the packed buffers — lossless: the pallas update is
        # elementwise, so buffer pad lanes never feed real lanes).
        if layout is not None:
            @jax.named_scope("fsgld.pack")
            def get_view(st):
                if hmc:
                    return st[2], layout.unpack(st[1])
                return st[1], None

            @jax.named_scope("fsgld.pack")
            def set_view(st, th, r):
                if hmc:
                    return (layout.pack(th), layout.pack(r), th)
                return (layout.pack(th), th)
        else:
            def get_view(st):
                return (st[0], st[1]) if hmc else (st, None)

            def set_view(st, th, r):
                return (th, r) if hmc else th

        # FA-LD takes the federated round body even with no Federation
        # spec (identity schedule, exact exchange): the averaging is a
        # communication-round feature, and sharing the fed body keeps ONE
        # RNG stream layout for the rivals/fald oracle to mirror.
        use_fed = fed is not None or agg
        if use_fed:
            from repro.fed import schedule as fsched
            from repro.fed.compress import (Compression, make_compressor,
                                            make_flattener)
            if fed is not None:
                sched, comp = fed.schedule, fed.compression
            else:
                sched, comp = fsched.CommSchedule(), Compression()
            use_part = sched.participation < 1.0
            use_strag = sched.straggler_prob > 0.0
            use_comp = not comp.identity
            # ELF leg selection: primal compresses client->server uploads
            # (today's path), dual compresses the server->client
            # broadcast with its own EF residual riding the carry.
            use_primal, use_dual = comp.use_primal, comp.use_dual
            use_exch = use_comp or agg

        # the identity fast path keeps its round-index-free scan (xs=None)
        # — same jaxpr as ever; any of these features needs the absolute
        # round index threaded through the scan instead.
        use_r = use_fed or chaos is not None or rec is not None
        if rec is not None and rec.use_detector:
            probe_sample = _make_batch_sampler(cfg, self.scheme,
                                               self.minibatch)
        log_lik = self.log_lik_fn

        # telemetry lowering: every metric is either closed-form over
        # values the round body already carries, or a PROBE evaluation on
        # a fold_in-salted key — nothing consumes the sampling stream,
        # and none of it needs the absolute round index (use_r unchanged:
        # the identity fast path keeps its xs=None scan with telemetry on)
        if tel is not None:
            scheme = self.scheme
            minibatch = self.minibatch
            if tel.probe:
                tel_sample = _make_batch_sampler(cfg, scheme, minibatch)
            h = cfg_dyn.step_size
            if self.dynamics == "sghmc":
                # naive-Euler SGHMC noise term: sqrt(2 a tau) sqrt(h) xi
                # (core/sghmc.py)
                tel_noise = float(np.sqrt(
                    2.0 * self.sghmc.friction * self.sghmc.temperature
                    * h))
            else:
                tel_noise = float(np.sqrt(h * cfg_dyn.temperature))

        def block(key, chains, shard_data, bank_rt, r0, fedc, hw0,
                  stream_ids=None, sp_rt=None):
            # streamed client axis: shard_data/bank_rt hold only the
            # RESIDENT window's K client rows; ``stream_ids`` is the
            # sorted (K,) global-id vector and ``sp_rt`` the resident
            # (sizes_i32, sizes_f32, probs_f32) metadata rows. Carried
            # sids stay GLOBAL (so fed carries compare bitwise across
            # window boundaries); each round remaps them to
            # resident-local once, by a compare-and-sum rank — NOT
            # searchsorted, which lowers with an inner scan and would
            # break the one-scan jaxpr guarantee.
            if stream is not None:
                def to_local(s):
                    loc = jnp.sum(stream_ids[None, :] < s[:, None],
                                  axis=1)
                    # pad chains may hold ids outside the window (their
                    # trajectories are discarded); clamp keeps their
                    # gathers in range without a pad primitive
                    return jnp.minimum(loc, stream - 1).astype(jnp.int32)
            else:
                to_local = lambda s: s  # noqa: E731
            if layout is not None:
                with jax.named_scope("fsgld.conducive"):
                    rt_bank = pack_bank(
                        layout, bank_rt if cfg.method == "fsgld" else None)
                with jax.named_scope("fsgld.pack"):
                    if hmc:
                        th_c, r_c = chains
                        # the momenta ride a SECOND chain-major buffer
                        # over the SAME packed layout (their own seed
                        # stream is the per-step noise draw routed by
                        # seed BlockSpecs)
                        state = (layout.pack(th_c), layout.pack(r_c),
                                 th_c)
                    else:
                        state = (layout.pack(chains), chains)
            else:
                rt_bank = bank_rt
                state = chains
            blk = jax.lax.axis_index("data") * per

            # ---- telemetry metric rows --------------------------------
            if tel is not None:
                th_tpl = chains[0] if hmc else chains
                # flat parameter count — the wire-byte estimates' dim
                tel_dim = sum(int(np.prod(l.shape[1:]))
                              for l in jax.tree.leaves(th_tpl))
                tel_sizes_rt = None if sp_rt is None else sp_rt[0]

            def tel_sq(tree):
                """Per-chain sum of squares over all leaves, (per,) f32."""
                s = None
                for l in jax.tree.leaves(tree):
                    v = jnp.sum(jnp.square(
                        l.astype(jnp.float32)).reshape((per, -1)), axis=1)
                    s = v if s is None else s + v
                return s

            def tel_metrics(k_run, state, pre_th, sids, exch_f, nbytes,
                            hw):
                """One round's metric rows, each (per,) fp32 — computed
                AFTER the round's masking (straggler/health), so frozen
                chains show zero drift and quarantined ones their word.
                ``sids`` are resident-local; ``exch_f``/``nbytes`` come
                from the caller (fed bodies gate them on the exchange
                mask, the identity body exchanges every round)."""
                th, _ = get_view(state)
                th_sq = tel_sq(th)
                m = {"theta_norm": jnp.sqrt(th_sq),
                     "drift_norm": jnp.sqrt(tel_sq(jax.tree.map(
                         lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), th, pre_th))),
                     "noise_scale": jnp.full((per,), tel_noise,
                                             jnp.float32)}
                if bank_rt is not None and cfg.method == "fsgld":
                    _, f_s = chain_scales(cfg, scheme, sids, minibatch,
                                          sp_rt)
                    from repro.core.conducive import \
                        conducive_gradient_from_bank
                    g_c = jax.vmap(
                        lambda t, s, f: conducive_gradient_from_bank(
                            t, bank_rt, s, f, cfg.alpha))(th, sids, f_s)
                    m["conducive_norm"] = jnp.sqrt(tel_sq(g_c))
                else:
                    m["conducive_norm"] = jnp.zeros((per,), jnp.float32)
                m["participation"] = exch_f
                m["bytes_per_round"] = nbytes
                m["health_word"] = (hw[0].astype(jnp.float32)
                                    if rec is not None
                                    else jnp.zeros((per,), jnp.float32))
                if tel.probe:
                    kp = jax.lax.dynamic_slice_in_dim(
                        pad_tail(jax.random.split(jax.random.fold_in(
                            k_run, TELEMETRY_PROBE_SALT), n_chains)),
                        blk, per)

                    def probe_one(t, k, s):
                        batch = tel_sample(k, s, shard_data, tel_sizes_rt)
                        return jax.value_and_grad(log_lik)(t, batch)

                    lp, g_p = jax.vmap(probe_one)(th, kp, sids)
                    m["grad_norm"] = jnp.sqrt(tel_sq(g_p))
                    m["log_post"] = lp.astype(jnp.float32) \
                        - 0.5 * cfg.prior_precision * th_sq
                return {n: m[n] for n in tel.names}

            def propose_sids(k_assign):
                """This round's chain->client draw — the same derivation
                on the identity and scheduled paths (schedules only gate
                whether a chain TAKES its draw)."""
                if cfg.method == "sgld":
                    return jnp.zeros((per,), jnp.int32)
                if reassign == "categorical":     # paper Algorithm 1
                    return jax.lax.dynamic_slice_in_dim(
                        pad_tail(jax.random.categorical(
                            k_assign,
                            log_probs[None].repeat(n_chains, 0))),
                        blk, per)
                # SPMD variant (DESIGN 4.1); block-cyclic when C > S
                return _perm_sids_slice(k_assign, S, blk, per, n_total)

            # ---- fault lowering (chaos + health) -----------------------
            gid = blk + jnp.arange(per)          # global chain ids
            is_real = gid < n_chains

            def poison_state(r, state):
                """chaos: NaN the chosen chains' post-round theta at the
                chosen absolute rounds — per-chain, so every other chain
                is bitwise untouched."""
                if chaos is None or not chaos.poisons_state:
                    return state
                m = jnp.isin(r, jnp.asarray(chaos.nan_rounds)) & \
                    jnp.isin(gid, jnp.asarray(chaos.nan_chains))
                th, mom = get_view(state)
                th = jax.tree.map(
                    lambda l: jnp.where(
                        m.reshape((per,) + (1,) * (l.ndim - 1)),
                        jnp.nan, l)
                    if jnp.issubdtype(l.dtype, jnp.inexact) else l, th)
                return set_view(state, th, mom)

            def finite_chains(tree):
                ok = None
                for l in jax.tree.leaves(tree):
                    f = jnp.all(jnp.isfinite(l.reshape((per, -1))), axis=1)
                    ok = f if ok is None else ok & f
                return ok

            def check_health(r, k_run, sids, pre_th, pre_mom, state,
                             trace, hw):
                """Per-chain health word update + recovery masking, run
                once per ROUND after the local updates (no extra
                launches). Every write is a per-chain where(): a chain
                that never trips keeps bit-identical state/trace, and a
                tripped chain never reaches into its neighbours.

                The divergence reference is a nearest-rank QUANTILE over
                the chain's last ``rec.window`` probes (the ring rides
                the health carry, -inf padded), not a running max: the
                quantile is robust to single lucky probes, so the
                threshold can sit a few probe-IQRs under the recent
                healthy plateau and a SLOW divergence trips early. While
                the window is -inf dominated (warm-up, post-respawn) the
                reference is -inf and nothing trips — so a fault-free
                run stays bitwise identical with health on or off."""
                word, lp_win = hw
                th, mom = get_view(state)
                bad_new = ~finite_chains(th)
                if hmc and rec.check_momentum:
                    bad_new = bad_new | ~finite_chains(mom)
                lp = None
                if rec.use_detector:
                    # probe key from fold_in: the detector consumes
                    # NOTHING from the sampling stream, so enabling it
                    # cannot perturb the chains it watches
                    kp = jax.lax.dynamic_slice_in_dim(
                        pad_tail(jax.random.split(jax.random.fold_in(
                            k_run, HEALTH_PROBE_SALT), n_chains)),
                        blk, per)
                    sq = None
                    for l in jax.tree.leaves(th):
                        s = jnp.sum(jnp.square(
                            l.astype(jnp.float32)).reshape((per, -1)), 1)
                        sq = s if sq is None else sq + s
                    lp = jax.vmap(
                        lambda t, k, s: log_lik(
                            t, probe_sample(k, s, shard_data)))(
                        th, kp, sids)
                    lp = lp.astype(jnp.float32) \
                        - 0.5 * cfg.prior_precision * sq
                    # nearest-rank quantile, NOT jnp.quantile: lerp
                    # between -inf (warm-up padding) and a finite probe
                    # would be NaN
                    q_idx = min(rec.window - 1,
                                int(rec.quantile * (rec.window - 1)))
                    lp_ref = jnp.sort(lp_win, axis=1)[:, q_idx]
                    bad_new = bad_new | ~jnp.isfinite(lp) | \
                        (lp < lp_ref - rec.divergence_threshold)
                    pushed = jnp.concatenate(
                        [lp_win[:, 1:], lp[:, None]], axis=1)
                if rec.policy == "quarantine":
                    bad = (word != 0) | bad_new
                    word = jnp.where((word == 0) & bad_new,
                                     r + 1, word)

                    def fix(new, old):
                        return jnp.where(
                            bad.reshape((per,) + (1,) * (new.ndim - 1)),
                            old, new)

                    if lp is not None:
                        # quarantined chains' windows freeze with them
                        lp_win = jnp.where(
                            (bad | ~jnp.isfinite(lp))[:, None],
                            lp_win, pushed)
                    repl = bad
                else:                                       # respawn
                    word = word + bad_new.astype(word.dtype)
                    healthy = (~bad_new) & is_real
                    donor = jnp.argmax(healthy)
                    any_h = jnp.any(healthy)

                    def fix(new, old):
                        # re-seed from the block's first healthy real
                        # chain; freeze in place when the whole block
                        # diverged at once
                        cand = jnp.where(any_h, new[donor][None], old)
                        return jnp.where(
                            bad_new.reshape(
                                (per,) + (1,) * (new.ndim - 1)),
                            cand, new)

                    if lp is not None:
                        # respawned chains restart an empty window (their
                        # donor's plateau is not theirs)
                        lp_win = jnp.where(
                            ((~bad_new) & jnp.isfinite(lp))[:, None],
                            pushed, lp_win)
                        lp_win = jnp.where(bad_new[:, None], -jnp.inf,
                                           lp_win)
                    repl = bad_new
                th = jax.tree.map(fix, th, pre_th)
                mom = jax.tree.map(fix, mom, pre_mom) if hmc else None
                if collect:
                    trace = jax.tree.map(
                        lambda t, f: jnp.where(
                            repl.reshape((per, 1) + (1,) * (t.ndim - 2)),
                            f[:, None], t),
                        trace, th)
                return set_view(state, th, mom), trace, (word, lp_win)

            def round_body(carry, r):
                key, state, hw = carry
                key, k_assign, k_run = jax.random.split(key, 3)
                sids = propose_sids(k_assign)
                run_sids = to_local(sids)
                if rec is not None or tel is not None:
                    pre_th, pre_mom = get_view(state)
                keys_blk = jax.lax.dynamic_slice_in_dim(
                    pad_tail(jax.random.split(k_run, n_chains)), blk, per)
                state, trace = round_fn(state, keys_blk, run_sids,
                                        shard_data, rt_bank, sp_rt)
                state = poison_state(r, state)
                if rec is not None:
                    state, trace, hw = check_health(
                        r, k_run, run_sids, pre_th, pre_mom, state, trace,
                        hw)
                y = (jax.tree.map(lambda t: t[:, ::collect_every], trace)
                     if collect else None)
                if tel is not None:
                    # the identity path exchanges (reassigns) every
                    # round: participation 1, exact wire bytes both legs
                    y = (y, tel_metrics(
                        k_run, state, pre_th, run_sids,
                        jnp.ones((per,), jnp.float32),
                        jnp.full((per,), 8.0 * tel_dim, jnp.float32), hw))
                return (key, state, hw), y

            def fed_round_body(carry, r):
                key, state, sids, cst, hw = carry
                key, k_assign, k_run, k_fed = jax.random.split(key, 4)
                new_sids = propose_sids(k_assign).astype(jnp.int32)
                comm = fsched.comm_mask(sched, r)
                if use_part:
                    exch = comm & jax.lax.dynamic_slice_in_dim(
                        pad_tail(fsched.participation_mask(
                            sched, jax.random.fold_in(k_fed, 0), r,
                            n_chains)), blk, per)
                else:
                    exch = jnp.broadcast_to(comm, (per,))
                if rec is not None and rec.policy == "quarantine":
                    # quarantined chains are masked OUT of the exchange:
                    # they neither reassign nor push/pull the server view
                    # (their ref/err rows freeze with them)
                    exch = exch & (hw[0] == 0)
                sids = jnp.where(exch, new_sids, sids)
                if use_exch:
                    # exchange at the round boundary: primal leg
                    # (compressed client->server upload), optional FA-LD
                    # server averaging over the participating chains,
                    # optional dual leg (compressed server->client
                    # broadcast) — the exchanging chains continue from
                    # the server's view; everyone else's state is
                    # untouched — bitwise: non-exchanging chains' leaves
                    # are never written (no fp32 flatten round-trip), and
                    # the whole pipeline (flatten, top_k/quantize,
                    # average, repack) runs under a lax.cond so delayed
                    # schedules skip it entirely on non-communication
                    # rounds (comm is a replicated scalar of r, so the
                    # cond is SPMD-safe).
                    def do_exchange(op):
                        state, cst_in = op
                        th, mom = get_view(state)
                        flat = flatten(th)
                        poison = None
                        if chaos is not None and chaos.poisons_payload:
                            # corrupted wire payload: the delta the server
                            # applies goes NaN for the chosen chains at
                            # the chosen rounds — their server view (and
                            # the state they continue from) diverges
                            poison = jnp.isin(r, jnp.asarray(
                                chaos.payload_nan_rounds)) & jnp.isin(
                                gid, jnp.asarray(chaos.payload_nan_chains))
                        if use_primal:
                            ref, err = cst_in[0], cst_in[1]
                            upd = flat - ref + err
                            dhat = compress(
                                upd, jax.random.fold_in(k_fed, 1))
                            if poison is not None:
                                dhat = jnp.where(poison[:, None],
                                                 jnp.nan, dhat)
                            # m_flat: the server's per-chain model after
                            # the upload leg
                            m_flat = ref + dhat
                            err_new = (upd - dhat if comp.error_feedback
                                       else jnp.zeros_like(upd))
                        else:
                            ref = cst_in[0] if cst_in is not None else None
                            m_flat = flat
                            if poison is not None:
                                m_flat = jnp.where(poison[:, None],
                                                   jnp.nan, m_flat)
                        if agg:
                            # FA-LD server step: average the exchanging
                            # REAL chains' models (masked psum over the
                            # chain axis — every data group sees the same
                            # average; pad chains never contribute).
                            w = exch & is_real
                            cnt = jax.lax.psum(
                                jnp.sum(w.astype(jnp.float32)), "data")
                            tot = jax.lax.psum(jnp.sum(
                                jnp.where(w[:, None], m_flat, 0.0),
                                axis=0), "data")
                            avg = tot / jnp.maximum(cnt, 1.0)
                            m_flat = jnp.where(w[:, None], avg[None],
                                               m_flat)
                        if use_dual:
                            # dual leg: the broadcast is a compressed
                            # delta against the SHARED reference (what
                            # both sides last agreed on), with its own
                            # error-feedback residual
                            derr = cst_in[2]
                            dupd = m_flat - ref + derr
                            dd = compress(
                                dupd, jax.random.fold_in(k_fed, 3))
                            v_new = ref + dd
                            derr_new = (dupd - dd if comp.error_feedback
                                        else jnp.zeros_like(dupd))
                        else:
                            # exact broadcast: the client receives the
                            # server model itself (NOT ref + (m - ref):
                            # the fp round-trip would break bitwise
                            # parity of primal-only runs)
                            v_new = m_flat
                        cst_out = cst_in
                        if use_comp:
                            mm = exch[:, None]
                            ref_o = jnp.where(mm, v_new, cst_in[0])
                            err_o = (jnp.where(mm, err_new, cst_in[1])
                                     if use_primal else cst_in[1])
                            if use_dual:
                                cst_out = (ref_o, err_o,
                                           jnp.where(mm, derr_new,
                                                     cst_in[2]))
                            else:
                                cst_out = (ref_o, err_o)
                        th_srv = unflatten(v_new)  # the clients' new view
                        th = jax.tree.map(
                            lambda srv, old: jnp.where(
                                exch.reshape((per,)
                                             + (1,) * (old.ndim - 1)),
                                srv, old),
                            th_srv, th)
                        return set_view(state, th, mom), cst_out

                    state, cst = jax.lax.cond(
                        comm, do_exchange, lambda op: op, (state, cst))
                run_sids = to_local(sids)
                if use_strag or rec is not None or tel is not None:
                    pre_th, pre_mom = get_view(state)
                keys_blk = jax.lax.dynamic_slice_in_dim(
                    pad_tail(jax.random.split(k_run, n_chains)), blk, per)
                state, trace = round_fn(state, keys_blk, run_sids,
                                        shard_data, rt_bank, sp_rt)
                if use_strag:
                    # dropped updates: straggler chains' state does not
                    # advance and their trace repeats the frozen position
                    strag = jax.lax.dynamic_slice_in_dim(
                        pad_tail(fsched.straggler_mask(
                            sched, jax.random.fold_in(k_fed, 2),
                            n_chains)), blk, per)

                    def keep(new, old):
                        mm = strag.reshape((per,) + (1,) * (new.ndim - 1))
                        return jnp.where(mm, old, new)

                    th, mom = get_view(state)
                    th = jax.tree.map(keep, th, pre_th)
                    mom = (jax.tree.map(keep, mom, pre_mom) if hmc
                           else None)
                    state = set_view(state, th, mom)
                    if collect:
                        trace = jax.tree.map(
                            lambda t, p: jnp.where(
                                strag.reshape((per,) + (1,) * (t.ndim - 1)),
                                p[:, None], t),
                            trace, pre_th)
                state = poison_state(r, state)
                if rec is not None:
                    state, trace, hw = check_health(
                        r, k_run, run_sids, pre_th, pre_mom, state, trace,
                        hw)
                y = (jax.tree.map(lambda t: t[:, ::collect_every], trace)
                     if collect else None)
                if tel is not None:
                    # exch already folds in the comm schedule, the
                    # participation draw, and quarantine masking — the
                    # chains that actually moved bytes this round
                    exch_f = exch.astype(jnp.float32)
                    y = (y, tel_metrics(
                        k_run, state, pre_th, run_sids, exch_f,
                        exch_f * float(comp.bytes_per_round(tel_dim)),
                        hw))
                return (key, state, sids, cst, hw), y

            rounds = (r0 + jnp.arange(num_rounds)) if use_r else None
            if not use_fed:
                (key, state, hw0), traces = jax.lax.scan(
                    round_body, (key, state, hw0), rounds,
                    length=num_rounds)
            else:
                th0, _ = get_view(state)
                flatten, unflatten, dim = make_flattener(th0)
                if use_comp:
                    compress = make_compressor(comp, dim)
                (key, state, f_sids, f_cst, hw0), traces = jax.lax.scan(
                    fed_round_body,
                    (key, state, fedc[0], fedc[1], hw0), rounds)
                fedc = (f_sids, f_cst)
            tmet = None
            if tel is not None:
                # scan stacked each (per,) metric row to (R, per);
                # chain-major (per, R) matches the trace's output layout
                traces, tmet = traces
                tmet = {k: jnp.swapaxes(v, 0, 1)
                        for k, v in tmet.items()}
            if layout is not None:
                with jax.named_scope("fsgld.pack"):
                    chains_out = ((state[2], layout.unpack(state[1]))
                                  if hmc else state[1])
            else:
                chains_out = state
            if collect:
                # (R, C_blk, T/ce, ...) -> (C_blk, R * T/ce, ...): same
                # round-major order the legacy host-side concatenate built.
                traces = jax.tree.map(
                    lambda t: jnp.swapaxes(t, 0, 1).reshape(
                        (t.shape[1], num_rounds * t.shape[2])
                        + t.shape[3:]),
                    traces)
            if tel is not None:
                return chains_out, traces, key, fedc, hw0, tmet
            return chains_out, traces, key, fedc, hw0

        cspec = self._chain_spec()
        fc_spec = fed_carry_spec() if use_fed else None
        h_spec = cspec if rec is not None else None
        in_specs = (P(), cspec, P(), P(), P(), fc_spec, h_spec)
        if stream is not None:
            # resident window ids + metadata rows: replicated, like the
            # shard stack they index into
            w_spec = stream_window_spec()
            in_specs = in_specs + (w_spec, (w_spec,) * 3)
        out_specs = (cspec, cspec if collect else None, P(), fc_spec,
                     h_spec)
        if tel is not None:
            # metric rows are chain-major (C, R): sharded like the trace
            out_specs = out_specs + (cspec,)
        mapped = jax.shard_map(
            block, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False)
        fn = jax.jit(mapped, donate_argnums=(1,))
        self._executors[cache_key] = fn
        return fn

    def _permute_sids(self, k_assign: jax.Array, n_chains: int):
        """Host-callable wrapper around ``_perm_sids_slice`` (the same
        helper the scanned round body uses) for one whole reassignment:
        returns the (n_chains,) collision-free sids for this round
        (block-cyclic when n_chains > S)."""
        S = self.cfg.num_shards
        per = n_chains // self.mesh.shape["data"]

        def block(k):
            return _perm_sids_slice(
                k[0], S, jax.lax.axis_index("data") * per, per,
                n_total=n_chains)

        return jax.shard_map(
            block, mesh=self.mesh, in_specs=(P(),),
            out_specs=P("data"), check_vma=False)(k_assign[None])

    # -- server-side loop --------------------------------------------------

    @_traced_run
    def run(self, key: jax.Array, theta0: PyTree, num_rounds: int, *,
            n_chains: int = 1, reassign: str = "categorical",
            collect_every: int = 1, refresh_every: Optional[int] = None,
            collect: bool = True, stacked: bool = False,
            federation=None, recovery=None, chaos=None,
            snapshot_every: Optional[int] = None,
            snapshot_path: Optional[str] = None, resume: bool = False,
            stream=None, telemetry=None):
        """Same contract (and same RNG stream) as the legacy
        ``FederatedSampler.run``: returns stacked samples with leading axes
        (n_chains, num_rounds * T_local / collect_every, ...), or the final
        chain states when ``collect=False`` (large-model mode — the trace
        of a billion-parameter posterior does not fit anywhere).

        All rounds execute as ONE jitted scan (one host dispatch per run;
        with ``refresh_every``, one per refresh segment — the refresh
        itself is a host-side surrogate re-fit between segments).

        ``stacked=True`` treats ``theta0`` as per-chain states with a
        leading (n_chains, ...) axis instead of one state to broadcast —
        the entry point for round-at-a-time drivers that carry chain
        state across calls (the retired launch/steps.py federated round).

        ``dynamics='sghmc'`` engines accept the plain parameter pytree
        and pair it with zero momenta internally (the momenta are part of
        the mailed chain state); ``collect=False`` returns the
        (theta, momentum) pairs.

        ``federation`` (a ``repro.fed.Federation`` spec, or None) applies
        the scenario's communication schedule and round-boundary payload
        compression inside the scanned round body. Partitioning is NOT
        the engine's job — ``shard_data`` must already be split (the
        ``repro.api`` facade applies ``Federation.partition``). An
        engine-identity spec is bit-identical to ``federation=None``.

        ``reassign='permutation'`` supports n_chains > num_shards via
        BLOCK-CYCLIC client visiting: the round's permutation is tiled so
        chain c sits at client perm[c % S] — every client hosts
        floor/ceil(C/S) chains.

        Fault tolerance: ``recovery`` (a ``repro.core.health.Recovery``)
        turns on the in-scan health check and makes the call return
        ``(result, RunHealth)`` — the health word per REAL chain (0 =
        never faulted). ``chaos`` injects a static fault plan (testing).
        ``snapshot_every=k, snapshot_path=dir`` atomically checkpoints
        the full scan carry every k rounds; ``resume=True`` continues
        from the newest valid snapshot in ``snapshot_path`` (falling
        back to a fresh run when none exists) with traces bitwise
        identical to an uninterrupted run.

        ``telemetry`` (a ``repro.obs.Telemetry``) lowers per-round
        per-chain metric rows into the scanned round body and APPENDS a
        ``repro.obs.MetricsFrame`` to the return value — the result
        tuple is built in order (result[, health][, frame]).
        ``telemetry.log_every`` segments the run (bitwise losslessly,
        via the same carry threading snapshots use) and emits an
        ``engine.progress`` trace event per segment. The frame covers
        the rounds executed by THIS call (a resumed run's frame starts
        at its resume point). Telemetry-off runs are bitwise identical
        to telemetry-on runs — and to runs on code that predates the
        telemetry layer.

        Host spans (``repro.obs.trace``): ``engine.run`` around the call
        (``executor_built``: executors built, i.e. cache misses), and in
        it ``engine.layout`` (resolving the packed layout; ``block_rows``
        and ``grid_steps``: the kernel's block height and grid length),
        ``engine.stage`` (copying and placing the chain state, setting
        up the carries; ``bytes``: the chain state staged) and one
        ``engine.segment`` per executor dispatch.
        """
        d_size = self.mesh.shape["data"]
        n_total = n_chains + (-n_chains) % d_size
        if self.cfg.method != "sgld" and reassign not in ("categorical",
                                                          "permutation"):
            raise ValueError(reassign)
        fed = (federation if federation is not None
               and not federation.engine_identity else None)
        chaos = chaos if chaos is not None and chaos.active else None
        if stream is not None:
            # streamed client axis: only the planner-replayable,
            # window-local features compose. Everything below needs
            # either all clients resident or an un-plannable RNG stream —
            # refuse loudly rather than stream wrong results.
            if self.cfg.method == "sgld":
                raise NotImplementedError(
                    "stream= does not compose with method='sgld': pooled "
                    "sampling draws from the virtual concatenation of ALL "
                    "clients and needs them resident")
            if reassign != "permutation":
                raise NotImplementedError(
                    f"stream= requires reassign='permutation' (got "
                    f"{reassign!r}): the resident-set planner replays the "
                    "collision-free permutation stream; categorical "
                    "draws are not plannable ahead of the scan")
            if refresh_every:
                raise NotImplementedError(
                    "stream= does not compose with refresh_every: the "
                    "surrogate re-fit is a pass over ALL clients' data")
            if snapshot_every or resume:
                raise NotImplementedError(
                    "stream= does not compose with snapshots/resume yet: "
                    "the window plan is not part of the snapshot payload")
            if recovery is not None or chaos is not None:
                raise NotImplementedError(
                    "stream= does not compose with recovery/chaos yet")
            if telemetry is not None:
                raise NotImplementedError(
                    "stream= does not compose with telemetry= yet: the "
                    "metric rows are not part of the window plan (the "
                    "host-side prefetch/overlap SPANS still fire — see "
                    "repro.obs.trace)")
            if stream.resident > self.cfg.num_shards:
                raise ValueError(
                    f"Stream(resident={stream.resident}) exceeds the "
                    f"client count ({self.cfg.num_shards}); resident is "
                    "the ON-DEVICE subset size and must be <= the number "
                    "of clients — lower resident, or raise the client "
                    "count")
        if fed is not None and refresh_every and self.cfg.method == "fsgld":
            raise NotImplementedError(
                "adaptive refresh does not compose with a non-identity "
                "communication schedule/compression yet: the carried "
                "sids / error-feedback state would reset at every "
                "refresh segment boundary")
        if (snapshot_every or resume) and not snapshot_path:
            raise ValueError(
                "snapshot_every/resume need a snapshot_path directory")
        if telemetry is not None and telemetry.log_every and \
                (snapshot_every or refresh_every):
            raise NotImplementedError(
                "Telemetry.log_every does not compose with "
                "snapshot_every/refresh_every: pick ONE segmentation "
                "driver (progress events already fire at snapshot/"
                "refresh segment boundaries)")
        if snapshot_path and refresh_every:
            raise NotImplementedError(
                "snapshots do not compose with adaptive refresh yet: the "
                "refreshed surrogate bank is not part of the snapshot "
                "payload")
        if self.dynamics == "sghmc":
            if refresh_every:
                raise NotImplementedError(
                    "adaptive refresh is not wired for sghmc dynamics")
            from repro.core.sghmc import init_momentum
            # zero momenta in theta0's structure — per-chain when stacked,
            # broadcast with theta0 otherwise (same expression either way)
            theta0 = (theta0, init_momentum(theta0))
        # the packed layout is built from the PARAMETER pytree alone: the
        # sghmc momenta share its structure (and hence its packed layout)
        ex_theta = theta0[0] if self.dynamics == "sghmc" else theta0
        with obs_trace.span("engine.layout") as lay:
            layout = self._layout_for(
                jax.tree.map(lambda t: t[0], ex_theta) if stacked
                else ex_theta)
            if layout is not None:
                lay.set(block_rows=layout.block_rows,
                        grid_steps=n_total * sum(layout.leaf_blocks))
        with obs_trace.span("engine.stage") as stage:
            cshard = NamedSharding(self.mesh, self._chain_spec())
            if stacked:
                assert jax.tree.leaves(theta0)[0].shape[0] == n_chains, \
                    (jax.tree.leaves(theta0)[0].shape, n_chains)
                # pad chains replicate chain 0's state (their updates are
                # computed and discarded — any finite state works). The
                # unpadded leaves are COPIED: the executor donates its chain
                # operand, and donating the caller's own arrays would delete
                # them under a round-at-a-time driver.
                chains = jax.tree.map(
                    lambda t: jnp.concatenate(
                        [t, jnp.broadcast_to(t[:1], (n_total - n_chains,)
                                             + t.shape[1:])])
                    if n_total > n_chains else t.copy(), theta0)
            else:
                chains = jax.tree.map(
                    lambda t: jnp.broadcast_to(
                        t[None], (n_total,) + t.shape).copy(), theta0)
            chains = jax.device_put(
                chains, jax.tree.map(lambda _: cshard, chains))
            stage.set(bytes=sum(int(t.nbytes)
                                for t in jax.tree.leaves(chains)))
            bank_rt = self.bank
            take = (lambda t: t[:n_chains]) if n_total > n_chains \
                else (lambda t: t)

            # in-scan carries threaded through the executor I/O (so segment
            # boundaries — snapshots, resume — never reset them)
            hw = None
            if recovery is not None:
                # the divergence probe window rides the carry as a (C, W)
                # ring, -inf padded (= empty)
                hw = (jnp.zeros((n_total,), jnp.int32),
                      jnp.full((n_total, recovery.window), -jnp.inf,
                               jnp.float32))
            fedc = None
            # FA-LD routes through the federated round body even with no
            # Federation spec (see _executor) — it needs the fed carry
            use_fed = fed is not None or self.aggregation == "fald"
            if use_fed:
                comp0 = fed.compression if fed is not None else None
                cst0 = None
                if comp0 is not None and not comp0.identity:
                    from repro.fed.compress import make_flattener
                    th_part = chains[0] if self.dynamics == "sghmc" else chains
                    flatten, _, _ = make_flattener(th_part)
                    # copy: flatten() can alias the (donated) chains buffer
                    ref0 = jnp.array(flatten(th_part), copy=True)
                    cst0 = (ref0, jnp.zeros_like(ref0))
                    if comp0.use_dual:
                        # dual-leg error feedback rides a third carry slot
                        cst0 = cst0 + (jnp.zeros_like(ref0),)
                fedc = (jnp.zeros((n_total,), jnp.int32), cst0)

        if stream is not None:
            return self._run_streamed(
                key, chains, num_rounds, stream=stream,
                n_chains=n_chains, n_total=n_total, reassign=reassign,
                collect_every=collect_every, collect=collect,
                layout=layout, federation=fed, fedc=fedc, take=take)

        typed_key = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)

        def snap_payload(trace_now):
            """The FULL scan carry, real-chain rows only (mesh padding is
            reconstructed on load): everything a resumed run needs to be
            bitwise identical to an uninterrupted one."""
            p = {"chains": jax.tree.map(take, chains),
                 "key": jax.random.key_data(key) if typed_key else key}
            if fedc is not None:
                p["sids"] = fedc[0][:n_chains]
                if fedc[1] is not None:
                    p["ref"] = fedc[1][0][:n_chains]
                    p["err"] = fedc[1][1][:n_chains]
                    if len(fedc[1]) == 3:
                        p["derr"] = fedc[1][2][:n_chains]
            if hw is not None:
                p["word"] = hw[0][:n_chains]
                p["lp_ref"] = hw[1][:n_chains]
            if collect:
                p["trace"] = trace_now
            return p

        def repad(t, fill=None):
            t = jnp.asarray(t)
            if n_total == n_chains:
                return t
            tail = (jnp.broadcast_to(t[:1], (n_total - n_chains,)
                                     + t.shape[1:])
                    if fill is None else
                    jnp.full((n_total - n_chains,) + t.shape[1:], fill,
                             t.dtype))
            return jnp.concatenate([t, tail])

        out = []
        r_start = 0
        if resume:
            from repro.checkpoint.snapshot import latest_snapshot
            th_like = (jax.tree.map(take, chains)[0]
                       if self.dynamics == "sghmc"
                       else jax.tree.map(take, chains))
            payload, r_start = latest_snapshot(snapshot_path,
                                               snap_payload(th_like))
            if payload is None:
                r_start = 0       # nothing to resume: fresh run
            else:
                chains = jax.tree.map(repad, payload["chains"])
                chains = jax.device_put(
                    chains, jax.tree.map(lambda _: cshard, chains))
                k = jnp.asarray(payload["key"])
                key = jax.random.wrap_key_data(k) if typed_key else k
                if fedc is not None:
                    cst0 = None
                    if fedc[1] is not None:
                        cst0 = (repad(payload["ref"]),
                                repad(payload["err"]))
                        if len(fedc[1]) == 3:
                            cst0 = cst0 + (repad(payload["derr"]),)
                    fedc = (repad(jnp.asarray(payload["sids"],
                                              jnp.int32), fill=0), cst0)
                if hw is not None:
                    hw = (repad(jnp.asarray(payload["word"], jnp.int32),
                                fill=0),
                          repad(jnp.asarray(payload["lp_ref"],
                                            jnp.float32), fill=-jnp.inf))
                if collect:
                    out = [jax.tree.map(jnp.asarray, payload["trace"])]

        refresh_mode = bool(refresh_every) and self.cfg.method == "fsgld"
        tel_seg = (telemetry.log_every if telemetry is not None
                   else None)
        seg_len = (snapshot_every if snapshot_every
                   else (refresh_every if refresh_mode
                         else (tel_seg or num_rounds)))
        tel_rows = []
        r0 = r_start
        while r0 < num_rounds:
            if refresh_mode and r0 > 0:
                # refresh boundary (r0 is a refresh_every multiple)
                if self.bank is None or self.bank.kind != "diag":
                    # refresh_bank(_mesh) fits DIAG banks over flat-vector
                    # params (same limit as the legacy path); swapping the
                    # bank kind under a specialized round fn would corrupt
                    # the kernel path silently — refuse loudly instead.
                    raise NotImplementedError(
                        "adaptive refresh supports flat-parameter 'diag' "
                        f"banks only (got {getattr(self.bank, 'kind', None)!r})")
                center = jax.tree.map(
                    lambda t: t[:n_chains].mean(0), chains)
                with obs_trace.span("engine.refresh", round=int(r0)):
                    bank_rt = self.refresh(center)
            seg = min(seg_len, num_rounds - r0)
            execute = self._executor(
                num_rounds=seg, n_chains=n_chains, n_total=n_total,
                reassign=reassign, collect=collect,
                collect_every=collect_every, layout=layout,
                federation=fed, recovery=recovery, chaos=chaos,
                telemetry=telemetry)
            t_seg = time.monotonic()
            with obs_trace.span("engine.segment", r0=int(r0),
                                rounds=int(seg)):
                outs = execute(
                    key, chains, self._data(), bank_rt,
                    jnp.asarray(r0, jnp.int32), fedc, hw)
            if telemetry is not None:
                chains, trace, key, fedc, hw, mrow = outs
                # the device_get syncs the segment — one host sync per
                # segment boundary, where snapshot writers sync anyway
                row = {k: np.asarray(jax.device_get(v))[:n_chains]
                       for k, v in mrow.items()}
                tel_rows.append(row)
            else:
                chains, trace, key, fedc, hw = outs
            if collect:
                out.append(trace)
            r0 += seg
            if telemetry is not None and obs_trace.enabled():
                dt = time.monotonic() - t_seg
                steps = seg * self.cfg.local_updates * n_chains
                obs_trace.event(
                    "engine.progress", round=int(r0),
                    rounds=int(num_rounds), seconds=round(dt, 6),
                    steps_per_s=round(steps / max(dt, 1e-9), 3),
                    **{k: round(float(v.mean()), 6)
                       for k, v in row.items()})
            if snapshot_every:
                from repro.checkpoint.snapshot import save_snapshot
                trace_now = None
                if collect:
                    sl = [jax.tree.map(take, t) for t in out]
                    trace_now = (sl[0] if len(sl) == 1 else jax.tree.map(
                        lambda *xs: jnp.concatenate(xs, 1), *sl))
                save_snapshot(snapshot_path, snap_payload(trace_now),
                              rounds_done=r0)
        if not collect:
            res = jax.tree.map(take, chains)
        else:
            out = [jax.tree.map(take, t) for t in out]
            res = (out[0] if len(out) == 1 else
                   jax.tree.map(lambda *xs: jnp.concatenate(xs, 1), *out))
        frame = None
        if telemetry is not None:
            # per-segment (C, seg) rows -> one round-major (R, C) frame
            frame = MetricsFrame(
                {k: np.concatenate([r[k] for r in tel_rows],
                                   axis=1).T.astype(np.float32)
                 for k in tel_rows[0]}) if tel_rows else MetricsFrame(
                {n: np.zeros((0, n_chains), np.float32)
                 for n in telemetry.names})
        if recovery is None:
            return res if frame is None else (res, frame)
        lp_ref = None
        if recovery.use_detector:
            # surface the reduced per-chain reference (the same
            # nearest-rank quantile the in-scan detector compares
            # against), not the raw probe ring
            q_idx = min(recovery.window - 1,
                        int(recovery.quantile * (recovery.window - 1)))
            lp_ref = jax.device_get(
                jnp.sort(hw[1][:n_chains], axis=1)[:, q_idx])
        health = RunHealth(
            word=jax.device_get(hw[0])[:n_chains],
            policy=recovery.policy,
            lp_ref=lp_ref)
        return (res, health) if frame is None else (res, health, frame)

    # -- streamed client axis ---------------------------------------------

    def _run_streamed(self, key, chains, num_rounds, *, stream, n_chains,
                      n_total, reassign, collect_every, collect, layout,
                      federation, fedc, take):
        """Streamed-window loop: plan the resident sets from the RNG
        chain, then for each fixed-length window dispatch the scan
        segment (async) and — while the device runs it — build and stage
        the NEXT window's resident buffers (double-buffered host
        prefetch; ``Stream(prefetch=False)`` serializes for A/B timing).

        Fault-free streamed runs are bitwise identical to the resident
        path: the carry (key, chain states, fed carry) threads through
        the same executor I/O that already makes snapshot segmentation
        invisible, and every resident-window lookup — shard rows, sizes,
        probs, surrogate rows — is a gather of the exact values the
        resident path reads."""
        from repro.fed import schedule as fsched
        S = self.cfg.num_shards
        use_fed = federation is not None or self.aggregation == "fald"
        sids_rn = fsched.replay_sids(
            key, num_rounds=num_rounds, n_chains=n_chains, num_shards=S,
            federated=use_fed,
            sched=(federation.schedule if federation is not None
                   else None),
            reassign=reassign)
        windows = fsched.plan_stream(sids_rn, resident=stream.resident,
                                     window=stream.window)
        sizes_np = np.asarray(np.asarray(self.scheme.sizes), np.int64)
        probs_np = self.scheme.probs_array()
        bank = self.bank

        def stage(win):
            """Host-build one window's device operands. Every transfer
            below is async (jax dispatches device_put/gathers without
            blocking), so calling this right after a segment dispatch
            overlaps the staging with the running scan."""
            ids = win.resident_ids          # (K,) sorted int32, padded
            data = self._client_rows(ids)
            # int->f32 via the SAME conversions the resident arrays take
            # (ShardScheme.as_arrays / sizes_array), so each (K,) row is
            # bitwise the resident table's row
            sp = (jnp.asarray(sizes_np[ids].astype(np.int32)),
                  jnp.asarray(sizes_np[ids].astype(np.float32)),
                  jnp.asarray(probs_np[ids]))
            bnk = None
            if bank is not None:
                idx = jnp.asarray(ids)
                row = lambda a: jnp.asarray(a)[idx]  # noqa: E731
                # resident-row bank: per-shard rows gathered, the global
                # product Gaussian carried through UNTOUCHED (it is a
                # sum over all S shards, computed once at fit time)
                bnk = SurrogateBank(jax.tree.map(row, bank.means),
                                    jax.tree.map(row, bank.precs),
                                    bank.global_, bank.kind)
            return data, bnk, jnp.asarray(ids), sp

        hw = None
        out = []
        t_run = time.monotonic()

        def timed_stage(idx):
            """Stage window ``idx`` under a span; returns (operands,
            host seconds spent staging) — after the FIRST window every
            stage call runs while the device executes the previous
            window's scan, so its span duration IS the prefetch work
            hidden behind compute (``Stream(prefetch=False)`` serializes
            and the same spans become the A/B reference)."""
            t0 = time.monotonic()
            with obs_trace.span("stream.stage", window=idx):
                s = stage(windows[idx])
            return s, time.monotonic() - t0

        staged, first_stage_s = timed_stage(0)
        stage_s = first_stage_s
        for i, win in enumerate(windows):
            execute = self._executor(
                num_rounds=win.length, n_chains=n_chains,
                n_total=n_total, reassign=reassign, collect=collect,
                collect_every=collect_every, layout=layout,
                federation=federation, stream=stream.resident)
            data_k, bank_k, ids_dev, sp_dev = staged
            with obs_trace.span("stream.dispatch", window=i,
                                r0=int(win.r0), rounds=int(win.length)):
                chains, trace, key, fedc, hw = execute(
                    key, chains, data_k, bank_k,
                    jnp.asarray(win.r0, jnp.int32), fedc, hw, ids_dev,
                    sp_dev)
            if i + 1 < len(windows):
                if not stream.prefetch:
                    jax.block_until_ready(chains)   # no overlap: A/B ref
                staged, ds = timed_stage(i + 1)
                stage_s += ds
            if collect:
                out.append(trace)
            if self.stream_hook is not None:
                self.stream_hook(i, win)
        if obs_trace.enabled():
            wall = time.monotonic() - t_run
            hidden = stage_s - first_stage_s  # post-dispatch stages only
            obs_trace.event(
                "stream.prefetch_overlap", windows=len(windows),
                prefetch=bool(stream.prefetch),
                stage_s=round(stage_s, 6), wall_s=round(wall, 6),
                overlap_frac=round(
                    (hidden / max(wall, 1e-9))
                    if stream.prefetch else 0.0, 6))
        if not collect:
            return jax.tree.map(take, chains)
        out = [jax.tree.map(take, t) for t in out]
        return (out[0] if len(out) == 1 else
                jax.tree.map(lambda *xs: jnp.concatenate(xs, 1), *out))

    # -- model-axis work: shard-parallel surrogate refresh ----------------

    def refresh(self, theta: PyTree) -> SurrogateBank:
        """Adaptive surrogate refresh at ``theta`` with the client-shard
        axis S split over the MODEL mesh axis (each model group runs the
        Fisher/gradient pass for its subset of clients, results gathered
        by the shard_map output spec). Same math as
        ``federated.refresh_bank``."""
        return refresh_bank_mesh(self.log_lik_fn, self._data(), theta,
                                 self.mesh, sizes=self.scheme.sizes)


def refresh_bank_mesh(log_lik_fn: LogLikFn, shard_data: PyTree,
                      theta: jax.Array, mesh, *, sizes=None,
                      jitter: float = 1e-3, batch: int = 256
                      ) -> SurrogateBank:
    """``federated.refresh_bank`` parallelised over the mesh 'model' axis:
    per-client score sums + centered Fishers are embarrassingly parallel
    over clients, so the S axis shards over 'model' (requires S % |model|
    == 0; the 1x1 host mesh degenerates to the serial pass). Ragged
    clients reduce over their live prefix only."""
    leaf = jax.tree.leaves(shard_data)[0]
    S, max_n = leaf.shape[0], leaf.shape[1]
    sizes = (max_n,) * S if sizes is None else tuple(sizes)
    n_arr = jnp.asarray(sizes, jnp.float32)
    m_size = mesh.shape["model"]
    assert S % m_size == 0, (S, m_size)

    def one_shard(theta, data_s, n_s):
        # Per-example scores in BATCHED gradient passes: each lax.map step
        # vmaps grad over a whole chunk of examples (gathered by index)
        # instead of a dynamic_slice-of-1 per example. Index chunks pad up
        # to a multiple of `batch` with clamped gathers; masking stays a
        # where(), not live*g: pad rows may hold NaN by design and
        # 0 * NaN == NaN would poison the reduction.
        def gpair(i):
            item = jax.tree.map(lambda d: d[i][None], data_s)
            g = jax.grad(log_lik_fn)(theta, item)
            g = jnp.where(i < n_s, g, jnp.zeros_like(g))
            return g, g * g

        # tail indices >= max_n gather clamped rows but always fail the
        # i < n_s mask (n_s <= max_n), so they contribute exact zeros.
        nb = -(-max_n // batch)
        idx = jnp.arange(nb * batch)
        g, g2 = jax.lax.map(jax.vmap(gpair), idx.reshape(nb, batch))
        # flatten and trim to max_n before reducing: the reduction sees
        # the same (max_n, ...) operand as the serial refresh pass, so the
        # partial-sum grouping (and hence rounding) is unchanged
        gsum = g.reshape((-1,) + g.shape[2:])[:max_n].sum(0)
        centered = (g2.reshape((-1,) + g2.shape[2:])[:max_n].sum(0)
                    - gsum * gsum / n_s)
        return gsum, centered

    def block(theta, data_blk, n_blk):
        return jax.vmap(one_shard, in_axes=(None, 0, 0))(
            theta, data_blk, n_blk)

    # theta is an explicit (replicated) operand, not a closure: a committed
    # input closed over by shard_map would carry its outer mesh inside
    b, fisher = jax.jit(jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(), P("model"), P("model")),
        out_specs=(P("model"), P("model")),
        check_vma=False))(theta, shard_data, n_arr)
    precs = jnp.maximum(fisher, 0.0) + jitter
    mus = theta[None] + b / precs
    return make_bank(mus, precs, "diag")
