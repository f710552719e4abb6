"""Plain reference of one FSGLD chain on the packed executor's random
streams, and the benchmark's own surrogate fit (traffic data).

The update (Algorithm 1 of the FSGLD paper, scalar surrogates, Langevin):

    theta' = theta + h/2 [ -lam0 theta + N_s/(f_s m) grad log p(batch|theta)
                           + alpha ( lam_g (mu_g - theta)
                                     - lam_s/f_s (mu_s - theta) ) ]
             + sqrt(h T) xi

Random streams, as the engine draws them for one chain on one device
(``engine.run(key, state, 1, stacked=True, reassign='permutation')``):
the round key splits into (carry, assign, run) keys; the client is
``permutation(assign, S)[0]``; the run key splits into one chain key,
that into one key per local step, and each step key into a minibatch key
(``randint(k, (m,), 0, N_s)`` rows of the client's shard) and a noise key;
the noise key splits into one key per parameter leaf (tree order), each
giving a uint32 seed ``randint(k, (), 0, 2**31 - 1)``; element ``i`` of a
leaf (row-major) gets xi from the counter hash below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import refmodel


# --- the counter-hash Gaussian stream (murmur3 fmix32 + Box-Muller) -------

def _mix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def gaussian(seed, n: int):
    """n standard normals of stream ``seed`` (uint32), element i from
    hashes of (2i + 1, seed) and (2i, seed)."""
    idx = jnp.arange(n, dtype=jnp.uint32)
    seed = jnp.asarray(seed, jnp.uint32)
    h1 = _mix(idx * jnp.uint32(2) + jnp.uint32(1)
              + seed * jnp.uint32(0x9E3779B9))
    h2 = _mix(idx * jnp.uint32(2) + seed * jnp.uint32(0x85EBCA77))
    u1 = (h1 >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24)) \
        + (0.5 / (1 << 24))
    u2 = (h2 >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * jnp.pi) * u2)


# --- random streams of one round -------------------------------------------

def round_streams(key, *, num_clients: int, local_steps: int,
                  minibatch: int, shard_size: int, num_leaves: int):
    """(client, [(row indices (m,), leaf seeds (L,) uint32)] per step)."""
    _, k_assign, k_run = jax.random.split(key, 3)
    client = jax.random.permutation(k_assign, num_clients)[0]
    k_chain = jax.random.split(k_run, 1)[0]
    steps = []
    for ks in jax.random.split(k_chain, local_steps):
        k_batch, k_noise = jax.random.split(ks)
        rows = jax.random.randint(k_batch, (minibatch,), 0, shard_size)
        seeds = jnp.stack([
            jax.random.randint(k, (), 0, 2**31 - 1).astype(jnp.uint32)
            for k in jax.random.split(k_noise, num_leaves)])
        steps.append((rows, seeds))
    return client, steps


# --- one update -----------------------------------------------------------

def update(theta, grad, seeds, mu_g, mu_s, *, h, scale, f_s, lam_g, lam_s,
           prior=1.0, alpha=1.0, temperature=1.0, store=None):
    """The FSGLD update leaf by leaf, in float32. ``lam_g``/``lam_s`` are
    per-leaf lists; ``store`` is the dtype the state is kept in (the
    state's own where None)."""
    lv, td = jax.tree.flatten(theta)
    out = []
    for i, (t, g, mg, ms) in enumerate(zip(
            lv, jax.tree.leaves(grad), jax.tree.leaves(mu_g),
            jax.tree.leaves(mu_s))):
        tf = t.astype(jnp.float32)
        cond = lam_g[i] * (mg.astype(jnp.float32) - tf) \
            - (lam_s[i] / f_s) * (ms.astype(jnp.float32) - tf)
        drift = -prior * tf + scale * g.astype(jnp.float32) + alpha * cond
        xi = gaussian(seeds[i], t.size).reshape(t.shape)
        new = tf + (h * 0.5) * drift + jnp.sqrt(h * temperature) * xi
        out.append(new.astype(store or t.dtype))
    return jax.tree.unflatten(td, out)


def half_batch(batch):
    """Half of the batch left out and the sum over the rest doubled (a
    fault the check has to catch): half of the sequences, or of the
    tokens of a single sequence."""
    m, s = batch["tokens"].shape
    if m >= 2:
        return {k: v[: m // 2] for k, v in batch.items()}, 2.0
    return {k: v[:, : s // 2] for k, v in batch.items()}, 2.0


@functools.partial(jax.jit, static_argnames=(
    "arch_items", "mode", "store", "fault", "zero_grad"))
def step(theta, batch, seeds, mu_g, mu_s, scal, *, arch_items, mode="fp32",
         store=None, fault=None, zero_grad=False):
    """One reference step. ``scal``: dict of h, scale, f_s and per-leaf
    lam_g / lam_s arrays."""
    arch = dict(arch_items)
    mult = 1.0
    if fault == "half_batch":
        batch, mult = half_batch(batch)
    if zero_grad:
        g = jax.tree.map(jnp.zeros_like, theta)
    else:
        g = jax.grad(lambda p: mult * refmodel.log_lik(p, arch, batch, mode))(
            theta)
    L = len(jax.tree.leaves(theta))
    return update(theta, g, seeds, mu_g, mu_s, h=scal["h"],
                  scale=scal["scale"], f_s=scal["f_s"],
                  lam_g=[scal["lam_g"][i] for i in range(L)],
                  lam_s=[scal["lam_s"][i] for i in range(L)], store=store)


def estimator_scale(shard_size: int, num_clients: int, minibatch: int):
    """(N_s / (f_s m), f_s) in float32, f_s = 1/S, as the update reads
    them."""
    f_s = jnp.float32(1.0 / num_clients)
    return jnp.float32(shard_size) / (f_s * jnp.float32(minibatch)), f_s


# --- the benchmark's surrogate fit (traffic data, shared by both sides) ----

@functools.partial(jax.jit, static_argnames=("arch_items", "last"),
                   donate_argnums=(0,))
def _fit_step(theta, data_s, key, h, *, arch_items, last=False):
    """One local SGLD step on one client's likelihood (no prior, no
    surrogate), batch of ``fit_minibatch`` rows, noise from the counter
    hash (``jax.random.normal`` over a whole leaf takes gigabytes of
    scratch at these sizes); with ``last`` the kept
    pair's midpoint (bfloat16) and per-leaf variance instead."""
    arch = dict(arch_items)
    n = data_s["tokens"].shape[0]
    m = arch["fit_minibatch"]
    k1, k2 = jax.random.split(key)
    rows = jax.random.randint(k1, (m,), 0, n)
    batch = jax.tree.map(lambda d: d[rows], data_s)
    g = jax.grad(lambda p: refmodel.log_lik(p, arch, batch, "bf16"))(theta)
    lv, td = jax.tree.flatten(theta)
    seeds = [jax.random.randint(k, (), 0, 2**31 - 1).astype(jnp.uint32)
             for k in jax.random.split(k2, len(lv))]
    delta = [(h / 2) * (n / m) * gg
             + jnp.sqrt(h) * gaussian(sd, t.size).reshape(t.shape)
             for t, gg, sd in zip(lv, jax.tree.leaves(g), seeds)]
    if not last:
        return jax.tree.unflatten(td, [t + d for t, d in zip(lv, delta)])
    mean = [(t + d / 2).astype(jnp.bfloat16) for t, d in zip(lv, delta)]
    var = jnp.stack([jnp.mean(jnp.square(d / 2)) for d in delta])
    return jax.tree.unflatten(td, mean), var


def fit_bank(theta0, data, key, *, arch: dict, h: float, burn: int,
             minibatch: int, jitter: float = 1e-8):
    """Per client: ``burn`` local SGLD steps from theta0, then one more;
    the Gaussian of the last two states (mean stored bfloat16, one
    precision 1/(var + jitter) per leaf). The global surrogate is their
    product: lam_g = sum lam_s, mu_g = sum lam_s mu_s / lam_g (bfloat16).
    Returns (means (S, ...) bf16, lam_s (S, L), mu_g bf16, lam_g (L,))."""
    items = tuple(sorted({**arch, "fit_minibatch": minibatch}.items()))
    S = data["tokens"].shape[0]
    means, lams = [], []
    for s in range(S):
        ks = jax.random.split(jax.random.fold_in(key, s), burn + 1)
        th = jax.tree.map(jnp.copy, theta0)
        d_s = jax.tree.map(lambda d: d[s], data)
        for i in range(burn):
            th = _fit_step(th, d_s, ks[i], h, arch_items=items)
        mu, var = _fit_step(th, d_s, ks[burn], h, arch_items=items,
                            last=True)
        means.append(mu)
        lams.append(1.0 / (var + jitter))
    lam_s = jnp.stack(lams)
    return _product(means, lam_s)


@jax.jit
def _product(means, lam_s):
    lam_g = lam_s.sum(0)
    td = jax.tree.structure(means[0])
    per_leaf = zip(*[jax.tree.leaves(m) for m in means])
    mu_g = [(sum(lam_s[s, i] * x.astype(jnp.float32)
                 for s, x in enumerate(xs)) / lam_g[i]).astype(jnp.bfloat16)
            for i, xs in enumerate(per_leaf)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *means)
    return stacked, lam_s, jax.tree.unflatten(td, mu_g), lam_g
