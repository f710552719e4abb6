"""Fused FSGLD parameter-update Pallas TPU kernel.

The per-step hot spot the paper's method adds to SGLD is elementwise but
multi-operand:

    theta' = theta + (h/2) * [ -prior_prec*theta + scale*g_hat
                               + alpha*( lam_g*(mu_g - theta)
                                         - (lam_s/f_s)*(mu_s - theta) ) ]
             + sqrt(h*temperature) * xi,        xi ~ N(0, 1)

Unfused this costs ~7 HBM round-trips over P parameters (theta, g, mu_g,
mu_s, xi, out + the precision vectors); the kernel does ONE pass with
(8,128)-aligned VMEM tiles and generates xi *in kernel* from a counter-based
hash (murmur3 finalizer + Box-Muller), so the noise tensor never touches HBM.

Using a counter-based hash (instead of pltpu.prng_random_bits) keeps the
kernel bit-exactly reproducible by the pure-jnp oracle in ref.py — the
correctness tests assert end-to-end equality including the noise.

Three DRIFT variants:
  plain   — SGLD/DSGLD (alpha = 0): operands (theta, g)
  scalar  — per-tensor scalar precisions: operands (theta, g, mu_g, mu_s)
  diag    — diagonal precisions: operands (theta, g, mu_g, mu_s, lam_g, lam_s)

crossed with two DYNAMICS (the paper's conducive correction is drift-level,
so it composes with any SG-MCMC integrator — see core/sghmc.py):
  langevin — the update above (one output);
  sghmc    — naive-Euler SGHMC with friction alpha_f (S_FRIC scalar row):
                 r'     = (1 - a) r + h * drift + sqrt(2 a tau) sqrt(h) xi
                 theta' = theta + r'
             extra momentum operand, two outputs (theta', r').

All operate on parameters reshaped to (rows, 128); the jit'd wrapper in
ops.py handles ravel / pad / unpad and per-tensor seeds.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK_ROWS = 256  # 256 x 128 fp32 = 128 KiB per operand tile in VMEM
PACK_BLOCK_ROWS = 8  # packed multi-leaf grid: fp32 min tile, the floor
# of ops.choose_block_rows; its cap: the body's (block_rows, 128)
# temporaries live in VMEM, which 2048 rows overflow on a v5e
PACK_BLOCK_ROWS_MAX = 512

# scalar-operand layout (one f32 row broadcast to every block of a
# (chain, leaf)); S_FRIC is the SGHMC friction alpha_f, dead for langevin
(S_H, S_SCALE, S_FS, S_PRIOR, S_ALPHA, S_TEMP, S_LAMG, S_LAMS,
 S_FRIC) = range(9)
SCALAR_COLS = 9

_N_SUR = {"plain": 0, "scalar": 2, "diag": 4}


def _mix(h: jax.Array) -> jax.Array:
    """murmur3 fmix32 — full avalanche integer hash (uint32 -> uint32)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _u24_to_f32(u: jax.Array) -> jax.Array:
    return u.astype(jnp.int32).astype(jnp.float32)


def _gaussian_noise(seed: jax.Array, idx: jax.Array) -> jax.Array:
    """Standard normal per element via two hash streams + Box-Muller.
    ``idx``: uint32 global element indices; ``seed``: uint32 scalar."""
    h1 = _mix(idx * jnp.uint32(2) + jnp.uint32(1) + seed * jnp.uint32(0x9E3779B9))
    h2 = _mix(idx * jnp.uint32(2) + seed * jnp.uint32(0x85EBCA77))
    # 24-bit mantissas -> u in (0, 1); u1 strictly > 0 for the log. Mosaic
    # has no uint32 -> f32 cast; the values are < 2^24, so the hop through
    # int32 is exact and the stream stays bit-identical to ref.py.
    u1 = _u24_to_f32(h1 >> jnp.uint32(8)) * (1.0 / (1 << 24)) \
        + (0.5 / (1 << 24))
    u2 = _u24_to_f32(h2 >> jnp.uint32(8)) * (1.0 / (1 << 24))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos((2.0 * jnp.pi) * u2)


def _drift(variant, sc, th, g, sur):
    """The shared FSGLD drift: prior + scaled minibatch gradient
    (+ conducive term for the surrogate variants). ``sc(j)`` reads scalar
    column j of the current (chain, leaf) row."""
    base = -sc(S_PRIOR) * th + sc(S_SCALE) * g
    if variant == "plain":
        return base
    if variant == "scalar":
        mg, ms = sur
        cond = sc(S_LAMG) * (mg - th) \
            - (sc(S_LAMS) / sc(S_FS)) * (ms - th)
    else:  # diag
        mg, ms, lg, ls = sur
        cond = lg * (mg - th) - (ls / sc(S_FS)) * (ms - th)
    return base + sc(S_ALPHA) * cond


def _locate(pid, leaf_blocks: tuple):
    """Grid step -> (chain, leaf, leaf's first block). The grid walks
    ``chains * bpc`` blocks, chain major; the leaf comes from L scalar
    compares against the STATIC block counts, so no per-block table has to
    fit in SMEM (a real model's table outgrows its 1 MiB)."""
    bpc = sum(leaf_blocks)
    blk = pid % bpc
    leaf, start = 0, 0
    for first in itertools.accumulate(leaf_blocks[:-1]):
        past = blk >= first
        leaf = leaf + past.astype(jnp.int32)
        start = jnp.where(past, first, start)
    return pid // bpc, leaf, start


def _make_kernel(variant: str, dynamics: str, *, block_rows: int,
                 leaf_blocks: tuple):
    """Kernel body for one (drift variant, dynamics, layout) cell.

    ``leaf_blocks[l]`` is the number of (block_rows, 128) blocks leaf l
    owns in each chain's segment (one leaf of ``bpc`` blocks for the
    per-leaf launcher). Each element keeps its in-leaf index, so the noise
    stream is bit-identical to the per-leaf kernel and to ref.py.

    Ref order: seed, scalars, theta, [momentum,] g, [surrogate
    operands...], theta_out[, momentum_out]. ``seed`` (1, 1, 1) and
    ``scalars`` (1, 1, SCALAR_COLS) are the SMEM rows of the block's
    (chain, leaf). The langevin cells reproduce the original per-dynamics
    kernels expression-for-expression, so noise and rounding are
    unchanged.
    """
    n_sur = _N_SUR[variant]
    momentum = dynamics == "sghmc"
    bpc = sum(leaf_blocks)

    def kernel(seed_ref, sc_ref, *refs):
        pid = pl.program_id(0)
        _, _, start = _locate(pid, leaf_blocks)
        th_ref = refs[0]
        r_ref = refs[1] if momentum else None
        k = 2 if momentum else 1
        g_ref = refs[k]
        sur = [refs[k + 1 + i][...].astype(jnp.float32)
               for i in range(n_sur)]
        outs = refs[k + 1 + n_sur:]

        def sc(j):
            return sc_ref[0, 0, j]

        th = th_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        drift = _drift(variant, sc, th, g, sur)

        base = ((pid % bpc - start) * (block_rows * LANE)).astype(jnp.uint32)
        row = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, LANE), 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, (block_rows, LANE), 1)
        idx = base + row * jnp.uint32(LANE) + col
        xi = _gaussian_noise(seed_ref[0, 0, 0], idx)

        h = sc(S_H)
        if dynamics == "langevin":
            sig = jnp.sqrt(h * sc(S_TEMP))
            outs[0][...] = th + (h * 0.5) * drift + sig * xi
        else:
            a = sc(S_FRIC)
            noise_sig = jnp.sqrt(2.0 * a * sc(S_TEMP))
            r = r_ref[...].astype(jnp.float32)
            r_new = (1.0 - a) * r + h * drift \
                + (noise_sig * jnp.sqrt(h)) * xi
            outs[0][...] = th + r_new
            outs[1][...] = r_new

    return kernel


def _variant_ops(variant, mu_g, mu_s, lam_g, lam_s, tile, shared_tile):
    """Surrogate operand / BlockSpec lists shared by both launchers.
    Shared (global) operands re-read per chain via ``shared_tile``."""
    if variant == "plain":
        return [], []
    if variant == "scalar":
        return [mu_g, mu_s], [shared_tile, tile]
    if variant == "diag":
        return [mu_g, mu_s, lam_g, lam_s], \
            [shared_tile, tile, shared_tile, tile]
    raise ValueError(variant)


@functools.partial(jax.jit, static_argnames=("variant", "dynamics",
                                             "interpret", "block_rows",
                                             "chains"))
def fsgld_update_2d(theta2d: jax.Array, g2d: jax.Array, seed: jax.Array,
                    scalars: jax.Array, *, variant: str = "plain",
                    dynamics: str = "langevin", r2d=None,
                    mu_g=None, mu_s=None, lam_g=None, lam_s=None,
                    interpret: bool = False,
                    block_rows: int = BLOCK_ROWS,
                    chains: int = 1):
    """Run the fused update on (rows, 128)-shaped operands.

    scalars: (chains, SCALAR_COLS) f32 rows [h, scale, f_s, prior_prec,
    alpha, temperature, lam_g, lam_s, friction]; seed: (chains,) uint32.
    ``dynamics='sghmc'`` takes the (rows, 128) momentum buffer ``r2d`` and
    returns the pair (theta', r'); 'langevin' returns theta' alone.

    CHAIN-BATCHED mode (``chains`` > 1): the leading ``rows`` axis is
    chain-major — rows [c*rows_c, (c+1)*rows_c) hold chain c's parameters
    (rows_c = rows / chains). Per-chain operands (theta, r, g, mu_s, lam_s)
    are full-height; per-chain *scalars* and *seeds* are read from SMEM
    by the block's chain ``i // bpc`` and SHARED operands (mu_g, lam_g — the
    global surrogate, identical for every chain) are (rows_c, 128) and
    re-read per chain via ``i % bpc``, so one pallas_call covers the whole
    chain block in a single HBM pass with no broadcast materialisation.
    Noise streams are per-chain (seed c + in-chain element index), making
    the batched kernel bit-identical to ``chains`` separate calls.
    """
    rows = theta2d.shape[0]
    assert theta2d.shape[1] == LANE, theta2d.shape
    assert rows % chains == 0, (rows, chains)
    rows_c = rows // chains
    br = min(block_rows, rows_c)
    assert rows_c % br == 0, (rows_c, br)
    bpc = rows_c // br  # blocks per chain
    return _fused_call(theta2d, g2d, seed, scalars, variant=variant,
                       dynamics=dynamics, r2d=r2d, mu_g=mu_g, mu_s=mu_s,
                       lam_g=lam_g, lam_s=lam_s, leaf_blocks=(bpc,),
                       block_rows=br, chains=chains, interpret=interpret)


# ---------------------------------------------------------------------------
# packed multi-leaf single-launch kernel (PR 2; SGHMC + mixed dtypes PR 4)
#
# The whole parameter pytree of a whole chain block rides in ONE
# (C * rows_total, 128) buffer: each leaf owns a contiguous run of rows
# padded up to a block multiple, chains are major. The static per-leaf
# block counts (``leaf_blocks``) let the kernel work out each block's
# (chain, leaf) and in-leaf offset, so one pallas_call per step covers
# every leaf of every chain while noise streams stay bit-identical to the
# per-leaf kernel above (same per-(chain, leaf) seed, same in-leaf element
# index). ``dynamics='sghmc'`` adds a SECOND chain-major buffer — the
# momenta — over the same layout.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "variant", "dynamics", "interpret", "block_rows", "chains",
    "leaf_blocks"))
def fsgld_update_packed(theta2d: jax.Array, g2d: jax.Array,
                        seeds: jax.Array, scalars: jax.Array, *,
                        variant: str = "plain",
                        dynamics: str = "langevin", r2d=None,
                        mu_g=None, mu_s=None, lam_g=None, lam_s=None,
                        leaf_blocks: tuple = (1,),
                        interpret: bool = False,
                        block_rows: int = PACK_BLOCK_ROWS,
                        chains: int = 1):
    """SINGLE-LAUNCH fused update over a packed multi-leaf chain block.

    theta2d/g2d (and ``r2d``, the momenta, for ``dynamics='sghmc'``):
    (chains * rows_total, 128) chain-major packed buffers, rows_total =
    block_rows * sum(leaf_blocks). seeds: (chains, L) uint32 — one stream
    per (chain, leaf), matching the per-leaf kernel's seed derivation.
    scalars: (chains, L, SCALAR_COLS) rows in the S_* layout (per-leaf
    scalar precisions for the 'scalar' variant live in S_LAMG/S_LAMS, the
    SGHMC friction in S_FRIC). mu_g/lam_g: (rows_total, 128) packed GLOBAL
    surrogate, re-read per chain; mu_s/lam_s: (chains * rows_total, 128)
    packed per-chain resident-client surrogates.

    leaf_blocks[l] is the STATIC number of (block_rows, 128) blocks leaf l
    owns in each chain's segment — one grid, one HBM pass, zero per-leaf
    dispatch. Bit-identical to per-leaf ``fsgld_update_2d`` calls because
    pad rows at each leaf tail are discarded at unpack and live elements
    keep their in-leaf index.
    Returns theta' ('langevin') or the pair (theta', r') ('sghmc').
    """
    assert seeds.shape == (chains, len(leaf_blocks)), \
        (seeds.shape, chains, leaf_blocks)
    return _fused_call(theta2d, g2d, seeds, scalars, variant=variant,
                       dynamics=dynamics, r2d=r2d, mu_g=mu_g, mu_s=mu_s,
                       lam_g=lam_g, lam_s=lam_s, leaf_blocks=leaf_blocks,
                       block_rows=block_rows, chains=chains,
                       interpret=interpret)


def _fused_call(theta2d, g2d, seeds, scalars, *, variant, dynamics, r2d,
                mu_g, mu_s, lam_g, lam_s, leaf_blocks, block_rows, chains,
                interpret):
    """The one pallas_call behind both launchers."""
    rows = theta2d.shape[0]
    assert theta2d.shape[1] == LANE, theta2d.shape
    bpc = sum(leaf_blocks)
    assert rows == chains * bpc * block_rows, (rows, chains, bpc, block_rows)

    tile = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    shared_tile = pl.BlockSpec((block_rows, LANE), lambda i: (i % bpc, 0))
    # per-(chain, leaf) seed and scalar rows ride SMEM as (1, 1, n) blocks
    # of (C * L, 1, n) arrays: their last two dims equal the array's, which
    # Mosaic accepts, where a (1, n) block over C rows is refused
    def row(i):
        chain, leaf, _ = _locate(i, leaf_blocks)
        return chain * len(leaf_blocks) + leaf, 0, 0

    seed_spec = pl.BlockSpec((1, 1, 1), row, memory_space=pltpu.SMEM)
    scalar_spec = pl.BlockSpec((1, 1, SCALAR_COLS), row,
                               memory_space=pltpu.SMEM)

    kernel = _make_kernel(variant, dynamics, block_rows=block_rows,
                          leaf_blocks=leaf_blocks)
    sur_ops, sur_specs = _variant_ops(variant, mu_g, mu_s, lam_g, lam_s,
                                      tile, shared_tile)
    if dynamics == "sghmc":
        assert r2d is not None and r2d.shape == theta2d.shape
        ops = [theta2d, r2d, g2d] + sur_ops
        specs = [tile, tile, tile] + sur_specs
        out_specs = (tile, tile)
        out_shape = (jax.ShapeDtypeStruct((rows, LANE), jnp.float32),) * 2
    else:
        ops = [theta2d, g2d] + sur_ops
        specs = [tile, tile] + sur_specs
        out_specs = tile
        out_shape = jax.ShapeDtypeStruct((rows, LANE), jnp.float32)

    # theta' (and r') overwrite theta's (r's) buffer: each block is read
    # before it is written, and the state then needs no second copy in HBM
    aliases = {2: 0, 3: 1} if dynamics == "sghmc" else {2: 0}
    return pl.pallas_call(
        kernel,
        grid=(chains * bpc,),
        in_specs=[seed_spec, scalar_spec] + specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(seeds.reshape(-1, 1, 1), scalars.reshape(-1, 1, SCALAR_COLS), *ops)
