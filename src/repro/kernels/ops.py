"""jit'd public wrappers around the fused FSGLD update kernel.

`fused_update_tree` applies the kernel leaf-by-leaf over a parameter pytree:
ravel -> pad to (rows, 128) -> pallas_call -> unpad/reshape, with a
deterministic per-leaf seed folded out of a JAX PRNG key. Off the TPU
the kernel runs in interpret mode (the TPU path is identical modulo
`interpret=False`).

`PackedChains` is the single-launch layout (PR 2): every leaf of every
chain lives in ONE chain-major (C * rows_total, 128) buffer, built once
per run by `pack`; per-step updates go through `packed_step`, which issues
exactly one `pallas_call` for the whole chain block using the layout's
static per-leaf block counts (see kernels/fsgld_update.py). The layout is
MULTI-SEGMENT (PR 4): SGHMC dynamics add a second chain-major momentum
buffer over the same layout, and non-fp32 parameter leaves ride
the fp32 buffer with a per-step `quantize` round-trip back to their
storage dtype — bit-identical to the per-leaf kernel's dtype handling.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels.fsgld_update import (LANE, PACK_BLOCK_ROWS,
                                        PACK_BLOCK_ROWS_MAX, SCALAR_COLS,
                                        fsgld_update_2d, fsgld_update_packed)

PyTree = Any


def _interpret(flag: Optional[bool]) -> bool:
    """``interpret=None`` resolves when the kernel is called, not when this
    module is imported: compiled on a TPU backend, interpreted (the kernel
    body run through XLA) on any other."""
    return jax.default_backend() != "tpu" if flag is None else flag


def _pad_2d(vec: jax.Array, block_rows: int):
    n = vec.shape[0]
    per_block = block_rows * LANE
    padded = -(-n // per_block) * per_block
    vec = jnp.pad(vec.astype(jnp.float32), (0, padded - n))
    return vec.reshape(-1, LANE), n


def _scalars_row(h, scale, f_s, prior_prec, alpha, temperature, lam_g,
                 lam_s, friction=0.0) -> jax.Array:
    return jnp.stack([
        jnp.float32(h), jnp.asarray(scale, jnp.float32),
        jnp.asarray(f_s, jnp.float32), jnp.float32(prior_prec),
        jnp.float32(alpha), jnp.float32(temperature),
        jnp.asarray(lam_g, jnp.float32), jnp.asarray(lam_s, jnp.float32),
        jnp.asarray(friction, jnp.float32),
    ]).reshape(1, SCALAR_COLS)


def fused_update_flat(theta: jax.Array, g: jax.Array, seed: jax.Array, *,
                      h, scale, f_s=1.0, prior_prec=0.0, alpha=0.0,
                      temperature=1.0, mu_g=None, mu_s=None, lam_g=None,
                      lam_s=None, momentum=None, friction=0.0,
                      dynamics: str = "langevin", block_rows: int = 256,
                      interpret: Optional[bool] = None):
    """Fused update of one flat vector. Seeds: uint32 scalar.

    ``dynamics='langevin'`` (default) returns theta'; ``'sghmc'`` carries
    the ``momentum`` operand through the SGHMC integrator and returns the
    pair (theta', momentum'). Non-fp32 operands round-trip through fp32
    per step (the kernels compute at fp32 and cast back out).
    """
    interpret = _interpret(interpret)
    orig_shape, orig_dtype = theta.shape, theta.dtype
    th2, n = _pad_2d(theta.reshape(-1), block_rows)
    g2, _ = _pad_2d(g.reshape(-1), block_rows)
    rows = th2.shape[0]
    br = min(block_rows, rows)
    while rows % br:
        br //= 2

    if mu_g is None:
        variant = "plain"
        kw = {}
        lam_row = (0.0, 0.0)
    elif jnp.ndim(lam_g) == 0:
        variant = "scalar"
        kw = {"mu_g": _pad_2d(mu_g.reshape(-1), block_rows)[0],
              "mu_s": _pad_2d(mu_s.reshape(-1), block_rows)[0]}
        lam_row = (lam_g, lam_s)
    else:
        variant = "diag"
        kw = {"mu_g": _pad_2d(mu_g.reshape(-1), block_rows)[0],
              "mu_s": _pad_2d(mu_s.reshape(-1), block_rows)[0],
              "lam_g": _pad_2d(lam_g.reshape(-1), block_rows)[0],
              "lam_s": _pad_2d(lam_s.reshape(-1), block_rows)[0]}
        lam_row = (0.0, 0.0)
    if dynamics == "sghmc":
        kw["r2d"] = _pad_2d(momentum.reshape(-1), block_rows)[0]

    sc = _scalars_row(h, scale, f_s, prior_prec, alpha, temperature,
                      *lam_row, friction)
    out = fsgld_update_2d(th2, g2, seed.reshape(1).astype(jnp.uint32), sc,
                          variant=variant, dynamics=dynamics,
                          interpret=interpret, block_rows=br, **kw)

    def unpad(o, dt):
        return o.reshape(-1)[:n].reshape(orig_shape).astype(dt)

    if dynamics == "sghmc":
        return unpad(out[0], orig_dtype), unpad(out[1], momentum.dtype)
    return unpad(out, orig_dtype)


def fused_update_chains_flat(theta: jax.Array, g: jax.Array,
                             seeds: jax.Array, *, h, scale, f_s,
                             prior_prec=0.0, alpha=0.0, temperature=1.0,
                             mu_g=None, mu_s=None, lam_g=None, lam_s=None,
                             momentum=None, friction=0.0,
                             dynamics: str = "langevin",
                             block_rows: int = 256,
                             interpret: Optional[bool] = None):
    """CHAIN-BATCHED fused update: one pallas_call over a whole chain block.

    theta, g: (C, ...) stacked per-chain tensors; seeds: (C,) uint32;
    scale, f_s: per-chain scalars (C,) — each chain is resident at a
    different client so its unbiasing factor N_s/(f_s m) differs.
    mu_g / lam_g: the GLOBAL surrogate, shared by every chain ((P,) or
    scalar lam); mu_s / lam_s: per-chain resident-client surrogates
    ((C, P), or (C,) scalar lams). The kernel reads shared operands once
    per chain via BlockSpec index maps instead of materialising a (C, P)
    broadcast, so the hot elementwise update stays one HBM pass per
    chain-block. Bit-identical to C separate fused_update_flat calls.
    ``dynamics='sghmc'`` carries the (C, ...) ``momentum`` stack through
    the SGHMC integrator and returns the (theta', momentum') pair.
    """
    interpret = _interpret(interpret)
    C = theta.shape[0]
    orig_shape, orig_dtype = theta.shape, theta.dtype
    per_block = block_rows * LANE

    def pad_chains(x):  # (C, ...) -> (C*rows_c, LANE)
        x = x.reshape(C, -1).astype(jnp.float32)
        n = x.shape[1]
        padded = -(-n // per_block) * per_block
        x = jnp.pad(x, ((0, 0), (0, padded - n)))
        return x.reshape(C * (padded // LANE), LANE)

    def pad_shared(x):  # (P,) -> (rows_c, LANE)
        return _pad_2d(x.reshape(-1), block_rows)[0]

    n = theta.reshape(C, -1).shape[1]
    th2 = pad_chains(theta)
    g2 = pad_chains(g)
    rows_c = th2.shape[0] // C

    scale_c = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (C,))
    fs_c = jnp.broadcast_to(jnp.asarray(f_s, jnp.float32), (C,))

    if mu_g is None:
        variant = "plain"
        kw = {}
        lam_rows = (jnp.zeros((C,), jnp.float32),) * 2
    elif jnp.ndim(lam_g) == 0:
        variant = "scalar"
        kw = {"mu_g": pad_shared(mu_g), "mu_s": pad_chains(mu_s)}
        lam_rows = (jnp.broadcast_to(jnp.asarray(lam_g, jnp.float32), (C,)),
                    jnp.broadcast_to(jnp.asarray(lam_s, jnp.float32), (C,)))
    else:
        variant = "diag"
        kw = {"mu_g": pad_shared(mu_g), "mu_s": pad_chains(mu_s),
              "lam_g": pad_shared(lam_g), "lam_s": pad_chains(lam_s)}
        lam_rows = (jnp.zeros((C,), jnp.float32),) * 2

    def col(v):
        return jnp.broadcast_to(jnp.asarray(v, jnp.float32), (C,))

    if dynamics == "sghmc":
        kw["r2d"] = pad_chains(momentum)

    sc = jnp.stack([col(h), scale_c, fs_c, col(prior_prec), col(alpha),
                    col(temperature), lam_rows[0], lam_rows[1],
                    col(friction)], axis=1)
    br = min(block_rows, rows_c)
    out = fsgld_update_2d(th2, g2, seeds.astype(jnp.uint32), sc,
                          variant=variant, dynamics=dynamics,
                          interpret=interpret, block_rows=br, chains=C,
                          **kw)

    def unpad(o, dt):
        return o.reshape(C, -1)[:, :n].reshape(orig_shape).astype(dt)

    if dynamics == "sghmc":
        return unpad(out[0], orig_dtype), unpad(out[1], momentum.dtype)
    return unpad(out, orig_dtype)


def fused_update_chains_tree(theta: PyTree, g: PyTree, keys: jax.Array, *,
                             h, scale, f_s, prior_prec=0.0, alpha=0.0,
                             temperature=1.0, bank=None, sids=None,
                             surrogate_kind: Optional[str] = None,
                             momentum: Optional[PyTree] = None,
                             friction=0.0, dynamics: str = "langevin"):
    """Chain-batched fused update across a parameter pytree whose leaves
    carry a leading chain axis (C, ...).

    keys: (C, 2) per-chain PRNG keys; scale/f_s: (C,) per-chain factors;
    bank: SurrogateBank ('diag' or 'scalar') with sids (C,) selecting each
    chain's resident client, or None for SGLD/DSGLD. Per-leaf per-chain
    seeds are derived exactly as fused_update_tree does per chain, so the
    result bit-matches a vmap of the single-chain kernel path.
    ``dynamics='sghmc'`` takes the ``momentum`` pytree (same structure,
    leading chain axis) and returns the (theta', momentum') pair.
    """
    leaves, treedef = jax.tree.flatten(theta)
    gleaves = jax.tree.leaves(g)
    rleaves = (jax.tree.leaves(momentum) if momentum is not None
               else [None] * len(leaves))
    L = len(leaves)
    all_seeds = jax.vmap(lambda k: jax.random.split(k, L))(keys)  # (C, L, 2)

    if bank is None:
        mu_gs = mu_ss = lg = ls = [None] * L
    elif surrogate_kind == "diag":
        assert L == 1, "diag surrogates operate on flat vectors"
        mu_gs, lg = [bank.global_.mean], [bank.global_.prec]
        mu_ss, ls = [bank.means[sids]], [bank.precs[sids]]
    elif surrogate_kind == "scalar":
        mu_gs = jax.tree.leaves(bank.global_.mean)
        lg = jax.tree.leaves(bank.global_.prec)
        mu_ss = [m[sids] for m in jax.tree.leaves(bank.means)]
        ls = [p[sids] for p in jax.tree.leaves(bank.precs)]
    else:
        raise ValueError(surrogate_kind)

    out, out_r = [], []
    for i, (t, gg, rr) in enumerate(zip(leaves, gleaves, rleaves)):
        seed_c = jax.vmap(
            lambda s: jax.random.randint(s, (), 0, 2**31 - 1)
            .astype(jnp.uint32))(all_seeds[:, i])
        res = fused_update_chains_flat(
            t, gg, seed_c, h=h, scale=scale, f_s=f_s,
            prior_prec=prior_prec, alpha=alpha, temperature=temperature,
            mu_g=mu_gs[i], mu_s=mu_ss[i],
            lam_g=(jnp.asarray(lg[i], jnp.float32)
                   if lg[i] is not None else None),
            lam_s=(jnp.asarray(ls[i], jnp.float32)
                   if ls[i] is not None else None),
            momentum=rr, friction=friction, dynamics=dynamics)
        if dynamics == "sghmc":
            out.append(res[0])
            out_r.append(res[1])
        else:
            out.append(res)
    if dynamics == "sghmc":
        return (jax.tree.unflatten(treedef, out),
                jax.tree.unflatten(treedef, out_r))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# packed single-launch chain-state layout (PR 2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedChains:
    """STATIC layout of a whole parameter pytree packed into one chain-major
    (C * rows_total, 128) fp32 buffer.

    Leaf l owns rows [row_offsets[l], row_offsets[l] + rows[l]) of every
    chain's segment; its first ``sizes[l]`` elements are live, the tail up
    to ``rows[l] * 128`` is pad (written by the kernel, never read back).
    ``leaf_blocks`` (blocks per leaf in each chain's segment) is all the
    packed kernel needs to work out each block's leaf and in-leaf element
    offset — that offset is what keeps the in-kernel noise stream
    bit-identical to the per-leaf kernel. Hashable (all-tuple) so it can
    key jit caches.
    """
    treedef: Any
    shapes: tuple
    dtypes: tuple
    sizes: tuple          # live element count per leaf
    rows: tuple           # padded row count per leaf (block_rows multiple)
    row_offsets: tuple    # first row of each leaf inside a chain segment
    rows_total: int
    block_rows: int

    @property
    def num_leaves(self) -> int:
        return len(self.shapes)

    @property
    def leaf_blocks(self) -> tuple:
        """Blocks each leaf owns in a chain's segment."""
        return tuple(r // self.block_rows for r in self.rows)

    def pack(self, tree: PyTree) -> jax.Array:
        """Leaves (C, *shape) -> (C * rows_total, 128) fp32, chain-major.
        Pure update-slices into a zero buffer: no ``pad`` primitive, so
        packing can sit inside a scanned round body without tripping the
        no-pad jaxpr gate (it is still hoisted out of the step loop)."""
        leaves, treedef = jax.tree.flatten(tree)
        assert treedef == self.treedef, (treedef, self.treedef)
        c = leaves[0].shape[0]
        buf = jnp.zeros((c, self.rows_total * LANE), jnp.float32)
        for leaf, off, n in zip(leaves, self.row_offsets, self.sizes):
            buf = jax.lax.dynamic_update_slice(
                buf, leaf.reshape(c, n).astype(jnp.float32),
                (0, off * LANE))
        return buf.reshape(c * self.rows_total, LANE)

    def pack_shared(self, tree: PyTree) -> jax.Array:
        """Chain-free pytree (global surrogate) -> (rows_total, 128)."""
        return self.pack(jax.tree.map(lambda t: t[None], tree))

    def unpack(self, buf: jax.Array) -> PyTree:
        """(C * rows_total, 128) -> leaves (C, *shape) in original dtypes."""
        flat = buf.reshape(-1, self.rows_total * LANE)
        c = flat.shape[0]
        leaves = []
        for shape, dt, off, n in zip(self.shapes, self.dtypes,
                                     self.row_offsets, self.sizes):
            seg = jax.lax.slice(flat, (0, off * LANE), (c, off * LANE + n))
            leaves.append(seg.reshape((c,) + shape).astype(dt))
        return jax.tree.unflatten(self.treedef, leaves)

    @property
    def all_fp32(self) -> bool:
        return all(dt == jnp.float32 for dt in self.dtypes)

    def quantize(self, buf: jax.Array) -> jax.Array:
        """Per-step storage-dtype round-trip for non-fp32 leaves.

        The packed buffer carries fp32 state across steps, but the
        per-leaf kernel path casts each leaf back to its own dtype at
        every step end (``fused_update_flat``'s ``astype(orig_dtype)``)
        and re-widens it on the next step. Replaying that round-trip
        (fp32 -> leaf dtype -> fp32) on each non-fp32 leaf's row segment
        keeps the packed executor bit-identical to the per-leaf path —
        and to the ``run_vmap`` oracle — for bf16/fp16 parameter leaves.
        Identity (the SAME array, zero ops) when every leaf is fp32;
        static slices + update-slices otherwise, so it can sit inside a
        scanned round body without tripping the no-pad jaxpr gate.
        """
        if self.all_fp32:
            return buf
        flat = buf.reshape(-1, self.rows_total * LANE)
        c = flat.shape[0]
        for dt, off, r in zip(self.dtypes, self.row_offsets, self.rows):
            if dt == jnp.float32:
                continue
            seg = jax.lax.slice(flat, (0, off * LANE),
                                (c, (off + r) * LANE))
            seg = seg.astype(dt).astype(jnp.float32)
            flat = jax.lax.dynamic_update_slice(flat, seg, (0, off * LANE))
        return flat.reshape(buf.shape)


def _leaf_rows(sizes, block_rows: int) -> tuple:
    """Rows each leaf owns when padded to whole (block_rows, 128) blocks."""
    per_block = block_rows * LANE
    return tuple(-(-n // per_block) * block_rows for n in sizes)


def choose_block_rows(sizes) -> int:
    """The packed grid's block height for leaves of ``sizes`` elements: the
    largest power of two from PACK_BLOCK_ROWS to PACK_BLOCK_ROWS_MAX whose
    leaf padding adds at most 1/256 of the rows the PACK_BLOCK_ROWS layout
    holds. Every grid step pays a fixed cost (~0.35 us on a v5e), so a tree
    of large leaves wants tall blocks; a tree of small, ragged leaves
    would pad into a larger buffer, and keeps short ones."""
    floor = sum(_leaf_rows(sizes, PACK_BLOCK_ROWS))
    best, b = PACK_BLOCK_ROWS, PACK_BLOCK_ROWS
    while b < PACK_BLOCK_ROWS_MAX:
        b *= 2
        if (sum(_leaf_rows(sizes, b)) - floor) * 256 <= floor:
            best = b
    return best


def make_packed_layout(theta: PyTree,
                       block_rows: Optional[int] = None) -> PackedChains:
    """Build the packed layout from a SINGLE-chain example pytree (shapes
    without the leading chain axis). ``block_rows`` defaults to
    ``choose_block_rows`` of the leaf sizes."""
    leaves, treedef = jax.tree.flatten(theta)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = tuple(int(l.size) for l in leaves)
    if block_rows is None:
        block_rows = choose_block_rows(sizes)
    rows = _leaf_rows(sizes, block_rows)
    row_offsets, acc = [], 0
    for r in rows:
        row_offsets.append(acc)
        acc += r
    return PackedChains(
        treedef=treedef, shapes=shapes, dtypes=dtypes, sizes=sizes,
        rows=rows, row_offsets=tuple(row_offsets), rows_total=acc,
        block_rows=block_rows)


def chain_leaf_seeds(keys: jax.Array, num_leaves: int) -> jax.Array:
    """(C, 2) per-chain step keys -> (C, L) uint32 per-(chain, leaf) seeds,
    derived EXACTLY as ``fused_update_chains_tree`` derives them (split the
    chain key into L leaf keys, draw one int31 per leaf) so packed and
    per-leaf kernels consume identical noise streams."""
    all_seeds = jax.vmap(lambda k: jax.random.split(k, num_leaves))(keys)
    draw = lambda s: jax.random.randint(  # noqa: E731 - mirrors per-leaf path
        s, (), 0, 2**31 - 1).astype(jnp.uint32)
    return jax.vmap(jax.vmap(draw))(all_seeds)


def packed_scalar_rows(layout: PackedChains, *, h, scale, f_s, prior_prec,
                       alpha, temperature, lam_g_leaf=None,
                       lam_s_leaf=None, friction=0.0) -> jax.Array:
    """Prebuild the (C, L, SCALAR_COLS) scalar-operand rows for a whole
    round: scale and f_s vary per chain (resident client), lam_g/lam_s
    vary per leaf in the 'scalar' surrogate variant ((L,) global / (C, L)
    resident scalar precisions); friction is the SGHMC alpha_f (dead for
    langevin dynamics); everything else broadcasts."""
    C = scale.shape[0]
    L = layout.num_leaves
    col = lambda v: jnp.broadcast_to(  # noqa: E731
        jnp.asarray(v, jnp.float32), (C, L))
    lamg = col(0.0) if lam_g_leaf is None \
        else jnp.broadcast_to(lam_g_leaf[None].astype(jnp.float32), (C, L))
    lams = col(0.0) if lam_s_leaf is None \
        else lam_s_leaf.astype(jnp.float32)
    return jnp.stack([
        col(h), col(scale[:, None]), col(f_s[:, None]), col(prior_prec),
        col(alpha), col(temperature), lamg, lams, col(friction)], axis=-1)


def packed_step(layout: PackedChains, theta_p: jax.Array, g_p: jax.Array,
                seeds: jax.Array, scalars: jax.Array, *, variant: str,
                mu_g=None, mu_s=None, lam_g=None, lam_s=None, r_p=None,
                dynamics: str = "langevin",
                interpret: Optional[bool] = None):
    """ONE pallas_call updating every leaf of every chain in the block.

    theta_p/g_p/mu_s/lam_s (and ``r_p``, the packed momenta, for
    ``dynamics='sghmc'``): (C * rows_total, 128) packed buffers;
    mu_g/lam_g: (rows_total, 128) packed global surrogate (re-read per
    chain by the kernel's shared BlockSpec); seeds: (C, L) uint32 from
    ``chain_leaf_seeds``; scalars: (C, L, SCALAR_COLS) from
    ``packed_scalar_rows``. Returns theta_p' or (theta_p', r_p').
    """
    interpret = _interpret(interpret)
    C = seeds.shape[0]
    return fsgld_update_packed(
        theta_p, g_p, seeds, scalars, variant=variant, dynamics=dynamics,
        r2d=r_p, mu_g=mu_g, mu_s=mu_s, lam_g=lam_g, lam_s=lam_s,
        leaf_blocks=layout.leaf_blocks, block_rows=layout.block_rows,
        chains=C, interpret=interpret)


def fused_update_tree(theta: PyTree, g: PyTree, key: jax.Array, *, h, scale,
                      f_s=1.0, prior_prec=0.0, alpha=0.0, temperature=1.0,
                      q_global=None, q_shard=None,
                      surrogate_kind: Optional[str] = None,
                      momentum: Optional[PyTree] = None, friction=0.0,
                      dynamics: str = "langevin"):
    """Apply the fused update across a parameter pytree.

    q_global/q_shard: repro.core.surrogate.Gaussian with 'diag' (flat-vector
    params) or 'scalar' (pytree means + per-leaf scalar precisions)
    structure, or None for SGLD/DSGLD. ``dynamics='sghmc'`` takes the
    ``momentum`` pytree and returns the (theta', momentum') pair; leaf
    seeds are derived identically for both dynamics (split the step key
    per leaf, one int31 draw each).
    """
    leaves, treedef = jax.tree.flatten(theta)
    gleaves = jax.tree.leaves(g)
    rleaves = (jax.tree.leaves(momentum) if momentum is not None
               else [None] * len(leaves))
    seeds = jax.random.split(key, len(leaves))

    if q_global is None:
        mu_gs = mu_ss = lg = ls = [None] * len(leaves)
    elif surrogate_kind == "diag":
        assert len(leaves) == 1, "diag surrogates operate on flat vectors"
        mu_gs, mu_ss = [q_global.mean], [q_shard.mean]
        lg, ls = [q_global.prec], [q_shard.prec]
    elif surrogate_kind == "scalar":
        mu_gs = jax.tree.leaves(q_global.mean)
        mu_ss = jax.tree.leaves(q_shard.mean)
        lg = jax.tree.leaves(q_global.prec)
        ls = jax.tree.leaves(q_shard.prec)
    else:
        raise ValueError(surrogate_kind)

    out, out_r = [], []
    for i, (t, gg, rr) in enumerate(zip(leaves, gleaves, rleaves)):
        seed = jax.random.randint(seeds[i], (), 0, 2**31 - 1).astype(
            jnp.uint32)
        res = fused_update_flat(
            t, gg, seed, h=h, scale=scale, f_s=f_s, prior_prec=prior_prec,
            alpha=alpha, temperature=temperature, mu_g=mu_gs[i],
            mu_s=mu_ss[i],
            lam_g=(jnp.asarray(lg[i], jnp.float32)
                   if lg[i] is not None else None),
            lam_s=(jnp.asarray(ls[i], jnp.float32)
                   if ls[i] is not None else None),
            momentum=rr, friction=friction, dynamics=dynamics)
        if dynamics == "sghmc":
            out.append(res[0])
            out_r.append(res[1])
        else:
            out.append(res)
    if dynamics == "sghmc":
        return (jax.tree.unflatten(treedef, out),
                jax.tree.unflatten(treedef, out_r))
    return jax.tree.unflatten(treedef, out)
