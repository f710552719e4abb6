"""Partition rules: map parameter / activation pytrees to PartitionSpecs.

Mesh axes (launch/mesh.py):
    single pod : ("data", "model") = (16, 16)
    multi-pod  : ("pod", "data", "model") = (2, 16, 16)

Policy (DESIGN.md Sec 4):
  * "model"  — tensor parallel: heads / d_ff / vocab.
  * "data"   — the FEDERATED axis: batch sharding AND FSDP for params.
               Each data-group is one logical client shard.
  * "pod"    — pure data parallel across pods (params replicated over pod;
               gradients all-reduce over it). Batch shards over (pod, data).

Rules are name-based over the param dict keys produced by models/model.py.
Dims that don't divide the axis size fall back to replication for that dim
(whisper's 20 heads / 51866 vocab on a 16-way model axis) — recorded by the
caller for DESIGN.md notes.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# param-name -> (dim -> logical axis); logical axes: 'fsdp' | 'mdl' | None
_RULES = {
    # embeddings / head
    "embed": ("mdl", "fsdp"),
    "head": ("fsdp", "mdl"),
    # attention
    "wq": ("fsdp", "mdl"),
    "wk": ("fsdp", "mdl"),
    "wv": ("fsdp", "mdl"),
    "wo": ("mdl", "fsdp"),
    # dense ffn
    "wi_gate": ("fsdp", "mdl"),
    "wi_up": ("fsdp", "mdl"),
    # moe
    "router": ("fsdp", None),
    "experts_wi_gate": (None, "fsdp", "mdl"),
    "experts_wi_up": (None, "fsdp", "mdl"),
    "experts_wo": (None, "mdl", "fsdp"),
    # rglru
    "w_x": ("fsdp", "mdl"),
    "w_gate": ("fsdp", "mdl"),
    "w_out": ("mdl", "fsdp"),
    "w_rec": ("fsdp", "mdl"),
    "w_inp": ("fsdp", "mdl"),
    "conv_w": (None, "mdl"),
    "lam": ("mdl",),
    # rwkv
    "w_r": ("fsdp", "mdl"),
    "w_k": ("fsdp", "mdl"),
    "w_v": ("fsdp", "mdl"),
    "w_o": ("mdl", "fsdp"),
    "w_lora_a": ("fsdp", None),
    "w_lora_b": (None, None),
    "u": ("mdl", None),
}

# ffn 'wo' is (F, D) -> ('mdl', 'fsdp'); attention 'wo' is (q_dim, D) ->
# same rule, so one entry suffices.


def logical_axes(mesh: Mesh):
    """Resolve logical axis names to mesh axes for this mesh."""
    axes = {"mdl": "model", "fsdp": "data"}
    batch = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return axes, batch


def _leaf_spec(path, leaf, mesh: Mesh, axes) -> P:
    name = None
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            name = str(entry.key)
            break
    rule = _RULES.get(name)
    shape = leaf.shape
    if rule is None:
        return P()  # norms, scalars, mix vectors, gates: replicate
    # stacked layer dims (scan) prepend extra leading axes: right-align rule
    offset = len(shape) - len(rule)
    spec = [None] * len(shape)
    if offset < 0:  # e.g. (1,)-shaped gate param hit a 2-D rule: replicate
        return P()
    for i, ax in enumerate(rule):
        if ax is None:
            continue
        mesh_axis = axes[ax]
        if mesh_axis is None or mesh_axis not in mesh.shape:
            continue  # axis disabled (e.g. serving layout drops 'fsdp')
        size = mesh.shape[mesh_axis]
        if shape[offset + i] % size == 0:
            spec[offset + i] = mesh_axis
        # else: leave replicated on that dim (uneven; e.g. whisper heads)
    return P(*spec)


def param_specs(params: PyTree, mesh: Mesh, *, serve: bool = False,
                serve_hbm_budget: float = 8 * 2**30) -> PyTree:
    """serve=True applies the SERVING layout: when the whole model (bf16)
    fits per device with model-axis-only sharding, the FSDP ('data') axis
    is dropped — weights stay resident and only (tiny) decode activations
    cross the ICI, instead of re-all-gathering every weight every token
    step (§Perf iteration 3). Models too big for that (grok, vision-90b)
    keep the 2-D layout."""
    axes, _ = logical_axes(mesh)
    if serve:
        total_bf16 = sum(
            int(np.prod(l.shape)) * 2 for l in jax.tree.leaves(params))
        if total_bf16 / mesh.shape["model"] <= serve_hbm_budget:
            axes = dict(axes, fsdp=None)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_spec(p, l, mesh, axes), params)


def param_shardings(params: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh))


def batch_specs(batch: PyTree, mesh: Mesh) -> PyTree:
    """Shard the leading (global batch) dim over (pod?, data), when it
    divides; otherwise replicate (long_500k has batch 1)."""
    _, baxes = logical_axes(mesh)
    bsize = int(np.prod([mesh.shape[a] for a in baxes]))

    def spec(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] % bsize == 0:
            return P(baxes)
        return P()
    return jax.tree.map(spec, batch)


def cache_specs(cache: PyTree, mesh: Mesh) -> PyTree:
    """KV caches / recurrent states: (layers, B, ...) — batch on dim 1 for
    stacked block caches, dim 0 for remainder-layer caches. We detect the
    stacked case by path prefix 'blocks'."""
    _, baxes = logical_axes(mesh)
    bsize = int(np.prod([mesh.shape[a] for a in baxes]))

    def leaf_spec(path, leaf):
        top = str(path[0].key) if isinstance(path[0],
                                             jax.tree_util.DictKey) else ""
        bdim = 1 if top == "blocks" else 0
        spec = [None] * leaf.ndim
        if leaf.ndim > bdim and leaf.shape[bdim] % bsize == 0:
            spec[bdim] = baxes
        # shard kv-heads / rwkv heads over model when they divide
        name = str(path[-1].key) if isinstance(path[-1],
                                               jax.tree_util.DictKey) else ""
        if name in ("k", "v") and leaf.ndim == bdim + 4:
            kdim, sdim = bdim + 2, bdim + 1
            if leaf.shape[kdim] % mesh.shape["model"] == 0:
                spec[kdim] = "model"
            elif leaf.shape[sdim] % mesh.shape["model"] == 0:
                # GQA kv-heads < model axis: shard the cache SEQ dim instead
                # (32k/16 = 2k per device; attention reduces over it with a
                # distributed softmax the compiler lowers to all-reduces).
                spec[sdim] = "model"
        if name == "S" and leaf.ndim == bdim + 4:  # rwkv state (B,H,hd,hd)
            hdim = bdim + 1
            if leaf.shape[hdim] % mesh.shape["model"] == 0:
                spec[hdim] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


def surrogate_specs(params_specs: PyTree) -> PyTree:
    """Surrogate means shard exactly like the params they mirror; scalar
    precisions replicate."""
    return params_specs


# ---------------------------------------------------------------------------
# chain-parallel (federated) layout: the mesh chain runtime (core/engine.py)
# and the large-model federated round (launch/steps.py) both place chains
# along the 'data' axis — one source of truth for that convention here.
# ---------------------------------------------------------------------------

CHAIN_AXIS = "data"


def chain_spec() -> P:
    """PartitionSpec prefix placing a leading chain axis on 'data'."""
    return P(CHAIN_AXIS)


def packed_chain_spec() -> P:
    """Layout CONVENTION for PACKED chain-state buffers
    (kernels.ops.PackedChains), recorded for the launch/steps.py
    migration onto the chain engine (ROADMAP open item). Today nothing
    uses it: packed buffers are created and consumed entirely INSIDE the
    engine's shard_map block and never cross a sharding boundary. When
    one does, this is its spec: the (C * rows_total, 128) row axis is
    CHAIN-MAJOR, so sharding dim 0 over the chain axis keeps every
    chain's whole segment on one data group — the same placement the
    unpacked (C, ...) tree gets from ``chain_spec`` (requires
    C % |data| == 0, which the engine already enforces). EVERY
    chain-major segment buffer of the multi-segment state shares this
    spec — the SGHMC momentum buffer rides the same packed layout and
    the same chain-major row order as the parameter buffer."""
    return P(CHAIN_AXIS, None)


def stream_window_spec() -> P:
    """Spec for streamed-client WINDOW operands (core/engine.py's
    ``stream=`` path): the resident client-id vector and the (K,)
    sizes/probs metadata rows, plus the (K, max_n, ...) resident shard
    data, are all REPLICATED — every data group must see the same resident
    window because any chain can be reassigned to any resident client
    within it (the same reason the full (S, ...) shard stack replicates on
    the resident path). The chain axis stays on 'data'; streaming changes
    WHICH client rows are on device, never how chains are placed."""
    return P()


def fed_carry_spec() -> P:
    """Spec for the engine's federated-round carry: the resident sids
    (C,) and every compression-state row — server-view reference,
    primal error feedback, dual error feedback — are PER-CHAIN (C,) /
    (C, P) arrays, so they shard over 'data' exactly like the chain
    states they mirror. The FA-LD server average is the one cross-chain
    reduction over this carry, and it runs as an in-scan masked psum
    over the same axis rather than a relayout."""
    return P(CHAIN_AXIS)


def chain_specs(tree: PyTree) -> PyTree:
    """Per-leaf chain-axis specs for a pytree of (C, ...) chain states."""
    return jax.tree.map(lambda _: P(CHAIN_AXIS), tree)


def chain_shardings(tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda _: NamedSharding(mesh, P(CHAIN_AXIS)), tree)


# ---------------------------------------------------------------------------
# ensemble-serving layout: K posterior draws served as a batched ensemble.
# The draw axis rides the SAME mesh axis the chains sampled on ('data') —
# a K-draw serving fleet is placed exactly like a K-chain sampling run, so
# the streaming chain→server path hands draws across without relayout.
# Params, decode caches, and recurrent states all lead with (K, ...);
# within a draw the serving layout (param_specs(serve=True)) still
# applies on 'model'.
# ---------------------------------------------------------------------------

ENSEMBLE_AXIS = CHAIN_AXIS


def ensemble_spec() -> P:
    """PartitionSpec prefix placing a leading draw axis on 'data'."""
    return P(ENSEMBLE_AXIS)


def ensemble_specs(tree: PyTree) -> PyTree:
    """Per-leaf draw-axis specs for (K, ...) stacked draws / caches."""
    return jax.tree.map(lambda _: P(ENSEMBLE_AXIS), tree)


def ensemble_shardings(tree: PyTree, mesh: Mesh) -> PyTree:
    """NamedShardings for a stacked-draw tree; requires
    K % mesh.shape['data'] == 0 (callers fall back to replication
    otherwise — an uneven ensemble never crashes the server)."""
    return jax.tree.map(
        lambda _: NamedSharding(mesh, P(ENSEMBLE_AXIS)), tree)
