"""Scan-over-layers language models for all six assigned families.

Parameters are plain nested dicts. The layer stack is grouped into repeating
*periods* (``cfg.layer_pattern``); all full periods are stacked along a
leading axis and applied with ``jax.lax.scan`` so HLO size / compile time are
depth-independent (essential for the 100-layer x 512-device dry-run on this
CPU container). Remainder layers (when num_layers % period != 0) are applied
unscanned.

Modes:
  forward(params, cfg, tokens, ...)      -> (hidden, aux)   train / prefill
  loss_fn(params, cfg, batch)            -> scalar sum log-lik (+ aux)
  init_cache / decode_step               -> single-token serving
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L

ACT_DTYPE = jnp.bfloat16


def _shard_batch(x):
    """Anchor activation sharding: batch over (pod?, data), rest replicated.
    Without this anchor GSPMD drops batch sharding at the remat+scan
    boundary and silently replicates whole-layer compute on every device
    (16-64x redundant flops — caught by the roofline analyzer).

    Acts only under a ``jax.set_mesh`` context (the dry-run) and only on
    its ``Auto`` axes: inside the chain engine's ``shard_map`` the axes are
    ``Manual`` and each device already holds its own chain block."""
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto}
    baxes = tuple(a for a in ("pod", "data") if a in auto)
    if not baxes or x.shape[0] % \
            int(np.prod([mesh.shape[a] for a in baxes])) != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(baxes, *([None] * (x.ndim - 1))))


def _cast_floating(tree, dtype=ACT_DTYPE):
    """Cast float leaves to the compute dtype at point-of-use. Master params
    stay fp32 (the sampler needs fp32 Langevin updates); doing the cast
    *inside* the layer scan keeps the FSDP all-gathers in bf16."""
    def cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(cast, tree)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _norm_init(d):
    return jnp.zeros((d,), jnp.float32)


def _dense_init(key, fan_in, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _init_ffn(key, cfg: ArchConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    if cfg.moe is not None:
        e = cfg.moe.num_experts
        p = {"router": _dense_init(ks[0], d, (d, e), jnp.float32),
             "experts_wo": _dense_init(ks[1], f, (e, f, d), dtype)}
        if cfg.ffn_type in ("silu", "geglu"):
            p["experts_wi_gate"] = _dense_init(ks[2], d, (e, d, f), dtype)
            p["experts_wi_up"] = _dense_init(ks[3], d, (e, d, f), dtype)
        else:
            p["experts_wi_up"] = _dense_init(ks[2], d, (e, d, f), dtype)
        return p
    p = {"wo": _dense_init(ks[1], f, (f, d), dtype)}
    if cfg.ffn_type in ("silu", "geglu"):
        p["wi_gate"] = _dense_init(ks[2], d, (d, f), dtype)
        p["wi_up"] = _dense_init(ks[3], d, (d, f), dtype)
    else:
        p["wi_up"] = _dense_init(ks[2], d, (d, f), dtype)
    return p


def _init_attn(key, cfg: ArchConfig, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    qd, kvd = cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {"wq": _dense_init(ks[0], d, (d, qd), dtype),
         "wk": _dense_init(ks[1], d, (d, kvd), dtype),
         "wv": _dense_init(ks[2], d, (d, kvd), dtype),
         "wo": _dense_init(ks[3], qd, (qd, d), dtype)}
    if cfg.qk_norm and not cross:
        p["q_norm"] = _norm_init(hd)
        p["k_norm"] = _norm_init(hd)
    return p


def _init_layer(key, kind: str, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 6)
    p = {"norm": _norm_init(cfg.d_model), "ffn_norm": _norm_init(cfg.d_model),
         "ffn": _init_ffn(ks[0], cfg, dtype)}
    d = cfg.d_model
    if kind in ("attn", "swa"):
        p["attn"] = _init_attn(ks[1], cfg, dtype)
    elif kind == "xattn" and cfg.family == "vlm":
        p["xattn"] = _init_attn(ks[1], cfg, dtype, cross=True)
        p["xattn"]["gate"] = jnp.zeros((1,), jnp.float32)
        p["xnorm"] = _norm_init(d)
    elif kind == "xattn":  # audio decoder layer: self-attn + cross-attn
        p["attn"] = _init_attn(ks[1], cfg, dtype)
        p["xattn"] = _init_attn(ks[2], cfg, dtype, cross=True)
        p["xnorm"] = _norm_init(d)
    elif kind == "rglru":
        p["rec"] = {
            "w_x": _dense_init(ks[1], d, (d, d), dtype),
            "w_gate": _dense_init(ks[2], d, (d, d), dtype),
            "w_out": _dense_init(ks[3], d, (d, d), dtype),
            "conv_w": _dense_init(ks[4], 4, (4, d), dtype),
            "w_rec": _dense_init(ks[5], d, (d, d), jnp.float32),
            "w_inp": _dense_init(ks[0], d, (d, d), jnp.float32),
            "lam": jnp.full((d,), 0.5, jnp.float32),
        }
    elif kind == "rwkv":
        H, hd = cfg.num_heads, cfg.head_dim
        lora = 64
        p["mix"] = {
            "mu_r": jnp.full((d,), 0.5, jnp.float32),
            "mu_k": jnp.full((d,), 0.5, jnp.float32),
            "mu_v": jnp.full((d,), 0.5, jnp.float32),
            "mu_w": jnp.full((d,), 0.5, jnp.float32),
            "w_r": _dense_init(ks[1], d, (d, H * hd), dtype),
            "w_k": _dense_init(ks[2], d, (d, H * hd), dtype),
            "w_v": _dense_init(ks[3], d, (d, H * hd), dtype),
            "w_o": _dense_init(ks[4], H * hd, (H * hd, d), dtype),
            "w0": jnp.full((d,), -1.0, jnp.float32),
            "w_lora_a": _dense_init(ks[5], d, (d, lora), jnp.float32),
            "w_lora_b": _dense_init(ks[0], lora, (lora, d), jnp.float32),
            "u": jnp.zeros((H, hd), jnp.float32),
        }
    else:
        raise ValueError(kind)
    return p


def _period_kinds(cfg: ArchConfig):
    pat = cfg.layer_pattern
    n_full = cfg.num_layers // len(pat)
    rem = cfg.num_layers % len(pat)
    return pat, n_full, pat[:rem]


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    pat, n_full, rem = _period_kinds(cfg)
    k_emb, k_head, k_blocks, k_rem, k_enc = jax.random.split(key, 5)

    def init_period(k):
        ks = jax.random.split(k, len(pat))
        return {f"l{i}": _init_layer(ks[i], kind, cfg, dtype)
                for i, kind in enumerate(pat)}

    params = {
        "embed": _dense_init(k_emb, cfg.d_model, (cfg.vocab_size, cfg.d_model),
                             dtype),
        "blocks": jax.vmap(init_period)(jax.random.split(k_blocks, n_full)),
        "final_norm": _norm_init(cfg.d_model),
        "head": _dense_init(k_head, cfg.d_model,
                            (cfg.d_model, cfg.vocab_size), dtype),
    }
    if rem:
        ks = jax.random.split(k_rem, len(rem))
        params["rem_blocks"] = {f"l{i}": _init_layer(ks[i], kind, cfg, dtype)
                                for i, kind in enumerate(rem)}
    if cfg.encoder_layers:
        enc_cfg = cfg
        ks = jax.random.split(k_enc, cfg.encoder_layers)

        def init_enc_layer(k):
            p = _init_layer(k, "attn", enc_cfg, dtype)
            return p
        params["encoder"] = {
            "blocks": jax.vmap(init_enc_layer)(ks),
            "final_norm": _norm_init(cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# layer application (full-sequence mode)
# ---------------------------------------------------------------------------

def _self_attn(x, p, cfg: ArchConfig, positions, *, window=None,
               causal=True):
    B, S, _ = x.shape
    h = L.rms_norm(x, p["norm"])
    q = (h @ p["attn"]["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (h @ p["attn"]["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["attn"]["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["attn"]["q_norm"])
        k = L.rms_norm(k, p["attn"]["k_norm"])
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    o = L.chunked_attention(q, k, v, q_positions=positions,
                            kv_positions=positions, causal=causal,
                            window=window)
    return x + o.reshape(B, S, -1) @ p["attn"]["wo"]


def _cross_attn(x, p, cfg: ArchConfig, enc_out, gated: bool):
    B, S, _ = x.shape
    Te = enc_out.shape[1]
    h = L.rms_norm(x, p["xnorm"])
    xp = p["xattn"]
    q = (h @ xp["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (enc_out @ xp["wk"]).reshape(B, Te, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ xp["wv"]).reshape(B, Te, cfg.num_kv_heads, cfg.head_dim)
    qpos = jnp.zeros((B, S), jnp.int32)
    kpos = jnp.zeros((B, Te), jnp.int32)
    o = L.chunked_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                            causal=False)
    o = o.reshape(B, S, -1) @ xp["wo"]
    if gated:
        o = jnp.tanh(xp["gate"]).astype(o.dtype) * o
    return x + o


def _ffn_residual(x, p, cfg: ArchConfig):
    h = L.rms_norm(x, p["ffn_norm"])
    if cfg.moe is not None:
        y, aux = L.moe_ffn(h, p["ffn"], top_k=cfg.moe.top_k,
                           ffn_type=cfg.ffn_type,
                           capacity_factor=cfg.moe.capacity_factor)
        return x + y, aux
    return x + L.ffn_apply(h, p["ffn"], cfg.ffn_type), jnp.float32(0.0)


def _apply_layer(kind: str, p, x, cfg: ArchConfig, positions, enc_out):
    if kind == "attn":
        x = _self_attn(x, p, cfg, positions)
    elif kind == "swa":
        x = _self_attn(x, p, cfg, positions, window=cfg.swa_window)
    elif kind == "xattn" and cfg.family == "vlm":
        x = _cross_attn(x, p, cfg, enc_out, gated=True)
    elif kind == "xattn":
        x = _self_attn(x, p, cfg, positions)
        x = _cross_attn(x, p, cfg, enc_out, gated=False)
    elif kind == "rglru":
        h = L.rms_norm(x, p["norm"])
        y, _ = L.rglru_forward(h, p["rec"])
        x = x + y
    elif kind == "rwkv":
        h = L.rms_norm(x, p["norm"])
        y, _ = L.rwkv_forward(h, p["mix"])
        x = x + y
    else:
        raise ValueError(kind)
    x, aux = _ffn_residual(x, p, cfg)
    return x, aux


def _apply_period(params_period, x, cfg: ArchConfig, positions, enc_out,
                  kinds):
    aux = jnp.float32(0.0)
    for i, kind in enumerate(kinds):
        x, a = _apply_layer(kind, params_period[f"l{i}"], x, cfg, positions,
                            enc_out)
        aux = aux + a
    return x, aux


def forward(params: dict, cfg: ArchConfig, tokens: jax.Array, *,
            enc_embeds: Optional[jax.Array] = None):
    """tokens: (B, S) int32. enc_embeds: stubbed modality-frontend output
    (audio frames / image patches), (B, T_enc, D), required for vlm/audio.

    Returns (hidden (B,S,D) pre-head, aux_loss scalar).
    """
    pat, n_full, rem = _period_kinds(cfg)
    B, S = tokens.shape
    x = _shard_batch(params["embed"][tokens].astype(ACT_DTYPE))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    enc_out = None
    if cfg.family == "vlm":
        enc_out = enc_embeds.astype(ACT_DTYPE)
    elif cfg.family == "audio":
        enc_out = encoder_forward(params, cfg, enc_embeds)

    def body(carry, period_params):
        x, aux = carry
        x = _shard_batch(x)
        period_params = _cast_floating(period_params)
        x, a = _apply_period(period_params, x, cfg, positions, enc_out, pat)
        return (_shard_batch(x), aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                               params["blocks"])
    for i, kind in enumerate(rem):
        x, a = _apply_layer(kind, _cast_floating(params["rem_blocks"][f"l{i}"]),
                            x, cfg, positions, enc_out)
        aux = aux + a
    x = L.rms_norm(x, params["final_norm"])
    return x, aux


def encoder_forward(params: dict, cfg: ArchConfig, enc_embeds: jax.Array):
    """Bidirectional encoder over stubbed frame embeddings (audio)."""
    B, T, _ = enc_embeds.shape
    x = enc_embeds.astype(ACT_DTYPE)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(x, p):
        x = _shard_batch(x)
        p = _cast_floating(p)
        x = _self_attn(x, p, cfg, positions, causal=False)
        x, _ = _ffn_residual(x, p, cfg)
        return _shard_batch(x), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["encoder"]["blocks"])
    return L.rms_norm(x, params["encoder"]["final_norm"])


# ---------------------------------------------------------------------------
# loss (chunked cross-entropy; log-likelihood convention for SG-MCMC)
# ---------------------------------------------------------------------------

def chunked_log_lik(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                    chunk: int = 512) -> jax.Array:
    """Sum_t log p(label_t | hidden_t). Never materialises (B,S,V): scans
    over sequence chunks (vocab up to 256k makes full logits ~33 GB/group)."""
    B, S, D = hidden.shape
    nb = L.cdiv(S, chunk)
    pad = nb * chunk - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hc = hidden.reshape(B, nb, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nb, chunk).transpose(1, 0, 2)

    def body(tot, blk):
        h, lab = blk
        logits = jnp.einsum("bsd,dv->bsv", h, head,
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0] - logz
        ll = jnp.where(lab >= 0, ll, 0.0)
        return tot + ll.sum(), None

    # NOTE (§Perf iteration 6, hypothesis REFUTED): we expected scan
    # linearization to stack the (nb,B,chunk,V) logits as backward
    # residuals; measurement shows XLA already avoids it (gemma-7b train
    # HBM unchanged at 5.58e12 B/dev with or without this checkpoint).
    # The checkpoint is kept as cheap insurance for other backends.
    tot, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0), (hc, lc))
    return tot


def log_lik_fn(params: dict, cfg: ArchConfig, batch: dict) -> jax.Array:
    """Total log-likelihood of a (mini)batch — the quantity whose gradient
    SGLD/DSGLD/FSGLD scale by N_s/(f_s m). ``batch``: tokens, labels,
    optional enc_embeds."""
    hidden, aux = forward(params, cfg, batch["tokens"],
                          enc_embeds=batch.get("enc_embeds"))
    ll = chunked_log_lik(hidden, params["head"].astype(ACT_DTYPE),
                         batch["labels"])
    # the router load-balance term enters as a likelihood *regulariser*
    return ll - 0.01 * aux * batch["tokens"].size


# ---------------------------------------------------------------------------
# cache-populating prefill (serving: one forward pass fills the decode
# cache; decode then continues token-by-token from position S)
# ---------------------------------------------------------------------------

def _prefill_layer_cache(kind: str, cfg: ArchConfig, h, p, positions,
                         x_seq_cache_len: int, carry_states):
    """Compute the decode-cache entry for one layer given its normed input
    h (B,S,D). For attention: project k/v and lay them out exactly as
    decode would have written them (ring layout for SWA)."""
    B, S, _ = h.shape
    if kind in ("attn", "swa") or (kind == "xattn"
                                   and cfg.family == "audio"):
        k = (h @ p["attn"]["wk"]).reshape(B, S, cfg.num_kv_heads,
                                          cfg.head_dim)
        v = (h @ p["attn"]["wv"]).reshape(B, S, cfg.num_kv_heads,
                                          cfg.head_dim)
        if cfg.qk_norm:
            k = L.rms_norm(k, p["attn"]["k_norm"])
        k = L.rope(k, positions, cfg.rope_theta)
        Sc = x_seq_cache_len
        if kind == "swa":
            W = min(cfg.swa_window, Sc)
            # last W positions, placed at their ring slots pos % W
            kw, vw = k[:, -W:], v[:, -W:]
            pw = positions[:, -W:]
            slots = pw % W
            kc = jnp.zeros((B, W) + k.shape[2:], k.dtype)
            vc = jnp.zeros((B, W) + v.shape[2:], v.dtype)
            pc = jnp.full((B, W), -1, jnp.int32)
            bidx = jnp.arange(B)[:, None]
            kc = kc.at[bidx, slots].set(kw)
            vc = vc.at[bidx, slots].set(vw)
            pc = pc.at[bidx, slots].set(pw)
            return {"k": kc, "v": vc, "pos": pc}
        pad = Sc - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pc = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
        return {"k": kc, "v": vc, "pos": pc.astype(jnp.int32)}
    if kind == "xattn" and cfg.family == "vlm":
        return {}
    # recurrent layers: the forward pass already produced the final state
    return carry_states


def prefill_with_cache(params: dict, cfg: ArchConfig, tokens: jax.Array,
                       cache_len: int, *,
                       enc_embeds: Optional[jax.Array] = None):
    """Forward over the prompt AND build the decode cache in one pass.

    Returns (last_logits (B,V), cache) where ``cache`` matches
    init_cache(cfg, B, cache_len) layout; decode_step continues from
    position tokens.shape[1].
    """
    pat, n_full, rem = _period_kinds(cfg)
    B, S = tokens.shape
    assert cache_len >= S
    x = _shard_batch(params["embed"][tokens].astype(ACT_DTYPE))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    enc_out = None
    if cfg.family == "vlm":
        enc_out = enc_embeds.astype(ACT_DTYPE)
    elif cfg.family == "audio":
        enc_out = encoder_forward(params, cfg, enc_embeds)

    def apply_and_cache(kind, p, x):
        h = L.rms_norm(x, p["norm"])
        states = None
        if kind == "rglru":
            y, h_last = L.rglru_forward(h, p["rec"])
            # conv history: last W-1 inputs of the conv
            xin = h @ p["rec"]["w_x"]
            Wc = p["rec"]["conv_w"].shape[0]
            hist = jnp.pad(xin, ((0, 0), (Wc - 1, 0), (0, 0)))[:, -(Wc - 1):]
            states = {"h": h_last, "conv": hist.astype(ACT_DTYPE)}
            x = x + y
        elif kind == "rwkv":
            y, st = L.rwkv_forward(h, p["mix"])
            states = {"S": st["S"],
                      "x_prev": st["x_prev"].astype(ACT_DTYPE)}
            x = x + y
        else:
            x, _ = (
                (_self_attn(x, p, cfg, positions,
                            window=cfg.swa_window if kind == "swa"
                            else None), None)
                if kind in ("attn", "swa") else (x, None))
            if kind == "xattn" and cfg.family == "vlm":
                x = _cross_attn(x, p, cfg, enc_out, gated=True)
            elif kind == "xattn":
                x = _self_attn(x, p, cfg, positions)
                x = _cross_attn(x, p, cfg, enc_out, gated=False)
        cache = _prefill_layer_cache(kind, cfg, h, p, positions, cache_len,
                                     states)
        x, _ = _ffn_residual(x, p, cfg)
        return x, cache

    def body(x, period_params):
        period_params = _cast_floating(period_params)
        caches = {}
        for i, kind in enumerate(pat):
            x, c = apply_and_cache(kind, period_params[f"l{i}"], x)
            caches[f"l{i}"] = c
        return _shard_batch(x), caches

    x, blocks_cache = jax.lax.scan(body, x, params["blocks"])
    cache = {"blocks": blocks_cache}
    if rem:
        rb = {}
        for i, kind in enumerate(rem):
            x, c = apply_and_cache(
                kind, _cast_floating(params["rem_blocks"][f"l{i}"]), x)
            rb[f"l{i}"] = c
        cache["rem_blocks"] = rb
    x = L.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bd,dv->bv", x[:, -1],
                        params["head"].astype(ACT_DTYPE),
                        preferred_element_type=jnp.float32)
    return logits, cache


# ---------------------------------------------------------------------------
# decode (single-token serving step)
# ---------------------------------------------------------------------------

def _layer_cache(kind: str, cfg: ArchConfig, batch: int, seq_len: int,
                 dtype):
    hd, K = cfg.head_dim, cfg.num_kv_heads
    if kind == "attn":
        S = seq_len
        return {"k": jnp.zeros((batch, S, K, hd), dtype),
                "v": jnp.zeros((batch, S, K, hd), dtype),
                "pos": jnp.full((batch, S), -1, jnp.int32)}
    if kind == "swa":
        W = min(cfg.swa_window, seq_len)
        return {"k": jnp.zeros((batch, W, K, hd), dtype),
                "v": jnp.zeros((batch, W, K, hd), dtype),
                "pos": jnp.full((batch, W), -1, jnp.int32)}
    if kind == "xattn" and cfg.family == "vlm":
        return {}
    if kind == "xattn":  # audio: self-attention cache
        S = seq_len
        return {"k": jnp.zeros((batch, S, K, hd), dtype),
                "v": jnp.zeros((batch, S, K, hd), dtype),
                "pos": jnp.full((batch, S), -1, jnp.int32)}
    if kind == "rglru":
        return L.rglru_init_state(batch, cfg.d_model, 4, dtype)
    if kind == "rwkv":
        return L.rwkv_init_state(batch, cfg.num_heads, cfg.head_dim,
                                 cfg.d_model, dtype)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               dtype=ACT_DTYPE) -> dict:
    pat, n_full, rem = _period_kinds(cfg)

    def one_period(_):
        return {f"l{i}": _layer_cache(kind, cfg, batch, seq_len, dtype)
                for i, kind in enumerate(pat)}

    cache = {"blocks": jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape).copy()
        if n_full else x, one_period(0))}
    # stacked leading dim == n_full periods
    if rem:
        cache["rem_blocks"] = {
            f"l{i}": _layer_cache(kind, cfg, batch, seq_len, dtype)
            for i, kind in enumerate(rem)}
    return cache


def _update_kv(cache, k_new, v_new, pos, ring: bool):
    """k_new/v_new: (B,1,K,hd); pos: (B,) absolute position."""
    S = cache["k"].shape[1]
    slot = (pos % S) if ring else jnp.minimum(pos, S - 1)

    def upd(buf, s, new):
        return jax.lax.dynamic_update_slice(buf, new, (s, 0, 0))

    k = jax.vmap(upd)(cache["k"], slot, k_new)
    v = jax.vmap(upd)(cache["v"], slot, v_new)
    posbuf = jax.vmap(lambda b, s, p: b.at[s].set(p))(cache["pos"], slot, pos)
    return {"k": k, "v": v, "pos": posbuf}


def _decode_self_attn(x, p, cfg: ArchConfig, cache, pos, *, ring):
    B = x.shape[0]
    h = L.rms_norm(x, p["norm"])
    q = (h @ p["attn"]["wq"]).reshape(B, 1, cfg.num_heads, cfg.head_dim)
    k = (h @ p["attn"]["wk"]).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["attn"]["wv"]).reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["attn"]["q_norm"])
        k = L.rms_norm(k, p["attn"]["k_norm"])
    q = L.rope(q, pos[:, None], cfg.rope_theta)
    k = L.rope(k, pos[:, None], cfg.rope_theta)
    cache = _update_kv(cache, k.astype(cache["k"].dtype),
                       v.astype(cache["v"].dtype), pos, ring)
    o = L.decode_attention(q, cache["k"], cache["v"], cache["pos"], pos)
    return x + o.reshape(B, 1, -1) @ p["attn"]["wo"], cache


def _decode_layer(kind: str, p, x, cfg: ArchConfig, cache, pos, enc_out):
    if kind == "attn":
        x, cache = _decode_self_attn(x, p, cfg, cache, pos, ring=False)
    elif kind == "swa":
        x, cache = _decode_self_attn(x, p, cfg, cache, pos, ring=True)
    elif kind == "xattn" and cfg.family == "vlm":
        x = _cross_attn(x, p, cfg, enc_out, gated=True)
    elif kind == "xattn":
        x, cache = _decode_self_attn(x, p, cfg, cache, pos, ring=False)
        x = _cross_attn(x, p, cfg, enc_out, gated=False)
    elif kind == "rglru":
        h = L.rms_norm(x, p["norm"])
        y, cache = L.rglru_decode(h, p["rec"], cache)
        x = x + y
    elif kind == "rwkv":
        h = L.rms_norm(x, p["norm"])
        y, cache = L.rwkv_decode(h, p["mix"], cache)
        x = x + y
    else:
        raise ValueError(kind)
    x, _ = _ffn_residual(x, p, cfg)
    return x, cache


def broadcast_cache(cache: dict, k: int) -> dict:
    """Fan a single prefilled decode cache out to K posterior draws:
    every leaf gains a leading draw axis (K, ...). This is the
    cache-sharing half of ensemble serving — prefill runs ONCE (anchor
    draw), the prompt region of the KV cache / recurrent state is shared
    by construction, and only the decode fan-out diverges per draw
    (each draw's decode writes its own k/v rows for generated tokens)."""
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (k,) + l.shape), cache)


def ensemble_decode_step(draws: dict, cfg: ArchConfig, caches: dict,
                         token: jax.Array, pos: jax.Array, *,
                         enc_out: Optional[jax.Array] = None):
    """One serving step across K posterior draws sharing ONE token
    stream: ``draws``/``caches`` carry a leading (K, ...) draw axis,
    ``token`` (B,1) and ``pos`` (B,) are shared — the served sequence is
    a single stream whose next token comes from the ensemble predictive
    mean, not K diverging streams. Returns (logits (K,B,V), caches).

    The draw axis is a plain vmapped batch axis, so under a mesh it
    rides a mesh axis exactly like chains do during sampling
    (``repro.sharding.rules.ensemble_specs``)."""
    fn = lambda p, c: decode_step(p, cfg, c, token, pos,  # noqa: E731
                                  enc_out=enc_out)
    return jax.vmap(fn)(draws, caches)


def decode_step(params: dict, cfg: ArchConfig, cache: dict,
                token: jax.Array, pos: jax.Array, *,
                enc_out: Optional[jax.Array] = None):
    """One serving step. token: (B,1) int32; pos: (B,) absolute positions.
    Returns (logits (B, V), new_cache)."""
    pat, n_full, rem = _period_kinds(cfg)
    B = token.shape[0]
    # serving: cast params to bf16 ONCE, before the layer scan — otherwise
    # the per-step FSDP all-gathers move fp32 weights and convert after
    # (2x the ICI bytes; §Perf iteration 3).
    params = _cast_floating(params)
    x = params["embed"][token[:, 0]][:, None, :].astype(ACT_DTYPE)
    if enc_out is not None:
        enc_out = enc_out.astype(ACT_DTYPE)

    def body(x, inp):
        pp, cc = inp
        x = _shard_batch(x)
        for i, kind in enumerate(pat):
            x, c2 = _decode_layer(kind, pp[f"l{i}"], x, cfg, cc[f"l{i}"],
                                  pos, enc_out)
            cc = {**cc, f"l{i}": c2}
        return x, cc

    x, new_blocks = jax.lax.scan(body, x, (params["blocks"],
                                           cache["blocks"]))
    new_cache = {"blocks": new_blocks}
    if rem:
        rb = {}
        for i, kind in enumerate(rem):
            x, c2 = _decode_layer(
                kind, params["rem_blocks"][f"l{i}"], x,
                cfg, cache["rem_blocks"][f"l{i}"], pos, enc_out)
            rb[f"l{i}"] = c2
        new_cache["rem_blocks"] = rb
    x = L.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["head"],
                        preferred_element_type=jnp.float32)[:, 0]
    return logits, new_cache
