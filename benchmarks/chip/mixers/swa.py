"""Sliding-window grouped-query self-attention with rotary positions
(Mistral/Llama style, as in h2o-danube): the plain reference, its
initialisation and its FLOP count."""
from __future__ import annotations

import jax
import jax.numpy as jnp

import refmodel

PARAM_KEY = "attn"


def init(key, arch):
    d, hd = arch["d_model"], arch["head_dim"]
    q, kv = arch["num_heads"] * hd, arch["num_kv_heads"] * hd
    ks = jax.random.split(key, 4)
    n = lambda k, fan, shape: (  # noqa: E731
        jax.random.normal(k, shape, jnp.float32) * fan ** -0.5)
    return {"wq": n(ks[0], d, (d, q)), "wk": n(ks[1], d, (d, kv)),
            "wv": n(ks[2], d, (d, kv)), "wo": n(ks[3], q, (q, d))}


def _rope(x, theta):
    """Rotary embedding over the last axis, halves rotated (x: B,S,H,hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def forward(h, p, arch, pr):
    b, s, _ = h.shape
    H, K, hd = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    q = _rope(pr.mm(h, p["wq"]).reshape(b, s, H, hd), arch["rope_theta"])
    k = _rope(pr.mm(h, p["wk"]).reshape(b, s, K, hd), arch["rope_theta"])
    v = pr.mm(h, p["wv"]).reshape(b, s, K, hd)
    # query head i reads kv head i // (H / K)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    w = arch["swa_window"]
    o = refmodel.attention(
        q, k, v, lambda qp, kp: (kp <= qp) & (kp > qp - w), pr)
    return pr.mm(o.reshape(b, s, H * hd), p["wo"])


def matmul_params(arch) -> int:
    d, hd = arch["d_model"], arch["head_dim"]
    q, kv = arch["num_heads"] * hd, arch["num_kv_heads"] * hd
    return 2 * d * q + 2 * d * kv


def fwd_flops_per_seq(arch, seq_len: int) -> float:
    """Scores and weighted values of one sequence, forward: 2 matmuls of
    2 * H * hd FLOPs per (query, key) pair the window and causality keep."""
    w = min(arch["swa_window"], seq_len)
    pairs = w * (w + 1) / 2 + (seq_len - w) * w
    return 4.0 * arch["num_heads"] * arch["head_dim"] * pairs


def param_count(arch) -> int:
    return matmul_params(arch)
