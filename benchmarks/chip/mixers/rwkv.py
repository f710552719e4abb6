"""RWKV-6 time mix as the program implements it: token shift, r/k/v
projections, a data-dependent per-channel decay from a rank-64 low-rank
branch, the bonus ``u`` for the current token and an output projection.
The program has no gate and no group norm after the mix (a departure
from RWKV-6, stated in the configuration file). Written as the plain
recurrence over tokens:

    o_t = r_t . (S_{t-1} + diag(u) k_t v_t^T),
    S_t = diag(w_t) S_{t-1} + k_t v_t^T,   w_t = exp(-exp(w0 + lora(x_t))).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PARAM_KEY = "mix"
LORA = 64
CHUNK = 64   # tokens per rematerialised block of the recurrence


def init(key, arch):
    d, H, hd = arch["d_model"], arch["num_heads"], arch["head_dim"]
    ks = jax.random.split(key, 6)
    n = lambda k, fan, shape: (  # noqa: E731
        jax.random.normal(k, shape, jnp.float32) * fan ** -0.5)
    half = jnp.full((d,), 0.5, jnp.float32)
    return {"mu_r": half, "mu_k": half, "mu_v": half, "mu_w": half,
            "w_r": n(ks[0], d, (d, H * hd)), "w_k": n(ks[1], d, (d, H * hd)),
            "w_v": n(ks[2], d, (d, H * hd)), "w_o": n(ks[3], H * hd,
                                                      (H * hd, d)),
            "w0": jnp.full((d,), -1.0, jnp.float32),
            "w_lora_a": n(ks[4], d, (d, LORA)),
            "w_lora_b": n(ks[5], LORA, (LORA, d)),
            "u": jnp.zeros((H, hd), jnp.float32)}


def forward(h, p, arch, pr):
    b, s, d = h.shape
    H, hd = arch["num_heads"], arch["head_dim"]
    prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)

    def shift(mu):
        return (h + mu.astype(h.dtype) * (prev - h)).astype(pr.dtype)

    r = pr.mm(shift(p["mu_r"]), p["w_r"]).astype(jnp.float32)
    k = pr.mm(shift(p["mu_k"]), p["w_k"]).astype(jnp.float32)
    v = pr.mm(shift(p["mu_v"]), p["w_v"]).astype(jnp.float32)
    dd = pr.mm(jnp.tanh(pr.mm(shift(p["mu_w"]), p["w_lora_a"])),
               p["w_lora_b"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(jnp.clip(p["w0"] + dd, -8.0, 8.0)))
    u = p["u"].astype(jnp.float32)
    heads = lambda t: t.reshape(b, s // CHUNK, CHUNK, H, hd) \
        .transpose(1, 2, 0, 3, 4)       # (blocks, CHUNK, b, H, hd)
    hp = jax.lax.Precision.HIGHEST

    def token(S, x):
        rt, kt, vt, wt = x
        kv = kt[..., :, None] * vt[..., None, :]          # (b, H, hd, hd)
        o = jnp.einsum("bhc,bhcd->bhd", rt, S + u[None, :, :, None] * kv,
                       precision=hp)
        return wt[..., :, None] * S + kv, o

    def block(S, x):
        return jax.lax.scan(token, S, x)

    S0 = jnp.zeros((b, H, hd, hd), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(block), S0,
                        tuple(map(heads, (r, k, v, w))))
    o = o.transpose(2, 0, 1, 3, 4).reshape(b, s, H * hd)
    return pr.mm(o.astype(pr.dtype), p["w_o"])


def matmul_params(arch) -> int:
    d, q = arch["d_model"], arch["num_heads"] * arch["head_dim"]
    return 4 * d * q + 2 * d * LORA


def fwd_flops_per_seq(arch, seq_len: int) -> float:
    """The recurrence, forward, per token and head: the k v^T outer
    product, the state read r . S (hd^2 multiply-adds each), the decayed
    state update (hd^2 multiply-adds) and the bonus term."""
    hd = arch["head_dim"]
    return seq_len * arch["num_heads"] * (6.0 * hd * hd + 4.0 * hd)


def param_count(arch) -> int:
    d, H, hd = arch["d_model"], arch["num_heads"], arch["head_dim"]
    return matmul_params(arch) + 5 * d + H * hd
