"""SiLU-gated feed-forward (SwiGLU): (silu(x Wg) * (x Wu)) Wo, its
initialisation and its parameter counts."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, arch):
    d, f = arch["d_model"], arch["d_ff"]
    kg, ku, ko = jax.random.split(key, 3)
    n = lambda k, fan, shape: (  # noqa: E731
        jax.random.normal(k, shape, jnp.float32) * fan ** -0.5)
    return {"wi_gate": n(kg, d, (d, f)), "wi_up": n(ku, d, (d, f)),
            "wo": n(ko, f, (f, d))}


def forward(h, p, arch, pr):
    """(the layer's output, the term it adds to the log-likelihood: none
    for a dense feed-forward)."""
    g = pr.mm(h, p["wi_gate"]).astype(jnp.float32)
    u = pr.mm(h, p["wi_up"]).astype(jnp.float32)
    return (pr.mm((jax.nn.silu(g) * u).astype(pr.dtype), p["wo"]),
            jnp.float32(0.0))


def matmul_params(arch) -> int:
    return 3 * arch["d_model"] * arch["d_ff"]


def param_count(arch) -> int:
    return matmul_params(arch)
