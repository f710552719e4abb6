"""Plain reference of the benchmark's language models, in straightforward
``jax.numpy``: the yardstick that decides ``correct``.

It imports nothing of the program. Parameters are nested dicts laid out
as the program lays them out (``embed``, ``blocks`` stacked over layers,
``final_norm``, ``head``), so one pytree feeds both. Each kind of layer
mixer lives in ``mixers/<kind>.py`` (init, forward and its FLOP count);
this module holds what every kind shares: embedding, RMS norm, the SiLU
feed-forward, the output head and the log-likelihood.

Two precisions:
  ``"fp32"``  float32 everywhere, matmuls at ``Precision.HIGHEST``: the
              reference;
  ``"bf16"``  parameters and activations in bfloat16 with float32
              accumulation: the control, and the benchmark's own
              surrogate fit, which is traffic data and not compared.
"""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent
_MIXERS: dict = {}


def mixer(kind: str):
    """The module ``mixers/<kind>.py``, loaded once by name."""
    if kind not in _MIXERS:
        path = HERE / "mixers" / f"{kind}.py"
        if not path.is_file():
            raise KeyError(f"no reference for layer kind {kind!r} ({path})")
        spec = importlib.util.spec_from_file_location(f"mixer_{kind}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MIXERS[kind] = mod
    return _MIXERS[kind]


class Prec:
    """Compute dtype and matmul precision of one precision mode."""

    def __init__(self, mode: str):
        if mode == "fp32":
            self.dtype, self.precision = jnp.float32, jax.lax.Precision.HIGHEST
        elif mode == "bf16":
            self.dtype, self.precision = jnp.bfloat16, jax.lax.Precision.DEFAULT
        else:
            raise ValueError(mode)
        self.mode = mode

    def mm(self, a, b):
        """a @ b with float32 accumulation, in this mode's dtype."""
        return jnp.matmul(a.astype(self.dtype), b.astype(self.dtype),
                          precision=self.precision,
                          preferred_element_type=jnp.float32
                          ).astype(self.dtype)

    def einsum(self, spec, *xs):
        return jnp.einsum(spec, *[x.astype(self.dtype) for x in xs],
                          precision=self.precision,
                          preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps=1e-6):
    """RMS norm with a zero-initialised gain: x / rms(x) * (1 + scale),
    computed in float32, returned in x's dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def ffn(x, p, pr: Prec):
    """SiLU-gated feed-forward: (silu(x Wg) * (x Wu)) Wo."""
    g = pr.mm(x, p["wi_gate"]).astype(jnp.float32)
    u = pr.mm(x, p["wi_up"]).astype(jnp.float32)
    return pr.mm((jax.nn.silu(g) * u).astype(pr.dtype), p["wo"])


def _dense(key, fan_in, shape):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def init_params(arch: dict, key) -> dict:
    """Weights from a key, in float32, every layer of one kind
    (``arch['layer_kind']``) stacked along a leading axis. Call it inside
    one ``jit``: the whole tree is made on the device in one program."""
    d, f, v, n = (arch["d_model"], arch["d_ff"], arch["vocab_size"],
                  arch["num_layers"])
    mix = mixer(arch["layer_kind"])
    k_emb, k_head, k_layers = jax.random.split(key, 3)

    def layer(k):
        k_ffn, k_mix = jax.random.split(k)
        kg, ku, ko = jax.random.split(k_ffn, 3)
        return {"norm": jnp.zeros((d,), jnp.float32),
                "ffn_norm": jnp.zeros((d,), jnp.float32),
                "ffn": {"wi_gate": _dense(kg, d, (d, f)),
                        "wi_up": _dense(ku, d, (d, f)),
                        "wo": _dense(ko, f, (f, d))},
                mix.PARAM_KEY: mix.init(k_mix, arch)}

    return {"embed": _dense(k_emb, d, (v, d)),
            "blocks": {"l0": jax.vmap(layer)(jax.random.split(k_layers, n))},
            "final_norm": jnp.zeros((d,), jnp.float32),
            "head": _dense(k_head, d, (d, v))}


def log_lik(params: dict, arch: dict, batch: dict, mode: str = "fp32"):
    """Sum over the batch's tokens of log p(label | prefix)."""
    pr = Prec(mode)
    mix = mixer(arch["layer_kind"])
    x = params["embed"][batch["tokens"]].astype(pr.dtype)

    def layer(x, p):
        x = x + mix.forward(rms_norm(x, p["norm"]), p[mix.PARAM_KEY],
                            arch, pr)
        x = x + ffn(rms_norm(x, p["ffn_norm"]), p["ffn"], pr)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"]["l0"])
    h = rms_norm(x, params["final_norm"])
    logits = pr.einsum("bsd,dv->bsv", h, params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, batch["labels"][..., None], -1).sum()
