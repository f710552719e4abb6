"""Plain reference of the benchmark's language models, in straightforward
``jax.numpy``: the yardstick that decides ``correct``.

It imports nothing of the program. Parameters are nested dicts laid out
as the program lays them out, so one pytree feeds both: ``embed``;
``blocks``, one entry ``l{i}`` per position of the layer pattern, each
stacked over the pattern's whole periods; ``rem_blocks`` for the layers
past the last whole period (unstacked); ``final_norm`` and ``head``.
Each kind of sequence mixer lives in ``mixers/<kind>.py`` (init, forward
and its FLOP count), each kind of feed-forward in ``ffns/<kind>.py``
(init, forward, parameter counts); this module holds what every kind
shares: embedding, RMS norm, masked attention, the output head and the
log-likelihood.

Two precisions:
  ``"fp32"``  float32 everywhere, matmuls at ``Precision.HIGHEST``: the
              reference;
  ``"bf16"``  parameters and activations in bfloat16 with float32
              accumulation: the control, and the benchmark's own
              surrogate fit, which is traffic data and not compared.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent
# sequences longer than this attend in checkpointed blocks of queries
ATTN_BLOCK_ABOVE = 1024
ATTN_Q_BLOCK = 512
_PLUGINS: dict = {}


def _plugin(folder: str, kind: str):
    """The module ``<folder>/<kind>.py``, loaded once by name."""
    if (folder, kind) not in _PLUGINS:
        path = HERE / folder / f"{kind}.py"
        if not path.is_file():
            raise KeyError(f"no reference for {folder} kind {kind!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"{folder}_{kind}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PLUGINS[folder, kind] = mod
    return _PLUGINS[folder, kind]


def mixer(kind: str):
    """The sequence mixer of one layer kind: ``mixers/<kind>.py``."""
    return _plugin("mixers", kind)


def ffn_of(arch: dict):
    """The feed-forward every layer of ``arch`` has: ``ffns/moe.py`` where
    the configuration routes over experts, else ``ffns/<ffn_type>.py``
    (SiLU where unstated, as in the program's configuration)."""
    return _plugin("ffns", "moe" if arch.get("moe") else
                   arch.get("ffn_type", "silu"))


def layer_kinds(arch: dict):
    """(the pattern, its whole periods, the kinds of the layers past the
    last whole period), as the program lays the layers out."""
    pattern = tuple(arch["layer_pattern"])
    periods, rem = divmod(arch["num_layers"], len(pattern))
    return pattern, periods, pattern[:rem]


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


def attention(q, k, v, keep, pr: Prec):
    """softmax(q k^T / sqrt(hd)) v over the (query, key) pairs that
    ``keep(q_pos, k_pos)`` keeps (boolean, broadcast from (n, 1) and
    (1, s) positions). q, k, v: (b, s, H, hd), one key and value head per
    query head. Up to ``ATTN_BLOCK_ABOVE`` tokens the whole (b, H, s, s)
    score matrix is built at once; past it the queries go in blocks of
    ``ATTN_Q_BLOCK`` (their greatest common divisor with s), each block
    rematerialised in the backward pass, so no more than one block's
    scores is live."""
    b, s, H, hd = q.shape
    pos = jnp.arange(s)

    def block(qb, q_pos):
        scores = pr.einsum("bqhd,bkhd->bhqk", qb, k) * hd ** -0.5
        scores = jnp.where(keep(q_pos[:, None], pos[None, :]), scores,
                           -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        return pr.einsum("bhqk,bkhd->bqhd", att, v).astype(pr.dtype)

    if s <= ATTN_BLOCK_ABOVE:
        return block(q, pos)
    n = math.gcd(s, ATTN_Q_BLOCK)
    qs = q.reshape(b, s // n, n, H, hd).swapaxes(0, 1)

    def step(_, x):
        qb, start = x
        return None, block(qb, start + jnp.arange(n))

    _, o = jax.lax.scan(jax.checkpoint(step), None,
                        (qs, jnp.arange(0, s, n)))
    return o.swapaxes(0, 1).reshape(b, s, H, hd)


def _dense(key, fan_in, shape):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def init_params(arch: dict, key) -> dict:
    """Weights from a key, in float32, in the program's layout (module
    docstring). Layer j of the stack takes key j of ``num_layers``; a
    layer splits its key into its feed-forward's and its mixer's. Call it
    inside one ``jit``: the whole tree is made on the device in one
    program."""
    d, v, n = arch["d_model"], arch["vocab_size"], arch["num_layers"]
    pattern, periods, rem = layer_kinds(arch)
    ffn = ffn_of(arch)
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, n)

    def layer(kind):
        mix = mixer(kind)

        def init(k):
            k_ffn, k_mix = jax.random.split(k)
            return {"norm": jnp.zeros((d,), jnp.float32),
                    "ffn_norm": jnp.zeros((d,), jnp.float32),
                    "ffn": ffn.init(k_ffn, arch),
                    mix.PARAM_KEY: mix.init(k_mix, arch)}
        return init

    stacked = keys[:periods * len(pattern)].reshape(
        periods, len(pattern), *keys.shape[1:])
    params = {"embed": _dense(k_emb, d, (v, d)),
              "blocks": {f"l{i}": jax.vmap(layer(kind))(stacked[:, i])
                         for i, kind in enumerate(pattern)},
              "final_norm": jnp.zeros((d,), jnp.float32),
              "head": _dense(k_head, d, (d, v))}
    if rem:
        params["rem_blocks"] = {
            f"l{i}": layer(kind)(keys[periods * len(pattern) + i])
            for i, kind in enumerate(rem)}
    return params


def log_lik(params: dict, arch: dict, batch: dict, mode: str = "fp32"):
    """Sum over the batch's tokens of log p(label | prefix), plus what
    each layer's feed-forward adds (nothing for a dense one)."""
    pr = Prec(mode)
    pattern, _, rem = layer_kinds(arch)
    ffn = ffn_of(arch)
    x = params["embed"][batch["tokens"]].astype(pr.dtype)

    def layer(kind, x, p):
        mix = mixer(kind)
        x = x + mix.forward(rms_norm(x, p["norm"]), p[mix.PARAM_KEY],
                            arch, pr)
        y, term = ffn.forward(rms_norm(x, p["ffn_norm"]), p["ffn"], arch, pr)
        return x + y, term

    def period(carry, p):
        x, extra = carry
        for i, kind in enumerate(pattern):
            x, term = layer(kind, x, p[f"l{i}"])
            extra = extra + term
        return (x, extra), None

    (x, extra), _ = jax.lax.scan(jax.checkpoint(period),
                                 (x, jnp.float32(0.0)), params["blocks"])
    for i, kind in enumerate(rem):
        x, term = layer(kind, x, params["rem_blocks"][f"l{i}"])
        extra = extra + term
    h = rms_norm(x, params["final_norm"])
    logits = pr.einsum("bsd,dv->bsv", h, params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, batch["labels"][..., None],
                               -1).sum() + extra
