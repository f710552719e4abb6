"""Operations and bytes of a step, from a configuration's shapes alone,
and the table of peaks. What any implementation must do, not what the
program happens to do: extra copies and recomputation do not count.

Step FLOPs (model FLOPs, forward + backward = 3 forward passes): every
parameter that multiplies activations costs 2 FLOPs per token forward,
so 6 per token per step; the embedding is a lookup and costs none; the
output head counts; a routed feed-forward counts the experts a token
reaches. Each layer's sequence mixer adds its own (attention scores and
weighted values, or the linear recurrence), also times 3. Every count
is a sum over the layers of what each one's mixer (``mixers/``) and
feed-forward (``ffns/``) reckon.

Update bytes: the FSGLD update must read theta, read the gradient and
write theta in float32, and read each conducive operand once at the
dtype the bank stores it (the global and the resident client's surrogate
mean; scalar precisions are per leaf and do not count).
"""
from __future__ import annotations

import collections
import json
import pathlib

import refmodel

HERE = pathlib.Path(__file__).resolve().parent


def _kinds(arch: dict) -> collections.Counter:
    """How many layers of each kind the stack holds."""
    pattern, periods, rem = refmodel.layer_kinds(arch)
    return collections.Counter(pattern * periods + rem)


def param_count(arch: dict) -> int:
    """Every parameter of the reference layout (and the program's): per
    layer two norm gains, its feed-forward's and its mixer's."""
    d, v, n = arch["d_model"], arch["vocab_size"], arch["num_layers"]
    ffn = refmodel.ffn_of(arch)
    mixers = sum(k * refmodel.mixer(kind).param_count(arch)
                 for kind, k in _kinds(arch).items())
    return 2 * v * d + d + n * (2 * d + ffn.param_count(arch)) + mixers


def matmul_params(arch: dict) -> int:
    """Parameters that multiply each token's activations (in expectation,
    for a routed feed-forward): the head, the feed-forward matrices and
    the mixers' projections (not the embedding lookup, not norm gains or
    mixing vectors)."""
    d, v, n = arch["d_model"], arch["vocab_size"], arch["num_layers"]
    ffn = refmodel.ffn_of(arch)
    mixers = sum(k * refmodel.mixer(kind).matmul_params(arch)
                 for kind, k in _kinds(arch).items())
    return d * v + n * ffn.matmul_params(arch) + mixers


def step_flops(arch: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one gradient pass over ``batch`` sequences."""
    tokens = batch * seq_len
    per_seq = sum(refmodel.mixer(kind).fwd_flops_per_seq(arch, seq_len) * k
                  for kind, k in _kinds(arch).items())
    return 6.0 * matmul_params(arch) * tokens + 3.0 * per_seq * batch


def update_bytes(arch: dict, bank_bytes: int = 2) -> float:
    """Bytes one FSGLD step (scalar surrogates, Langevin) must move."""
    return param_count(arch) * (4 + 4 + 4 + 2 * bank_bytes)


def update_flops(arch: dict) -> float:
    """FLOPs of the update, noise included (hash, log, cos, sqrt and
    about 20 arithmetic operations per element: a generous count)."""
    return 40.0 * param_count(arch)


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``;
    a device missing from ``peaks.json`` is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]
