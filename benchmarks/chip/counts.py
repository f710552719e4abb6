"""Operations and bytes of a step, from a configuration's shapes alone,
and the table of peaks. What any implementation must do, not what the
program happens to do: extra copies and recomputation do not count.

Step FLOPs (model FLOPs, forward + backward = 3 forward passes): every
parameter that multiplies activations costs 2 FLOPs per token forward,
so 6 per token per step; the embedding is a lookup and costs none; the
output head counts. The sequence mixer adds its own (attention scores
and weighted values, or the linear recurrence), also times 3.

Update bytes: the FSGLD update must read theta, read the gradient and
write theta in float32, and read each conducive operand once at the
dtype the bank stores it (the global and the resident client's surrogate
mean; scalar precisions are per leaf and do not count).
"""
from __future__ import annotations

import json
import pathlib

import refmodel

HERE = pathlib.Path(__file__).resolve().parent


def param_count(arch: dict) -> int:
    """Every parameter of the reference layout (and the program's)."""
    d, f, v, n = (arch["d_model"], arch["d_ff"], arch["vocab_size"],
                  arch["num_layers"])
    mix = refmodel.mixer(arch["layer_kind"])
    per_layer = 3 * d * f + 2 * d + mix.param_count(arch)
    return 2 * v * d + d + n * per_layer


def matmul_params(arch: dict) -> int:
    """Parameters that multiply activations: the head, the feed-forward
    matrices and the mixer's projections (not the embedding lookup, not
    norm gains or mixing vectors)."""
    d, f, v, n = (arch["d_model"], arch["d_ff"], arch["vocab_size"],
                  arch["num_layers"])
    mix = refmodel.mixer(arch["layer_kind"])
    return d * v + n * (3 * d * f + mix.matmul_params(arch))


def step_flops(arch: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one gradient pass over ``batch`` sequences."""
    mix = refmodel.mixer(arch["layer_kind"])
    tokens = batch * seq_len
    per_seq = mix.fwd_flops_per_seq(arch, seq_len) * arch["num_layers"]
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
