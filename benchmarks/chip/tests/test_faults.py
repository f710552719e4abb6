"""A run at a size the CPU holds, with the timed path sound, broken
underneath, or replaced by the control, judged by a cell's committed
limits: only the sound run comes out correct.

The chip check is skipped (``Cell.run`` is the rest of a run). Faults are
planted in the program the window drives: its update kernel returns the
state unchanged or alters one row of its output, or its log-likelihood
leaves out half of the batch and doubles the rest. (One chip: there is
no exchange between chips to leave out.) The control is the reference computed in bfloat16
(parameters, gradient pass and chain state) in the program's place.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

import cell
import checks
import refsampler
from conftest import tiny_spec

LIMITS = pathlib.Path(__file__).resolve().parents[1] / "limits"


def committed(workload):
    return json.loads((LIMITS / f"{workload}.json").read_text())


def run_tiny(kind, workload, seconds=0.5):
    spec = tiny_spec(kind)
    spec["limits"] = committed(workload)
    c = cell.Cell("tiny", 2**32 + 11, spec=spec)
    res = c.run(seconds, t_start=0.0, devices=jax.devices(),
                log=lambda *a: None)
    return res.checks, checks.judge(res.checks, c.limits)


# the last: a pattern of two kinds, three layers (a whole period and one
# layer past it), judged by the tightest state limit of the three
CASES = [("swa", "danube-2L.b4x1024"), ("rwkv", "rwkv6-1L.b4x1024"),
         ("rwkv", "rwkv6-1L.b1x1024"), ("swa+rwkv", "rwkv6-1L.b4x1024")]


@pytest.mark.parametrize("kind,workload", CASES)
def test_sound_run_reads_under_the_gradient_and_state_limits(kind,
                                                              workload):
    """At toy widths on the CPU the bfloat16 gradient pass moves the
    parameters' change by more than at the published widths on the chip
    (change_gap ~1e-5 here), so only the other two numbers hold here."""
    values, _ = run_tiny(kind, workload)
    limits = committed(workload)
    for k in ("grad_gap", "state_gap"):
        assert values[k] <= limits[k], (k, values)


@pytest.mark.parametrize("kind,workload", CASES)
def test_state_returned_unchanged_is_not_correct(kind, workload,
                                                 monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "packed_step",
                        lambda layout, theta_p, *a, **k: theta_p)
    values, ok = run_tiny(kind, workload)
    assert not ok, values


@pytest.mark.parametrize("kind,workload", CASES)
def test_answer_altered_where_produced_is_not_correct(kind, workload,
                                                      monkeypatch):
    """The update kernel's output with one row of 128 elements shifted by
    the noise's own scale."""
    from repro.kernels import ops
    orig = ops.packed_step

    def altered(*a, **k):
        out = orig(*a, **k)
        return out.at[0].add(3e-3)

    monkeypatch.setattr(ops, "packed_step", altered)
    values, ok = run_tiny(kind, workload)
    assert not ok, values


@pytest.mark.parametrize("kind,workload", CASES)
def test_half_batch_is_not_correct(kind, workload, monkeypatch):
    import repro.models
    orig = repro.models.log_lik_fn

    def half(params, cfg, batch):
        part, mult = refsampler.half_batch(batch)
        return mult * orig(params, cfg, part)

    monkeypatch.setattr(repro.models, "log_lik_fn", half)
    values, ok = run_tiny(kind, workload)
    assert not ok, values


@pytest.mark.parametrize("kind,workload", CASES)
def test_control_is_not_correct(kind, workload):
    spec = tiny_spec(kind)
    c = cell.Cell("tiny", 2**31 + 3, spec=spec)
    c.make_inputs(0.0)
    with jax.default_matmul_precision("highest"):
        t0 = c.weights()
        r2, r4 = c.reference_rounds(t0)
        z2 = c.reference_rounds(t0, zero_grad=True, rounds=1)[0]
        p2, p4 = c.reference_rounds(t0, mode="bf16", store=jnp.bfloat16)
        values = checks.numbers(t0, p2, p4, r2, r4, z2, c.grad_unit())
    assert not checks.judge(values, committed(workload)), values
