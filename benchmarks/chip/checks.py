"""The numbers that decide ``correct``, from the chain states after the
first two rounds (four local steps), program against reference.

All three are taken by the worst parameter leaf, each measured against
the reference's norm of that leaf or of the median leaf, whichever is
larger (some leaves hardly move):

  grad_gap    the gradients as the update got them in round 1, worked out
              from the state after it: the update is affine in theta and
              in the gradient, so  theta_2 - theta_2|grad=0  =
              h/2 N_s/(f_s m) [(1 + c) g_1 + g_2]  with the per-leaf
              constant c of the conducive and prior terms. Gap of norms.
  change_gap  the parameters' change over both rounds. Gap of norms.
  state_gap   the state after both rounds: norm of the difference. The
              noise stream, the resident client's surrogate rows and the
              client assignment all show here; a gap of norms cannot see
              a noise stream or a surrogate row swapped for another.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("grad_gap", "change_gap", "state_gap")


@jax.jit
def _leaf(t0, p2, p4, r2, r4, z2, unit):
    f = lambda x: x.astype(jnp.float32)  # noqa: E731
    n = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))  # noqa: E731
    t0, p2, p4, r2, r4, z2 = map(f, (t0, p2, p4, r2, r4, z2))
    return jnp.stack([n((p2 - z2) / unit), n((r2 - z2) / unit),
                      n(p4 - t0), n(r4 - t0), n(p4 - r4)])


def numbers(theta0, p2, p4, r2, r4, z2, grad_unit: float) -> dict:
    """Trees of one chain's leaves: theta0 the start, p2/p4 the program's
    states after rounds 1 and 2 (host or device), r2/r4 the reference's,
    z2 the reference's round 1 with the gradient set to zero;
    ``grad_unit`` = h/2 N_s/(f_s m)."""
    rows = np.array([np.asarray(_leaf(*xs, jnp.float32(grad_unit)),
                                np.float64)
                     for xs in zip(*map(jax.tree.leaves,
                                        (theta0, p2, p4, r2, r4, z2)))])
    gp, gr, dp, dr, diff = rows.T

    def worst(num, ref):
        den = np.maximum(ref, np.median(ref))
        return float(np.max(num / den))

    return {"grad_gap": worst(np.abs(gp - gr), gr),
            "change_gap": worst(np.abs(dp - dr), dr),
            "state_gap": worst(diff, dr)}


def judge(values: dict, limits: dict) -> bool:
    """Every number finite and within its limit."""
    return all(limits.get(k) is not None and np.isfinite(values[k])
               and values[k] <= limits[k]
               for k in NAMES)
