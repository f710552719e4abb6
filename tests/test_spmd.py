"""SPMD behaviour tests that need >1 device: run in subprocesses with
forced host-device counts (the main test process must keep the single real
CPU device — see dryrun.py's XLA_FLAGS note)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_large_model_round_runs_on_chain_engine_multidevice():
    """The large-model federated round runs ON THE CHAIN ENGINE (the
    private ppermute ring in launch/steps.py is retired): 4 transformer
    chains on a 4-way data axis go through repro.api.FSGLD, reassignment
    is the engine's collision-free SPMD permutation, the sampler keeps
    sampling (finite chains) and the chains diverge (each visited its own
    client sequence)."""
    script = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro import api
from repro.configs import get_smoke_config
from repro.data import token_shards
from repro.launch.mesh import make_sim_mesh
from repro.models import init_params, log_lik_fn
mesh = make_sim_mesh(data=4, model=1)
cfg = get_smoke_config("qwen3-1.7b")
params = init_params(cfg, jax.random.PRNGKey(0))
shards = token_shards(jax.random.PRNGKey(1), num_shards=4, shard_size=16,
                      seq_len=16, vocab_size=cfg.vocab_size)
f = api.FSGLD(
    api.Posterior(lambda p, b: log_lik_fn(p, cfg, b), prior_precision=1.0),
    shards, minibatch=4, step_size=1e-4, method="dsgld",
    schedule=api.Schedule(rounds=2, local_steps=2, n_chains=4,
                          reassign="permutation"),
    execution=api.Execution(mesh=mesh, collect=False))
finals = f.sample(jax.random.PRNGKey(7), params)
leaves = jax.tree.leaves(finals)
assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
           for l in leaves)
assert leaves[0].shape[0] == 4
# chains visited different client sequences: their states diverged
emb = finals["embed"].reshape(4, -1)
d01 = float(jnp.abs(emb[0] - emb[1]).max())
assert d01 > 0.0, "chains did not diverge"
print("ENGINE_ROUND_OK")
"""
    r = _run(script, devices=4)
    assert "ENGINE_ROUND_OK" in r.stdout, (r.stdout, r.stderr[-2000:])


@pytest.mark.slow
def test_dryrun_single_combo_subprocess():
    """End-to-end dry-run smoke: one fast combo compiles on the full
    512-device production mesh in a subprocess."""
    script = r"""
import repro.launch.dryrun as d
rc = d.main(["--arch", "h2o-danube-1.8b", "--shape", "long_500k"])
assert rc == 0
print("DRYRUN_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "DRYRUN_OK" in r.stdout, (r.stdout, r.stderr[-2000:])
