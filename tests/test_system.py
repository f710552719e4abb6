"""End-to-end system behaviour: trainer/server drivers, federated shard_map
round (ppermute chain exchange), data pipeline properties, checkpointing,
HLO roofline analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SamplerConfig, get_smoke_config
from repro.data import (gaussian_shards, linreg_datasets, metric_pairs,
                        susy_shards, token_shards)
from repro import checkpoint
from repro.models import init_params


@pytest.mark.slow  # full smoke train driver, ~40s on the CPU container
def test_train_driver_runs(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "rwkv6-7b", "--smoke", "--rounds", "2",
               "--local-updates", "1", "--fit-steps", "6", "--seq", "32",
               "--shard-size", "16", "--batch", "4",
               "--ckpt", str(tmp_path / "ck")])
    assert rc == 0
    params = init_params(get_smoke_config("rwkv6-7b"), jax.random.PRNGKey(0))
    restored, step, extra = checkpoint.restore(str(tmp_path / "ck"), params)
    assert step == 2 and extra["method"] == "fsgld"


def test_dsgld_train_driver_runs():
    from repro.launch.train import main
    assert main(["--arch", "qwen3-1.7b", "--smoke", "--method", "dsgld",
                 "--rounds", "1", "--local-updates", "1", "--seq", "16",
                 "--shard-size", "8", "--batch", "2"]) == 0


def test_serve_driver_runs():
    from repro.launch.serve import main
    assert main(["--arch", "recurrentgemma-2b", "--smoke", "--batch", "2",
                 "--prompt-len", "4", "--gen", "3"]) == 0


def test_train_draw_bank_then_ensemble_serve(tmp_path):
    """The streaming chain→server path end to end: train writes
    DrawMeta-enveloped draws into a versioned bank, serve answers with
    the K-draw ensemble from the same directory."""
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main
    bank = str(tmp_path / "bank")
    rc = train_main(["--arch", "h2o-danube-1.8b", "--smoke", "--method",
                     "dsgld", "--rounds", "2", "--local-updates", "1",
                     "--seq", "16", "--shard-size", "8", "--batch", "2",
                     "--draw-bank", bank, "--bank-every", "1"])
    assert rc == 0
    draws = checkpoint.list_draws(bank)
    assert len(draws) == 2
    meta = checkpoint.read_meta(draws[-1])
    assert meta.method == "dsgld" and meta.round == 2
    assert meta.arch == "h2o-danube-1.8b"
    assert serve_main(["--arch", "h2o-danube-1.8b", "--smoke", "--batch",
                       "2", "--prompt-len", "4", "--gen", "3",
                       "--draws", "2", "--bank", bank]) == 0


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_smoke_config("gemma-7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    checkpoint.save(str(tmp_path / "c"), params, step=7, extra={"k": 1})
    restored, step, extra = checkpoint.restore(str(tmp_path / "c"), params)
    assert step == 7 and extra == {"k": 1}
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# data pipeline structure
# ---------------------------------------------------------------------------

def test_susy_shards_noniid_vs_iid():
    key = jax.random.PRNGKey(0)
    noniid, pi_n = susy_shards(key, num_shards=20, shard_size=500,
                               beta_a=0.5)
    iid, pi_i = susy_shards(key, num_shards=20, shard_size=500,
                            beta_a=100.0)
    # non-IID: label proportions spread out; IID: concentrated at 1/2
    assert float(jnp.std(pi_n)) > 5 * float(jnp.std(pi_i))
    assert noniid["x"].shape == (20, 500, 18)
    # shard label means track pi
    emp = noniid["y"].mean(axis=1)
    assert float(jnp.corrcoef(emp, pi_n)[0, 1]) > 0.95


def test_metric_pairs_class_disjoint():
    key = jax.random.PRNGKey(0)
    data, centers = metric_pairs(key, num_classes=26, dim=8, num_shards=13,
                                 pairs_per_shard=40)
    assert data["xi"].shape == (13, 40, 8)
    assert set(np.unique(np.asarray(data["y"]))) == {0.0, 1.0}


def test_token_shards_heterogeneous():
    key = jax.random.PRNGKey(0)
    d = token_shards(key, num_shards=4, shard_size=32, seq_len=16,
                     vocab_size=64, alpha=0.05)
    assert d["tokens"].shape == (4, 32, 16)
    # labels are next-token shifts of the same stream
    # per-client unigram distributions differ (non-IID)
    hists = [np.bincount(np.asarray(d["tokens"][s]).ravel(), minlength=64)
             for s in range(4)]
    cos = np.dot(hists[0], hists[1]) / (np.linalg.norm(hists[0])
                                        * np.linalg.norm(hists[1]))
    assert cos < 0.9, cos


def test_linreg_datasets_shapes():
    out = linreg_datasets(jax.random.PRNGKey(0))
    assert set(out) == {"concrete", "noise", "conductivity"}
    assert out["conductivity"]["x"].shape == (17389, 81)


# ---------------------------------------------------------------------------
# roofline analyzer
# ---------------------------------------------------------------------------

def test_hlo_analyzer_scales_loops():
    from repro.roofline.hlo_analysis import analyze_text

    def f_scan(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    def f_nested(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    one = 2 * 64 ** 3
    for f, want in [(f_scan, 10 * one), (f_nested, 20 * one)]:
        c = jax.jit(f).lower(x, w).compile()
        got = analyze_text(c.as_text())["flops"]
        assert abs(got - want) / want < 0.01, (got, want)


def test_hlo_analyzer_matches_xla_on_loop_free():
    from repro.roofline.hlo_analysis import analyze_text

    def f(w1, w2, x):
        return jnp.sum(jnp.tanh(x @ w1) @ w2)

    g = jax.grad(f, argnums=(0, 1))
    xs = [jax.ShapeDtypeStruct(s, jnp.float32)
          for s in [(64, 128), (128, 32), (16, 64)]]
    c = jax.jit(g).lower(*xs).compile()
    got = analyze_text(c.as_text())["flops"]
    want = c.cost_analysis()["flops"]
    assert abs(got - want) / want < 0.05, (got, want)
