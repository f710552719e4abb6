"""FLOP and byte counts against hand counts, and the layouts they count."""
import json
import pathlib

import jax
import pytest

import cell
import counts
import refmodel
from conftest import tiny_spec

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def arch(name):
    return cell.arch_of(json.loads((CONFIGS / f"{name}.json").read_text()))


def leaves(a):
    shapes = jax.eval_shape(lambda k: refmodel.init_params(a, k),
                            jax.random.PRNGKey(0))
    return jax.tree.leaves(shapes)


@pytest.mark.parametrize("kind", ["swa", "rwkv", "swa+rwkv"])
def test_param_count_matches_the_reference_layout(kind):
    a = cell.arch_of(tiny_spec(kind)["config"])
    assert counts.param_count(a) == sum(l.size for l in leaves(a))


@pytest.mark.parametrize("kind", ["swa", "rwkv", "swa+rwkv"])
def test_reference_layout_is_the_programs(kind):
    from repro.models import init_params
    spec = tiny_spec(kind)["config"]["arch"]
    cfg = cell.program_config(spec)
    prog = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    ref = jax.eval_shape(lambda k: refmodel.init_params(
        cell.arch_of({"arch": spec}), k), jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert [l.shape for l in jax.tree.leaves(prog)] == \
        [l.shape for l in jax.tree.leaves(ref)]


def test_hand_count_at_a_tiny_swa_config():
    a = {"d_model": 8, "d_ff": 16, "vocab_size": 10, "num_layers": 2,
         "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
         "swa_window": 3, "layer_pattern": ["swa"]}
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; ffn 3 x 8x16 = 384
    assert counts.matmul_params(a) == 8 * 10 + 2 * (192 + 384)
    assert counts.param_count(a) == 2 * 80 + 8 + 2 * (192 + 384 + 16)
    # a sequence of 5 with window 3 keeps 1+2+3+3+3 = 12 (query, key)
    # pairs; each costs 2 matmuls of 2 * heads * head_dim FLOPs
    attn = 12 * 4 * 2 * 4
    assert counts.step_flops(a, batch=1, seq_len=5) == \
        6 * 1232 * 5 + 3 * 2 * attn


def test_hand_count_at_a_tiny_rwkv_config():
    a = {"d_model": 8, "d_ff": 16, "vocab_size": 10, "num_layers": 1,
         "num_heads": 2, "num_kv_heads": 2, "head_dim": 4,
         "layer_pattern": ["rwkv"]}
    mix = 4 * 8 * 8 + 2 * 8 * 64          # r, k, v, o; the rank-64 decay
    assert counts.matmul_params(a) == 8 * 10 + 3 * 8 * 16 + mix
    assert counts.param_count(a) == 2 * 80 + 8 + 3 * 8 * 16 + 16 + mix \
        + 5 * 8 + 2 * 4
    # per token and head: k v^T, r . S and the decayed update (hd^2
    # multiply-adds each) and the bonus term: 6 * 16 + 4 * 4 FLOPs
    per_seq = 3 * 2 * (6 * 16 + 4 * 4)
    assert counts.step_flops(a, batch=2, seq_len=3) == \
        6 * counts.matmul_params(a) * 6 + 3 * per_seq * 2


def test_update_bytes_are_the_required_ones():
    a = arch("rwkv6-1.6b-1L")
    p = counts.param_count(a)
    assert p == 329_533_440
    # theta read + write and gradient read in fp32; two bf16 means
    assert counts.update_bytes(a) == 16 * p


def test_rwkv_and_danube_counts_at_b4():
    r, d = arch("rwkv6-1.6b-1L"), arch("h2o-danube-1.8b-2L")
    assert counts.param_count(d) == 302_789_120
    # less the embedding, the norms' gains, the mixing vectors, w0 and u
    assert counts.matmul_params(r) == 329_533_440 - 65536 * 2048 - (
        2 * 2048 + 2048 + 5 * 2048 + 32 * 64)
    tf = counts.step_flops(r, 4, 1024) / 1e12
    assert 4.80 < tf < 4.82
    assert 5.5 < counts.step_flops(d, 4, 1024) / 1e12 < 5.6


def test_missing_device_is_an_error():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


# recorded from the counts of one layer kind, before they summed plug-ins
# over the layers of a pattern
PINNED = [("h2o-danube-1.8b-2L", 4, 5556739768320.0, 4844625920),
          ("rwkv6-1.6b-1L", 4, 4809390292992.0, 5272535040),
          ("rwkv6-1.6b-1L", 1, 1202347573248.0, 5272535040)]


@pytest.mark.parametrize("name,batch,flops,update", PINNED)
def test_counts_of_the_benchmark_cells_are_unchanged(name, batch, flops,
                                                     update):
    a = arch(name)
    assert counts.step_flops(a, batch, 1024) == flops
    assert counts.update_bytes(a) == update
