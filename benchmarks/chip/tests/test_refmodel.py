"""The reference model's layout and arithmetic: one-kind stacks as they
were, patterns of several kinds, the feed-forward plug-ins, attention in
query blocks, and the configuration as the program and the reference
each take it."""
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import refmodel
from conftest import tiny_spec

# (weights, log-likelihood, gradient) at seed 0 on the CPU, recorded from
# the one-kind reference before it took patterns of several kinds and
# feed-forward plug-ins: sha256 of the leaves' bytes (first 16 digits)
PINNED = [("swa", "d9b9ef3760f3a706", -787.6827392578125, "206da6275bfe712d"),
          ("rwkv", "85574b745b6ce22d", -781.6497802734375, "96d31cb1f82fbbf7")]


def digest(tree):
    return hashlib.sha256(b"".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(tree))
    ).hexdigest()[:16]


def weights_and_batch(pattern, seed=0):
    a = cell.arch_of(tiny_spec(pattern)["config"])
    key = cell.seed_key(seed)
    params = cell.make_weights(key, arch_items=tuple(sorted(a.items())))
    data = cell.make_shards(key, jnp.float32(0.1), S=1, N=2, seq=64,
                            vocab=a["vocab_size"])
    return a, params, {k: v[0] for k, v in data.items()}


@pytest.mark.parametrize("kind,weights,ll,grad", PINNED)
def test_one_kind_reference_is_bit_identical_to_before(kind, weights, ll,
                                                       grad):
    a, params, batch = weights_and_batch(kind)
    f = lambda p, b: refmodel.log_lik(p, a, b)  # noqa: E731
    assert digest(params) == weights
    assert float(jax.jit(f)(params, batch)) == ll
    assert digest(jax.jit(jax.grad(f))(params, batch)) == grad


def test_pattern_of_two_kinds_stacks_periods_and_keeps_the_rest():
    a, params, _ = weights_and_batch("swa+rwkv")
    assert set(params["blocks"]) == {"l0", "l1"}
    assert params["blocks"]["l0"]["attn"]["wq"].shape == (1, 64, 64)
    assert params["blocks"]["l1"]["mix"]["w_r"].shape == (1, 64, 64)
    assert set(params["rem_blocks"]) == {"l0"}
    assert params["rem_blocks"]["l0"]["attn"]["wq"].shape == (64, 64)
    # every layer draws its own weights
    assert not np.array_equal(params["blocks"]["l0"]["attn"]["wq"][0],
                              params["rem_blocks"]["l0"]["attn"]["wq"])


def test_pattern_applies_each_layer_once_in_order():
    """The three-layer swa+rwkv stack equals its layers applied by hand:
    swa, rwkv (the period), then swa (the layer past it)."""
    a, params, batch = weights_and_batch("swa+rwkv")
    pr = refmodel.Prec("fp32")
    ffn = refmodel.ffn_of(a)

    def layer(kind, x, p):
        mix = refmodel.mixer(kind)
        x = x + mix.forward(refmodel.rms_norm(x, p["norm"]),
                            p[mix.PARAM_KEY], a, pr)
        return x + ffn.forward(refmodel.rms_norm(x, p["ffn_norm"]),
                               p["ffn"], a, pr)[0]

    first = jax.tree.map(lambda t: t[0], params["blocks"])
    x = params["embed"][batch["tokens"]]
    x = layer("swa", x, first["l0"])
    x = layer("rwkv", x, first["l1"])
    x = layer("swa", x, params["rem_blocks"]["l0"])
    logits = pr.einsum("bsd,dv->bsv",
                       refmodel.rms_norm(x, params["final_norm"]),
                       params["head"])
    want = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               batch["labels"][..., None], -1).sum()
    got = refmodel.log_lik(params, a, batch)
    # float32, the same operations; scan and checkpoint may fuse them
    # otherwise: a few units of rounding in a sum of 128 terms of ~6
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_feed_forward_is_chosen_by_kind_and_its_term_is_added(monkeypatch):
    """``moe`` in the configuration picks ``ffns/moe.py``, else its
    ``ffn_type``; the term a feed-forward returns is added once per
    layer to the log-likelihood."""
    silu = refmodel.ffn_of({"ffn_type": "silu"})
    assert refmodel.ffn_of({}) is silu
    routed = types.SimpleNamespace(
        init=silu.init, param_count=silu.param_count,
        matmul_params=silu.matmul_params,
        forward=lambda h, p, arch, pr: (silu.forward(h, p, arch, pr)[0],
                                        jnp.float32(-0.25)))
    monkeypatch.setitem(refmodel._PLUGINS, ("ffns", "moe"), routed)
    a, params, batch = weights_and_batch("swa+rwkv")
    routed_arch = {**a, "moe": cell.freeze({"num_experts": 4, "top_k": 2})}
    assert refmodel.ffn_of(routed_arch) is routed
    dense = refmodel.log_lik(params, a, batch)
    with_term = refmodel.log_lik(params, routed_arch, batch)
    np.testing.assert_allclose(float(with_term), float(dense) - 3 * 0.25,
                               rtol=1e-6)


def test_dense_feed_forward_adds_nothing():
    a, params, batch = weights_and_batch("swa")
    p = jax.tree.map(lambda t: t[0], params["blocks"]["l0"]["ffn"])
    h = params["embed"][batch["tokens"]]
    y, term = refmodel.ffn_of(a).forward(h, p, a, refmodel.Prec("fp32"))
    assert y.shape == h.shape and float(term) == 0.0


@pytest.mark.parametrize("q_block", [16, 24])
def test_attention_in_query_blocks_equals_the_whole(q_block, monkeypatch):
    """The swa reference's log-likelihood and gradient with queries in
    blocks (the threshold lowered below the 64-token sequence; 24 does
    not divide 64, so blocks of 8) against the whole score matrix. The
    same sums in float32, but contracted in other shapes: XLA may order
    them otherwise, so agreement to a few units of float32 rounding."""
    a, params, batch = weights_and_batch("swa")
    f = lambda p: refmodel.log_lik(p, a, batch)  # noqa: E731
    whole_ll, whole_g = jax.value_and_grad(f)(params)
    monkeypatch.setattr(refmodel, "ATTN_BLOCK_ABOVE", 16)
    monkeypatch.setattr(refmodel, "ATTN_Q_BLOCK", q_block)
    ll, g = jax.value_and_grad(f)(params)
    np.testing.assert_allclose(float(ll), float(whole_ll), rtol=1e-6)
    for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(whole_g)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(y).max()))


def test_blocked_attention_keeps_one_block_of_scores_live():
    """Past the threshold no (b, H, s, s) score matrix is built: the
    largest intermediate of the gradient has one block's query rows."""
    pr = refmodel.Prec("fp32")
    s, H, hd = 2048, 2, 8
    keep = lambda qp, kp: kp <= qp  # noqa: E731
    q = jax.ShapeDtypeStruct((1, s, H, hd), jnp.float32)

    def loss(q, k, v):
        return refmodel.attention(q, k, v, keep, pr).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)

    def sizes(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                yield int(np.prod(var.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) == H * refmodel.ATTN_Q_BLOCK * s


def test_arch_of_takes_several_kinds_and_hashes_nested_groups():
    config = {"arch": {"layer_pattern": ["swa", "swa", "swa", "attn"],
                       "moe": {"num_experts": 4, "top_k": 2},
                       "rope": {"sizes": [1, 2]}}}
    a = cell.arch_of(config)
    assert a["layer_pattern"] == ("swa", "swa", "swa", "attn")
    assert a["moe"]["top_k"] == 2 and a["rope"]["sizes"] == (1, 2)
    items = tuple(sorted(a.items()))
    assert hash(items) == hash(tuple(sorted(cell.arch_of(config).items())))
    assert config["arch"]["moe"] == {"num_experts": 4, "top_k": 2}


def test_program_config_builds_nested_dataclasses():
    from repro.configs.base import MoEConfig
    arch = {**tiny_spec("swa")["config"]["arch"], "family": "moe",
            "moe": {"num_experts": 4, "top_k": 2}, "not_a_field": 1}
    cfg = cell.program_config(arch)
    assert cfg.moe == MoEConfig(num_experts=4, top_k=2)
    assert cfg.layer_pattern == ("swa",)
    assert cell.program_config(tiny_spec("swa")["config"]["arch"]).moe \
        is None
