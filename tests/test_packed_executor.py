"""Packed single-launch executor (PR 2; multi-segment PR 4) correctness.

Contracts under test:

  * packed single-launch steps are BIT-IDENTICAL to the per-leaf
    chain-batched kernel — and therefore to the ``run_vmap`` oracle — for
    plain / scalar / diag variants, BOTH dynamics (langevin momentum-free
    and SGHMC with the second momentum buffer), multi-leaf pytrees, and
    ragged shards (the full executor x dynamics x dtype grid lives in
    tests/test_parity_matrix.py);
  * the block height comes from the leaf shapes (``choose_block_rows``:
    512 rows at the benchmark configurations' widths, 8 for small ragged
    leaves, padding at most 1/256), and a step's state does not depend
    on it;
  * one ``pallas_call`` per step for the whole chain block and ZERO
    ``pad`` primitives inside the scan bodies (asserted on the jaxpr);
  * the compiled executor names its layers: each ``fsgld.*`` named scope
    is in the optimized HLO's ``op_name`` metadata;
  * ``MeshChainEngine.run`` traces ONCE for R rounds (scan-over-rounds,
    no per-round retrace or dispatch);
  * ``PackedChains`` pack/unpack round-trips exactly for any floating
    dtype mix and ``quantize`` replays the per-leaf storage-dtype
    round-trip (identity object for all-fp32 layouts);
  * odd-chain pad devices SKIP pad-chain gradient work
    (``make_masked_grad_vmap``, asserted on the switch branch jaxprs).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.base import SamplerConfig
from repro.core import (FederatedSampler, MeshChainEngine, make_bank,
                        pad_shards, analytic_gaussian_likelihood_surrogate)
from repro.core.engine import pack_bank, resident_surrogates
from repro.kernels import ops
from repro.models import init_params


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------

def log_lik_flat(theta, batch):
    return -0.5 * jnp.sum((batch["x"] - theta) ** 2)


def log_lik_tree(theta, batch):
    pred = batch["x"] @ theta["w"] + theta["b"]
    return -0.5 * jnp.sum((batch["y"] - pred) ** 2)


def _flat_problem(key, S=5, n=40, d=3):
    mus = jax.random.uniform(key, (S, d), minval=-4, maxval=4)
    x = mus[:, None, :] + jax.random.normal(jax.random.fold_in(key, 1),
                                            (S, n, d))
    mu_s, prec_s = jax.vmap(analytic_gaussian_likelihood_surrogate)(x)
    return {"x": x}, make_bank(mu_s, prec_s, "diag")


def _tree_problem(key, S=4, n=24, din=2, dout=600):
    """Multi-leaf linear-model posterior + 'scalar' surrogate bank.
    dout=600 makes the w leaf (2, 600) span TWO packed blocks, so the
    engine-level oracle comparison also covers in-leaf base offsets."""
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (S, n, din))
    w_true = jax.random.normal(ks[1], (din, dout))
    y = x @ w_true + 0.1 * jax.random.normal(ks[2], (S, n, dout))
    theta0 = {"b": jnp.zeros(dout), "w": jnp.zeros((din, dout))}
    means = {"b": jax.random.normal(ks[3], (S, dout)) * 0.1,
             "w": jnp.broadcast_to(w_true[None], (S, din, dout))
             + 0.1 * jax.random.normal(ks[3], (S, din, dout))}
    precs = {"b": jnp.linspace(1.0, 2.0, S),
             "w": jnp.linspace(3.0, 5.0, S)}
    return {"x": x, "y": y}, make_bank(means, precs, "scalar"), theta0


def _ragged_problem(key, S=5, d=3):
    base = jax.random.normal(key, (S, 64, d)) + jnp.arange(S)[:, None, None]
    per_shard = [{"x": base[s, : 12 + 9 * s]} for s in range(S)]
    stacked, sizes = pad_shards(per_shard)  # NaN pad: touching it poisons
    xs = [p["x"] for p in per_shard]
    mu = jnp.stack([x.mean(0) for x in xs])
    prec = jnp.stack([jnp.full((d,), float(x.shape[0])) for x in xs])
    return stacked, sizes, make_bank(mu, prec, "diag")


# ---------------------------------------------------------------------------
# unit level: packed_step == per-leaf chain-batched kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dynamics", ["langevin", "sghmc"])
@pytest.mark.parametrize("variant", ["plain", "scalar"])
@pytest.mark.parametrize("block_rows", [8, 32])
def test_packed_step_bitmatches_per_leaf_kernel_multileaf(block_rows,
                                                          variant,
                                                          dynamics):
    key = jax.random.PRNGKey(0)
    C, S = 4, 5
    # "b" spans MULTIPLE packed blocks at both heights (10000 > 2 *
    # 32*LANE = 8192): a non-zero in-leaf block offset and a leaf
    # spanning several blocks — the paths a single-block leaf never
    # touches — are exercised here
    shapes = {"a": (37,), "b": (2, 5000), "c": (3,)}
    ks = jax.random.split(key, 10)
    theta = {n: jax.random.normal(jax.random.fold_in(ks[0], i), (C,) + s)
             for i, (n, s) in enumerate(shapes.items())}
    g = {n: jax.random.normal(jax.random.fold_in(ks[1], i), (C,) + s)
         for i, (n, s) in enumerate(shapes.items())}
    keys = jax.random.split(ks[2], C)
    sids = jnp.array([0, 2, 2, 4], jnp.int32)
    scale = jnp.linspace(10.0, 40.0, C)
    f_s = jnp.linspace(0.1, 0.4, C)
    kw = dict(h=1e-4, prior_prec=1.0, alpha=1.0, temperature=1.0)
    hmc = dynamics == "sghmc"
    dyn_kw = dict(dynamics=dynamics, friction=0.25) if hmc else {}
    mom = {n: 0.01 * jax.random.normal(jax.random.fold_in(ks[4], i),
                                       (C,) + s)
           for i, (n, s) in enumerate(shapes.items())} if hmc else None

    if variant == "plain":
        bank, kind = None, None
    else:
        means = {n: jax.random.normal(jax.random.fold_in(ks[3], i),
                                      (S,) + s)
                 for i, (n, s) in enumerate(shapes.items())}
        precs = {n: jnp.linspace(0.5, 1.5, S) + i
                 for i, n in enumerate(shapes)}
        bank, kind = make_bank(means, precs, "scalar"), "scalar"

    ref = ops.fused_update_chains_tree(
        theta, g, keys, scale=scale, f_s=f_s, bank=bank, sids=sids,
        surrogate_kind=kind, momentum=mom, **dyn_kw, **kw)
    ref_r = None
    if hmc:
        ref, ref_r = ref

    layout = ops.make_packed_layout(jax.tree.map(lambda t: t[0], theta),
                                    block_rows=block_rows)
    assert layout.leaf_blocks[1] > 1
    th_p = layout.pack(theta)
    g_p = layout.pack(g)
    seeds = ops.chain_leaf_seeds(keys, layout.num_leaves)
    if variant == "plain":
        mu_g = mu_s = None
        lam_g_leaf = lam_s_leaf = None
    else:
        pb = pack_bank(layout, bank)
        mu_g = pb["mu_g"]
        mu_s, _ = resident_surrogates(layout, pb, sids)
        lam_g_leaf = pb["lam_g_leaf"]
        lam_s_leaf = pb["lam_s_leaf"][sids]
    scalars = ops.packed_scalar_rows(
        layout, scale=scale, f_s=f_s, lam_g_leaf=lam_g_leaf,
        lam_s_leaf=lam_s_leaf, friction=(0.25 if hmc else 0.0), **kw)
    out_p = ops.packed_step(layout, th_p, g_p, seeds, scalars,
                            variant=variant if bank else "plain",
                            mu_g=mu_g, mu_s=mu_s,
                            r_p=(layout.pack(mom) if hmc else None),
                            dynamics=dynamics)
    if hmc:
        got, got_r = layout.unpack(out_p[0]), layout.unpack(out_p[1])
    else:
        got, got_r = layout.unpack(out_p), None
    for n in shapes:
        np.testing.assert_array_equal(np.asarray(got[n]),
                                      np.asarray(ref[n]), err_msg=n)
        if hmc:
            np.testing.assert_array_equal(np.asarray(got_r[n]),
                                          np.asarray(ref_r[n]),
                                          err_msg=f"momentum:{n}")


def test_packed_step_bitmatches_per_leaf_kernel_diag():
    key = jax.random.PRNGKey(1)
    C, S, P = 4, 5, 3001  # > 2 packed blocks: in-leaf base offsets live
    ks = jax.random.split(key, 8)
    theta = jax.random.normal(ks[0], (C, P))
    g = jax.random.normal(ks[1], (C, P))
    keys = jax.random.split(ks[2], C)
    sids = jnp.array([1, 0, 3, 3], jnp.int32)
    scale = jnp.linspace(5.0, 20.0, C)
    f_s = jnp.linspace(0.2, 0.5, C)
    bank = make_bank(jax.random.normal(ks[3], (S, P)),
                     jnp.abs(jax.random.normal(ks[4], (S, P))) + 0.1,
                     "diag")
    kw = dict(h=1e-4, prior_prec=1.0, alpha=1.0, temperature=1.0)

    ref = ops.fused_update_chains_tree(
        theta, g, keys, scale=scale, f_s=f_s, bank=bank, sids=sids,
        surrogate_kind="diag", **kw)

    layout = ops.make_packed_layout(theta[0])
    pb = pack_bank(layout, bank)
    mu_s, lam_s = resident_surrogates(layout, pb, sids)
    seeds = ops.chain_leaf_seeds(keys, layout.num_leaves)
    scalars = ops.packed_scalar_rows(layout, scale=scale, f_s=f_s, **kw)
    out_p = ops.packed_step(
        layout, layout.pack(theta), layout.pack(g), seeds, scalars,
        variant="diag", mu_g=pb["mu_g"], lam_g=pb["lam_g"], mu_s=mu_s,
        lam_s=lam_s)
    np.testing.assert_array_equal(np.asarray(layout.unpack(out_p)),
                                  np.asarray(ref))


def _packed_state_at(block_rows, variant, dynamics, tree):
    """Leaves after one ``packed_step`` at a given block height (and the
    storage-dtype round trip), from fixed inputs: the surrogate operands
    are packed straight from trees, so every variant's drift runs."""
    C = 3
    ks = jax.random.split(jax.random.PRNGKey(11), 8)

    def draw(k, lead=(C,)):
        return {n: jax.random.normal(jax.random.fold_in(k, i),
                                     lead + s.shape).astype(s.dtype)
                for i, (n, s) in enumerate(tree.items())}

    layout = ops.make_packed_layout(tree, block_rows=block_rows)
    L = layout.num_leaves
    kw = {}
    if variant != "plain":
        kw["mu_g"] = layout.pack_shared(draw(ks[2], ()))
        kw["mu_s"] = layout.pack(draw(ks[3]))
    if variant == "diag":
        kw["lam_g"] = jnp.abs(layout.pack_shared(draw(ks[4], ())))
        kw["lam_s"] = jnp.abs(layout.pack(draw(ks[5])))
    if dynamics == "sghmc":
        kw["r_p"] = 0.01 * layout.pack(draw(ks[6]))
    scalars = ops.packed_scalar_rows(
        layout, h=1e-3, scale=jnp.linspace(5.0, 20.0, C),
        f_s=jnp.linspace(0.2, 0.5, C), prior_prec=1.0, alpha=1.0,
        temperature=1.0, lam_g_leaf=jnp.linspace(1.0, 2.0, L),
        lam_s_leaf=jnp.linspace(0.5, 1.5, C * L).reshape(C, L),
        friction=0.25 if dynamics == "sghmc" else 0.0)
    out = ops.packed_step(
        layout, layout.pack(draw(ks[0])), layout.pack(draw(ks[1])),
        ops.chain_leaf_seeds(jax.random.split(ks[7], C), L), scalars,
        variant=variant, dynamics=dynamics, **kw)
    outs = out if dynamics == "sghmc" else (out,)
    return [layout.unpack(layout.quantize(o)) for o in outs]


@pytest.mark.parametrize("dynamics", ["langevin", "sghmc"])
@pytest.mark.parametrize("variant", ["plain", "scalar", "diag"])
def test_packed_state_does_not_depend_on_block_height(variant, dynamics):
    """The same step at 8- and 32-row blocks gives equal leaves: live
    elements keep their (chain, leaf) seed and in-leaf index, only the
    pad at each leaf's tail grows. A bf16 leaf rides the quantize path."""
    tree = {"a": jax.ShapeDtypeStruct((37,), jnp.float32),
            "b": jax.ShapeDtypeStruct((3, 3000), jnp.float32),
            "c": jax.ShapeDtypeStruct((5000,), jnp.bfloat16)}
    lo = _packed_state_at(8, variant, dynamics, tree)
    hi = _packed_state_at(32, variant, dynamics, tree)
    for got_lo, got_hi in zip(lo, hi):
        for n in tree:
            assert got_hi[n].dtype == tree[n].dtype
            np.testing.assert_array_equal(np.asarray(got_lo[n]),
                                          np.asarray(got_hi[n]), err_msg=n)


# ---------------------------------------------------------------------------
# the block rule: block height from the leaf shapes
# ---------------------------------------------------------------------------

def _rows_at(sizes, block_rows):
    return sum(-(-n // (block_rows * 128)) * block_rows for n in sizes)


def test_block_rule_keeps_small_ragged_leaves_at_the_floor():
    """At 16 rows this tree would pad 60% more rows: it keeps 8."""
    tree = {"a": jnp.zeros(37), "b": jnp.zeros((2, 1300)),
            "c": jnp.zeros(3)}
    assert ops.make_packed_layout(tree).block_rows == 8
    assert _rows_at([37, 2600, 3], 16) > 1.5 * _rows_at([37, 2600, 3], 8)


def _benchmark_arch(name):
    if name == "h2o-danube-1.8b-2L":
        return dataclasses.replace(get_config("h2o-danube-1.8b"),
                                   num_layers=2)
    # RWKV-6 Finch 1.6B's widths on the registry's RWKV-6 family
    return dataclasses.replace(get_config("rwkv6-7b"), num_layers=1,
                               d_model=2048, num_heads=32, num_kv_heads=32,
                               head_dim=64, d_ff=7168)


@pytest.mark.parametrize("name,params,grid", [
    ("h2o-danube-1.8b-2L", 302_789_120, 4_623),
    ("rwkv6-1.6b-1L", 329_533_440, 5_037)])
def test_block_rule_picks_512_at_benchmark_widths(name, params, grid):
    """Shapes only (``jax.eval_shape``): no weights, no compile."""
    cfg = _benchmark_arch(name)
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    sizes = [int(l.size) for l in jax.tree.leaves(tree)]
    assert sum(sizes) == params
    layout = ops.make_packed_layout(tree)
    assert layout.block_rows == 512
    assert sum(layout.leaf_blocks) == grid
    assert layout.rows_total * 256 <= _rows_at(sizes, 8) * 257


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(1, 3 * 10**7), min_size=1, max_size=24))
def test_block_rule_pads_at_most_1_in_256(sizes):
    b = ops.choose_block_rows(sizes)
    floor = _rows_at(sizes, 8)
    assert b in (8, 16, 32, 64, 128, 256, 512)
    assert (_rows_at(sizes, b) - floor) * 256 <= floor
    # the largest such height up to the cap
    taller = [2 * b * 2**k for k in range(8) if 2 * b * 2**k <= 512]
    assert all((_rows_at(sizes, t) - floor) * 256 > floor for t in taller)


# ---------------------------------------------------------------------------
# engine level: packed executor vs the run_vmap oracle
# ---------------------------------------------------------------------------

def test_packed_engine_bitmatches_oracle_multileaf_scalar_bank():
    """Multi-leaf pytree + 'scalar' bank through the full engine: packed
    single-launch rounds equal the legacy per-chain kernel vmap bitwise."""
    data, bank, theta0 = _tree_problem(jax.random.PRNGKey(2))
    cfg = SamplerConfig(method="fsgld", step_size=1e-4, num_shards=4,
                        local_updates=4, prior_precision=1.0,
                        surrogate="scalar")
    eng = MeshChainEngine(log_lik_tree, cfg, data, minibatch=6, bank=bank,
                          use_kernel=True)
    assert eng._layout_for(theta0) is not None, "packed path not taken"
    tr = eng.run(jax.random.PRNGKey(7), theta0, 3, n_chains=4)
    legacy = FederatedSampler(log_lik_tree, cfg, data, minibatch=6,
                              bank=bank, use_kernel=True)
    ref = legacy.run_vmap(jax.random.PRNGKey(7), theta0, 3, n_chains=4)
    for name in theta0:
        assert tr[name].shape == (4, 12) + theta0[name].shape
        np.testing.assert_array_equal(np.asarray(tr[name]),
                                      np.asarray(ref[name]), err_msg=name)


@pytest.mark.parametrize("method", ["sgld", "dsgld", "fsgld"])
def test_packed_engine_bitmatches_oracle_flat_diag(method):
    data, bank = _flat_problem(jax.random.PRNGKey(0))
    cfg = SamplerConfig(method=method, step_size=1e-4, num_shards=5,
                        local_updates=5, prior_precision=1.0)
    eng = MeshChainEngine(log_lik_flat, cfg, data, minibatch=8,
                          bank=bank if method == "fsgld" else None,
                          use_kernel=True)
    tr = eng.run(jax.random.PRNGKey(3), jnp.zeros(3), 4, n_chains=4)
    legacy = FederatedSampler(log_lik_flat, cfg, data, minibatch=8,
                              bank=bank if method == "fsgld" else None,
                              use_kernel=True)
    ref = legacy.run_vmap(jax.random.PRNGKey(3), jnp.zeros(3), 4,
                          n_chains=4)
    np.testing.assert_array_equal(np.asarray(tr), np.asarray(ref))


def test_packed_engine_matches_per_leaf_engine_ragged():
    """Ragged NaN-padded shards: the packed executor equals the per-leaf
    chain-batched engine bitwise and never touches a pad row."""
    stacked, sizes, bank = _ragged_problem(jax.random.PRNGKey(4))
    S = len(sizes)
    cfg = SamplerConfig(method="fsgld", step_size=1e-4, num_shards=S,
                        local_updates=3, prior_precision=1.0)
    kw = dict(minibatch=6, bank=bank, sizes=sizes, use_kernel=True)
    packed = MeshChainEngine(log_lik_flat, cfg, stacked, **kw)
    per_leaf = MeshChainEngine(log_lik_flat, cfg, stacked, packed=False,
                               **kw)
    a = packed.run(jax.random.PRNGKey(5), jnp.zeros(3), 3, n_chains=4,
                   reassign="permutation")
    b = per_leaf.run(jax.random.PRNGKey(5), jnp.zeros(3), 3, n_chains=4,
                     reassign="permutation")
    assert bool(jnp.all(jnp.isfinite(a)))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# dispatch economics: one trace for R rounds, one pallas_call per step
# ---------------------------------------------------------------------------

def _trace_count(num_rounds):
    calls = []

    def counting_ll(theta, batch):
        calls.append(1)
        return -0.5 * jnp.sum((batch["x"] - theta) ** 2)

    data, bank = _flat_problem(jax.random.PRNGKey(0))
    cfg = SamplerConfig(method="fsgld", step_size=1e-4, num_shards=5,
                        local_updates=3, prior_precision=1.0)
    eng = MeshChainEngine(counting_ll, cfg, data, minibatch=8, bank=bank,
                          use_kernel=True)
    eng.run(jax.random.PRNGKey(7), jnp.zeros(3), num_rounds, n_chains=4)
    first = len(calls)
    # same executor again: cached jit, zero retraces
    eng.run(jax.random.PRNGKey(8), jnp.zeros(3), num_rounds, n_chains=4)
    return first, len(calls)


def test_run_traces_once_for_r_rounds():
    """scan-over-rounds: trace work is CONSTANT in the round count (the
    old host loop retraced nothing but re-dispatched per round; a naive
    unrolled jit would retrace per round), and a second run() with the
    same shape is a pure cache hit."""
    first2, second2 = _trace_count(2)
    first6, second6 = _trace_count(6)
    assert first2 == first6, (first2, first6)
    assert second2 == first2, "second run() retraced"
    assert second6 == first6, "second run() retraced"


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _all_eqns(sub)


def _subjaxprs(v):
    if hasattr(v, "jaxpr"):           # ClosedJaxpr
        return [v.jaxpr]
    if hasattr(v, "eqns"):            # raw Jaxpr
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for x in v for j in _subjaxprs(x)]
    return []


def test_packed_run_jaxpr_single_pallas_call_no_pad_in_scan():
    """Acceptance gate: the WHOLE R-round executor jaxpr contains exactly
    one pallas_call (the single-launch step inside the nested scans — not
    one per leaf, not one per round) and no `pad` primitive inside any
    scan body (pack/unpack are hoisted update-slices/slices)."""
    data, bank, theta0 = _tree_problem(jax.random.PRNGKey(2))
    cfg = SamplerConfig(method="fsgld", step_size=1e-4, num_shards=4,
                        local_updates=4, prior_precision=1.0,
                        surrogate="scalar")
    eng = MeshChainEngine(log_lik_tree, cfg, data, minibatch=6, bank=bank,
                          use_kernel=True)
    layout = eng._layout_for(theta0)
    assert layout is not None and layout.num_leaves == 2
    execute = eng._executor(num_rounds=3, n_chains=4,
                            reassign="categorical", collect=True,
                            collect_every=2, layout=layout)
    chains = jax.tree.map(
        lambda t: jnp.zeros((4,) + t.shape, t.dtype), theta0)
    jaxpr = jax.make_jaxpr(execute)(
        jax.random.PRNGKey(0), chains, data, bank,
        jnp.asarray(0, jnp.int32), None, None)

    eqns = list(_all_eqns(jaxpr.jaxpr))
    pallas = [e for e in eqns if "pallas" in e.primitive.name]
    assert len(pallas) == 1, [e.primitive.name for e in pallas]

    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert scans, "no scan in the executor: rounds loop not scanned"
    for s in scans:
        body = [e.primitive.name
                for e in _all_eqns(s.params["jaxpr"].jaxpr)]
        assert "pad" not in body, "pad op inside a scan body"
        assert body.count("pallas_call") <= 1


@pytest.mark.parametrize("packed,scopes", [
    (True, {"fsgld.batch", "fsgld.grad", "fsgld.pack", "fsgld.conducive",
            "fsgld.update"}),
    (False, {"fsgld.batch", "fsgld.grad", "fsgld.update"})])
def test_compiled_executor_names_its_layers(packed, scopes):
    """Every fsgld.* named scope of the round body survives compilation
    as a component of some op's op_name in the optimized HLO: the names
    a profiler trace attributes device time by."""
    data, bank, theta0 = _tree_problem(jax.random.PRNGKey(2))
    cfg = SamplerConfig(method="fsgld", step_size=1e-4, num_shards=4,
                        local_updates=2, prior_precision=1.0,
                        surrogate="scalar")
    eng = MeshChainEngine(log_lik_tree, cfg, data, minibatch=6, bank=bank,
                          use_kernel=True, packed=packed)
    execute = eng._executor(num_rounds=1, n_chains=4,
                            reassign="permutation", collect=False,
                            collect_every=1, layout=eng._layout_for(theta0))
    chains = jax.tree.map(
        lambda t: jnp.zeros((4,) + t.shape, t.dtype), theta0)
    hlo = execute.lower(
        jax.random.PRNGKey(0), chains, data, bank,
        jnp.asarray(0, jnp.int32), None, None).compile().as_text()
    found = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
             for part in name.split("/") if part.startswith("fsgld.")}
    assert found == scopes


def test_packed_float_only_guard():
    """bf16 (any floating dtype) now PACKS — the PR 2 fp32-only guard is
    gone; only non-float leaves fall off the packed path (auto) or refuse
    (explicit packed=True)."""
    data, bank = _flat_problem(jax.random.PRNGKey(0))
    cfg = SamplerConfig(method="dsgld", step_size=1e-4, num_shards=5,
                        local_updates=2, prior_precision=1.0)
    eng = MeshChainEngine(log_lik_flat, cfg, data, minibatch=8,
                          use_kernel=True)
    assert eng._layout_for(jnp.zeros(3, jnp.bfloat16)) is not None
    # auto mode: non-FLOAT params silently fall back to the per-leaf path
    assert eng._layout_for({"w": jnp.zeros(3),
                            "steps": jnp.zeros(3, jnp.int32)}) is None
    # explicit packed=True refuses instead of changing dtype semantics
    eng2 = MeshChainEngine(log_lik_flat, cfg, data, minibatch=8,
                           use_kernel=True, packed=True)
    with pytest.raises(ValueError):
        eng2._layout_for({"w": jnp.zeros(3),
                          "steps": jnp.zeros(3, jnp.int32)})


# ---------------------------------------------------------------------------
# PackedChains pack/unpack round-trips: mixed dtypes, ragged/odd leaf shapes
# ---------------------------------------------------------------------------

_RT_DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]


@settings(max_examples=20, deadline=None)
@given(n_a=st.integers(1, 2200), n_b=st.integers(1, 3000),
       chains=st.integers(1, 5), dt_combo=st.integers(0, 26))
def test_pack_unpack_roundtrip_mixed_dtypes(n_a, n_b, chains, dt_combo):
    """Property: pack -> unpack is the identity for ANY mix of floating
    leaf dtypes and ragged leaf sizes (leaves spanning one block, many
    blocks, or a fraction of one). Narrow-dtype leaves widen to fp32
    losslessly, so the round trip is exact, and quantize() on a
    fresh-packed buffer is a fixed point. ``dt_combo`` decodes base-3 into
    the three leaf dtypes (0 = all fp32 ... 26 = all fp16)."""
    dt_a, dt_b, dt_c = dt_combo % 3, (dt_combo // 3) % 3, dt_combo // 9
    shapes = {"a": ((n_a,), _RT_DTYPES[dt_a]),
              "b": ((2, n_b), _RT_DTYPES[dt_b]),
              "c": ((37,), _RT_DTYPES[dt_c])}
    key = jax.random.PRNGKey(n_a * 7 + n_b * 3 + dt_combo)
    tree = {n: jax.random.normal(jax.random.fold_in(key, i),
                                 (chains,) + s).astype(dt)
            for i, (n, (s, dt)) in enumerate(shapes.items())}
    layout = ops.make_packed_layout(jax.tree.map(lambda t: t[0], tree))
    buf = layout.pack(tree)
    assert buf.shape == (chains * layout.rows_total, ops.LANE)
    assert buf.dtype == jnp.float32
    back = layout.unpack(buf)
    for n in tree:
        assert back[n].dtype == tree[n].dtype, n
        np.testing.assert_array_equal(np.asarray(back[n]),
                                      np.asarray(tree[n]), err_msg=n)
    # storage-dtype values are a fixed point of the per-step quantize
    np.testing.assert_array_equal(np.asarray(layout.quantize(buf)),
                                  np.asarray(buf))


def test_quantize_matches_per_leaf_dtype_roundtrip():
    """quantize() == unpack -> cast-to-storage-dtype -> repack, i.e. the
    exact round trip the per-leaf kernel applies each step, on values NOT
    already representable in the storage dtype."""
    tree = {"a": jnp.zeros((3, 513), jnp.bfloat16),
            "w": jnp.zeros((3, 2, 300), jnp.float32)}
    layout = ops.make_packed_layout(jax.tree.map(lambda t: t[0], tree))
    # fresh fp32 values with mantissas bf16 cannot hold
    buf = layout.pack({"a": jax.random.normal(jax.random.PRNGKey(0),
                                              (3, 513)) * 1.2345,
                       "w": jax.random.normal(jax.random.PRNGKey(1),
                                              (3, 2, 300)) * 1.2345})
    q = layout.quantize(buf)
    ref = layout.pack(layout.unpack(buf))  # unpack casts to leaf dtypes
    np.testing.assert_array_equal(np.asarray(q), np.asarray(ref))
    got = layout.unpack(q)
    # fp32 leaf untouched bitwise; bf16 leaf actually rounded
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(layout.unpack(buf)["w"]))
    raw_a = np.asarray(buf.reshape(3, -1)[:, :513], np.float32)
    assert not np.array_equal(np.asarray(got["a"], np.float32), raw_a)


def test_quantize_identity_for_fp32_layout():
    """All-fp32 layouts return the SAME buffer object: zero added ops in
    the scanned round body (the no-pad/single-pallas jaxpr gate relies on
    this)."""
    tree = {"a": jnp.zeros((2, 40)), "b": jnp.zeros((2, 7))}
    layout = ops.make_packed_layout(jax.tree.map(lambda t: t[0], tree))
    buf = layout.pack(tree)
    assert layout.quantize(buf) is buf


# ---------------------------------------------------------------------------
# pad-chain masking: odd-chain blocks skip pad gradients, not discard them
# ---------------------------------------------------------------------------

def test_masked_grad_vmap_skips_pad_chain_gradients():
    """ROADMAP open item: with n_chains=3 on a 2-way data axis (per=2,
    one pad chain), the pad device's switch branch must compute the
    gradient over ONE chain and concatenate a zero row — not vmap the
    full block and discard. Asserted structurally on the branch jaxprs."""
    from repro.core.engine import make_masked_grad_vmap
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P

    d = 3
    grad_fn = jax.grad(lambda th, b: -0.5 * jnp.sum((b["x"] - th) ** 2))
    masked = make_masked_grad_vmap(grad_fn, per=2, n_chains=3, d_size=2)
    # no padding -> the plain vmap shortcut, no switch at all
    plain = make_masked_grad_vmap(grad_fn, per=2, n_chains=4, d_size=2)
    thetas = jnp.zeros((2, d))
    batches = {"x": jnp.zeros((2, 6, d))}
    pj = jax.make_jaxpr(plain)(thetas, batches)
    assert all(e.primitive.name != "cond" for e in _all_eqns(pj.jaxpr))

    # axis_index needs an axis context: trace inside shard_map on the
    # host mesh (the switch itself only cares about the traced index)
    mesh = make_host_mesh()
    fn = jax.shard_map(masked, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(thetas, batches)
    conds = [e for e in _all_eqns(jaxpr.jaxpr)
             if e.primitive.name == "cond"]
    assert conds, "pad masking switch missing from the round gradient pass"
    branches = conds[0].params["branches"]
    assert len(branches) == 2

    def has_padding_concat(bj):
        return any(
            e.primitive.name == "concatenate"
            and tuple(e.outvars[0].aval.shape) == (2, d)
            and tuple(e.invars[-1].aval.shape) == (1, d)
            for e in _all_eqns(bj.jaxpr))

    def grad_widths(bj):
        # leading dims of sliced per-branch gradient operands: the pad
        # branch must slice the block down to its single real chain
        return {tuple(e.outvars[0].aval.shape)[0]
                for e in _all_eqns(bj.jaxpr)
                if e.primitive.name in ("slice", "dynamic_slice")
                and len(e.outvars[0].aval.shape) >= 2}

    pad_branches = [b for b in branches if has_padding_concat(b)]
    full_branches = [b for b in branches if not has_padding_concat(b)]
    assert len(pad_branches) == 1 and len(full_branches) == 1
    assert 1 in grad_widths(pad_branches[0]), \
        "pad branch never sliced the block to its real chains"
