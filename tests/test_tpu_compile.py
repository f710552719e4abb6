"""AOT compiles of the fused update kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler (installed with JAX) lowers the Pallas
kernels with ``interpret=False`` for a chip that is described, not
attached, and refuses what Mosaic would refuse on the chip (unaligned
blocks, SMEM or VMEM overflow, casts it cannot lower). Shapes are the
real ones: h2o-danube-1.8b's leaves, and rwkv6-1.6b's vocabulary-wide
embedding and head, at their published widths.

The topology is described inside a module fixture (never on import): only
one process at a time may load the TPU library, and it is this file's
worker that does.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fsgld_update import (LANE, PACK_BLOCK_ROWS_MAX,
                                        SCALAR_COLS, fsgld_update_2d,
                                        fsgld_update_packed)
from repro.kernels.ops import make_packed_layout
from repro.models import init_params

CFG = get_config("h2o-danube-1.8b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"


def _operands(variant, dynamics, sds, rows_chains, rows_shared):
    kw = {}
    if variant in ("scalar", "diag"):
        kw.update(mu_g=sds((rows_shared, LANE)),
                  mu_s=sds((rows_chains, LANE)))
    if variant == "diag":
        kw.update(lam_g=sds((rows_shared, LANE)),
                  lam_s=sds((rows_chains, LANE)))
    if dynamics == "sghmc":
        kw["r2d"] = sds((rows_chains, LANE))
    return kw


@pytest.mark.parametrize("chains", [1, 4])
def test_per_leaf_kernel_compiles_for_v5e(one_chip, chains):
    """One attention projection (d_model x d_model) per chain."""
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows_c = CFG.d_model * CFG.q_dim // LANE
    rows = rows_c * chains
    kw = _operands("scalar", "langevin", sds, rows, rows_c)

    def fn(th, g, seed, sc, kw):
        return fsgld_update_2d(th, g, seed, sc, variant="scalar",
                               chains=chains, interpret=False, **kw)

    _compile(fn, (sds((rows, LANE)), sds((rows, LANE)),
                  sds((chains,), jnp.uint32),
                  sds((chains, SCALAR_COLS)), kw))


@pytest.mark.parametrize("variant,dynamics",
                         [("scalar", "langevin"), ("plain", "sghmc")])
def test_packed_kernel_compiles_for_v5e(one_chip, variant, dynamics):
    """Every leaf of one full-width h2o-danube layer, 2 chains, one
    pallas_call at the packed layout (the block height
    ``choose_block_rows`` picks from the leaf shapes: 512 rows)."""
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    C = 2
    one = dataclasses.replace(CFG, num_layers=1)
    layer = jax.eval_shape(
        lambda: init_params(one, jax.random.PRNGKey(0)))["blocks"]
    layout = make_packed_layout(layer)
    assert layout.block_rows == PACK_BLOCK_ROWS_MAX
    rows = C * layout.rows_total
    kw = _operands(variant, dynamics, sds, rows, layout.rows_total)

    def fn(th, g, seeds, sc, kw):
        return fsgld_update_packed(
            th, g, seeds, sc, variant=variant, dynamics=dynamics,
            leaf_blocks=layout.leaf_blocks, block_rows=layout.block_rows,
            chains=C, interpret=False, **kw)

    _compile(fn, (sds((rows, LANE)), sds((rows, LANE)),
                  sds((C, layout.num_leaves), jnp.uint32),
                  sds((C, layout.num_leaves, SCALAR_COLS)), kw))


@pytest.mark.parametrize("variant,dynamics",
                         [("scalar", "langevin"), ("diag", "sghmc")])
def test_packed_kernel_compiles_for_v5e_at_rwkv_vocab_leaves(
        one_chip, variant, dynamics):
    """rwkv6-1.6b's (65536, 2048) embedding and (2048, 65536) head, one
    chain, at the block height the rule picks for them; ``diag``/``sghmc``
    holds the most operands, so a body that outgrows VMEM fails here."""
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d_model, vocab = 2048, 65536
    leaves = {"embed": jax.ShapeDtypeStruct((vocab, d_model), jnp.float32),
              "head": jax.ShapeDtypeStruct((d_model, vocab), jnp.float32)}
    layout = make_packed_layout(leaves)
    assert layout.block_rows == PACK_BLOCK_ROWS_MAX
    rows = layout.rows_total
    kw = _operands(variant, dynamics, sds, rows, rows)

    def fn(th, g, seeds, sc, kw):
        return fsgld_update_packed(
            th, g, seeds, sc, variant=variant, dynamics=dynamics,
            leaf_blocks=layout.leaf_blocks, block_rows=layout.block_rows,
            interpret=False, **kw)

    _compile(fn, (sds((rows, LANE)), sds((rows, LANE)),
                  sds((1, layout.num_leaves), jnp.uint32),
                  sds((1, layout.num_leaves, SCALAR_COLS)), kw))
