"""Bring-up check on TPU: FSGLD chains at the published widths of
h2o-danube-1.8b, through the train driver, on the packed Pallas executor.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip path only, four chips

Every phase runs in this one process (a chip belongs to one process), and
any failure ends the script with a non-zero code and no result line. It
refuses to run unless JAX's first device is a TPU. The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

One chip:
  kernel  the packed update kernel at one full-width layer's packed
          layout, compiled (a ``tpu_custom_call`` in its HLO), against the
          pure-jnp oracle ``repro.kernels.ref``;
  (a)     the FSGLD job through ``repro.launch.train`` with the packed
          executor, chain 0 checkpointed;
  (b)     the same key and job on the ``vmap`` (pure-jnp) executor;
  (c)     (a) against (b).
Four chips: 4 chains on a (4, 1) mesh through the train driver with the
``delayed-5x`` schedule (the in-scan exchange), against the same job on a
one-device mesh, at the family's toy widths (four chains of the full
widths do not fit one device).

Times printed are of one cold run, compilation included: not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
ARCH = "h2o-danube-1.8b"
# Depth is the only cut: every width stays as published. Two layers hold
# one chain with its packed operands, gradients and a 4-client bf16
# surrogate bank in 16 GB (~10.3 GB peak on a v5e); a second chain at
# this depth does not fit (~16.8 GB in an AOT compile for v5e).
LAYERS = 2
JOB = ["--arch", ARCH, "--layers", str(LAYERS), "--method", "fsgld",
       "--chains", "1", "--num-shards", "4", "--shard-size", "8",
       "--seq", "1024", "--batch", "4", "--rounds", "2",
       "--local-updates", "2", "--fit-steps", "4", "--step-size", "1e-7",
       "--seed", "0"]
STEPS = 2 * 2        # rounds x local updates, per chain
STEP_SIZE = 1e-7
JOB4 = ["--arch", ARCH, "--smoke", "--method", "fsgld", "--chains", "4",
        "--num-shards", "4", "--shard-size", "16", "--seq", "64",
        "--batch", "4", "--rounds", "6", "--local-updates", "2",
        "--fit-steps", "16", "--use-kernel", "--federation", "delayed-5x",
        "--seed", "0"]

# (c) tolerances. The two runs see the same minibatches, so the same
# drift, but draw their noise from different streams: the packed kernel
# from the counter hash of kernels/ref.py, the vmap executor from
# jax.random. They are two samples of one chain's dynamics, so per leaf:
# - their RMS displacements from the initial state agree: for leaves of
#   >= 2560 elements the sampling error of the ratio is <= 2%. A drift
#   off by 10%, or a noise term dropped where the noise (sqrt(STEPS * h)
#   per element, printed) is half the displacement, moves it by > 10%;
DISP_RATIO_TOL = 0.10
# - they differ by their independent noise, N(0, 2 * STEPS * h) per
#   element, which the conducive and prior terms shrink or amplify: the
#   fitted surrogate precisions scale as 1/h, so h * lam is O(1) whatever
#   h is (0.67x-1.67x at toy widths on the CPU). Beyond 2x the two runs
#   took different drifts (another minibatch, client or surrogate row);
DIFF_RATIO_MAX = 2.0
# - ll/token on the probe batch moves, to first order, by grad . (a - b):
#   with each leaf's difference of RMS r_l spread over its elements, a
#   deviation of sqrt(sum_l |grad_l|^2 r_l^2). Limit: LL_SIGMAS of that.
LL_SIGMAS = 5.0
# kernel phase: same formula, same noise stream; only the transcendental
# approximations (log, cos, sqrt) of Mosaic and XLA may differ in the
# last bits. A wrong seed or element index is off by ~sqrt(h) = 0.1.
KERNEL_ATOL = 1e-5
# four chips: same kernel, keys and noise streams on both meshes, but the
# per-device programs differ (4 chains vs 1). On the CPU the two runs
# agree bitwise; on a v5e the bf16 gradient pass rounds differently, and
# after 12 steps the chains differ by up to 3.4e-3 of the largest
# parameter. A chain on the wrong device, at another client's shard or
# without its exchange lands about as far from its own reference chain as
# from the other chains (RMS ~0.2 apart at these sizes). Limit: each
# 4-chip chain is within MULTI_RATIO of that distance of its reference.
MULTI_RATIO = 0.1


class Compiles:
    """Seconds the backend spends compiling, from JAX's monitoring events
    (tracing and lowering nest across jits, so they are not summed)."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _fail(msg):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _check(cond, msg):
    if not cond:
        _fail(msg)


def _peak_gb(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def kernel_phase(jax, jnp, cfg):
    """Packed kernel vs ref.py at the packed layout of one full-width layer
    (2 chains, 'scalar' surrogate variant, langevin)."""
    from repro.kernels import ops, ref
    from repro.models import init_params

    C, h = 2, 1e-2
    one = dataclasses.replace(cfg, num_layers=1)
    shapes = jax.eval_shape(
        lambda: init_params(one, jax.random.PRNGKey(0)))["blocks"]
    layout = ops.make_packed_layout(shapes)
    L = layout.num_leaves
    ks = jax.random.split(jax.random.PRNGKey(1), 8)

    def rand(k, lead):
        leaves = jax.tree.leaves(shapes)
        return jax.tree.unflatten(jax.tree.structure(shapes), [
            jax.random.normal(jax.random.fold_in(k, i), lead + l.shape)
            for i, l in enumerate(leaves)])

    theta, g, mu_s = rand(ks[0], (C,)), rand(ks[1], (C,)), rand(ks[2], (C,))
    mu_g = rand(ks[3], ())
    scale = jnp.array([3.0, 5.0])
    f_s = jnp.array([0.25, 0.5])
    lam_g = jax.random.uniform(ks[4], (L,), minval=0.5, maxval=2.0)
    lam_s = jax.random.uniform(ks[5], (C, L), minval=0.1, maxval=1.0)
    kw = dict(h=h, prior_prec=1.0, alpha=1.0, temperature=1.0)
    seeds = ops.chain_leaf_seeds(jax.random.split(ks[6], C), L)
    scalars = ops.packed_scalar_rows(layout, scale=scale, f_s=f_s,
                                     lam_g_leaf=lam_g, lam_s_leaf=lam_s,
                                     **kw)

    def step(th, gg, ms, mg, sd, sc):
        return ops.packed_step(layout, th, gg, sd, sc, variant="scalar",
                               mu_g=mg, mu_s=ms)

    args = (layout.pack(theta), layout.pack(g), layout.pack(mu_s),
            layout.pack_shared(mu_g), seeds, scalars)
    compiled = jax.jit(step).lower(*args).compile()
    _check("tpu_custom_call" in compiled.as_text(),
           "packed step compiled without a tpu_custom_call: the kernel "
           "did not lower for the chip")
    got = jax.tree.leaves(layout.unpack(compiled(*args)))

    oracle = jax.jit(ref.fsgld_update_flat)
    worst = 0.0
    for li, (t, gg, ms, mg) in enumerate(zip(
            jax.tree.leaves(theta), jax.tree.leaves(g),
            jax.tree.leaves(mu_s), jax.tree.leaves(mu_g))):
        for c in range(C):
            want = oracle(t[c].reshape(-1), gg[c].reshape(-1), seeds[c, li],
                          scale=scale[c], f_s=f_s[c], mu_g=mg.reshape(-1),
                          mu_s=ms[c].reshape(-1), lam_g=lam_g[li],
                          lam_s=lam_s[c, li], **kw)
            diff = float(jnp.max(jnp.abs(got[li][c].reshape(-1) - want)))
            worst = max(worst, diff)
    print(f"kernel: packed step vs kernels/ref.py, {L} full-width leaves x "
          f"{C} chains ({layout.rows_total * 128 * C / 1e6:.1f}M elements): "
          f"max |diff| = {worst:.3e} (limit {KERNEL_ATOL:g})", flush=True)
    _check(np.isfinite(worst) and worst <= KERNEL_ATOL,
           f"packed kernel disagrees with kernels/ref.py: {worst}")


def timed_run(jax, train, compiles, argv, label, **kw):
    c0, t0 = compiles.seconds, time.perf_counter()
    res = train.run(argv, **kw)
    jax.block_until_ready(res["finals"])
    wall = time.perf_counter() - t0
    comp = compiles.seconds - c0
    print(f"{label}: one cold run {wall:.1f}s wall, of which "
          f"{comp:.1f}s compiling, {wall - comp:.1f}s the rest; "
          f"ll/token {np.array2string(np.asarray(res['ll_per_token']))}; "
          f"device peak_bytes_in_use {_peak_gb(jax):.2f} GB", flush=True)
    return res


def one_chip(jax, jnp, train, compiles):
    from repro import checkpoint
    from repro.configs import get_config

    cfg = get_config(ARCH)
    cut = dataclasses.replace(cfg, num_layers=LAYERS)
    print(f"arch {cfg.name}: d_model {cfg.d_model}, heads {cfg.num_heads} "
          f"(kv {cfg.num_kv_heads}) x head_dim {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, window {cfg.swa_window}, "
          f"layer pattern {cfg.layer_pattern} (all as published, "
          f"{cfg.source})", flush=True)
    print(f"cut: layers {LAYERS} of {cfg.num_layers}; parameters "
          f"{cut.param_count() / 1e6:.1f}M per chain "
          f"({cut.param_count() * 4 / 1e9:.2f} GB fp32); chips "
          f"{len(jax.devices())}", flush=True)

    t0 = time.perf_counter()
    kernel_phase(jax, jnp, cfg)
    print(f"kernel phase: {time.perf_counter() - t0:.1f}s wall, "
          f"compilation included", flush=True)

    work = REPO / ".chip_smoke"
    shutil.rmtree(work, ignore_errors=True)
    try:
        a = timed_run(jax, train, compiles,
                      JOB + ["--use-kernel", "--ckpt", str(work / "packed")],
                      "(a) packed executor")
        like = jax.device_get(a["params"])
        ll_a = np.asarray(a["ll_per_token"])
        del a
        b = timed_run(jax, train, compiles,
                      JOB + ["--ckpt", str(work / "vmap")],
                      "(b) vmap executor")
        ll_b = np.asarray(b["ll_per_token"])
        grads = _ll_grads(jax, jnp, b)
        del b
        th_a, _, _ = checkpoint.restore(str(work / "packed"), like)
        th_b, _, _ = checkpoint.restore(str(work / "vmap"), like)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    sigma = np.sqrt(2 * STEPS * STEP_SIZE)
    rows, ll_var = [], 0.0
    for (path, x0), xa, xb, g in zip(
            jax.tree_util.tree_flatten_with_path(like)[0],
            jax.tree.leaves(th_a), jax.tree.leaves(th_b), grads):
        name = jax.tree_util.keystr(path)
        x0, xa, xb = (np.asarray(x, np.float64) for x in (x0, xa, xb))
        _check(np.isfinite(xa).all() and np.isfinite(xb).all(),
               f"non-finite parameters in {name}")
        disp = np.sqrt(np.mean((xa - x0) ** 2) / np.mean((xb - x0) ** 2))
        diff = np.sqrt(np.mean((xa - xb) ** 2))
        ll_var += g * diff ** 2
        rows.append(f"{name} {np.sqrt(np.mean((xb - x0) ** 2)):.3e} "
                    f"{disp:.4f} {diff / sigma:.3f}")
        _check(abs(disp - 1.0) <= DISP_RATIO_TOL,
               f"{name}: RMS displacement packed/vmap = {disp:.4f}")
        _check(diff / sigma <= DIFF_RATIO_MAX,
               f"{name}: RMS(packed - vmap) / sqrt(2*steps*h) = "
               f"{diff / sigma:.3f}")
    print(f"(c) chain 0 after {STEPS} steps of h = {STEP_SIZE:g} (noise "
          f"sqrt(steps*h) = {sigma / np.sqrt(2):.3e} per element); per leaf: "
          f"RMS displacement of vmap, "
          f"RMS displacement packed/vmap (limit 1 +- {DISP_RATIO_TOL}), "
          f"RMS(packed - vmap) / sqrt(2*steps*h) (limit {DIFF_RATIO_MAX}): "
          + "; ".join(rows), flush=True)
    _check(np.isfinite(ll_a).all() and np.isfinite(ll_b).all(),
           "non-finite ll/token")
    dll = float(np.max(np.abs(ll_a - ll_b)))
    ll_sd = float(np.sqrt(ll_var))
    print(f"(c) ll/token packed {ll_a} vs vmap {ll_b}: |diff| {dll:.5f}; "
          f"first-order deviation from the parameter difference "
          f"{ll_sd:.5f} (limit {LL_SIGMAS:g} of it, {LL_SIGMAS * ll_sd:.5f})",
          flush=True)
    _check(dll <= LL_SIGMAS * ll_sd,
           f"ll/token differs by {dll}, {dll / ll_sd:.1f} deviations")


def _ll_grads(jax, jnp, res):
    """Per leaf |grad ll/token|^2 on the probe batch at chain 0's final
    state (the vmap run's)."""
    from repro.models import log_lik_fn

    cfg, probe = res["cfg"], res["probe"]
    n_tok = probe["tokens"].size
    theta = jax.tree.map(lambda t: t[0], res["finals"])
    g = jax.jit(jax.grad(lambda p, b: log_lik_fn(p, cfg, b) / n_tok))(
        theta, probe)
    return [float(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(g)]


def four_chips(jax, train, compiles):
    devs = jax.devices()
    _check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    ref = timed_run(jax, train, compiles, JOB4,
                    "reference: 4 chains on a one-device mesh",
                    devices=devs[:1])
    ref_finals = jax.device_get(ref["finals"])
    del ref
    multi = timed_run(jax, train, compiles, JOB4,
                      "4 chains on a (4, 1) mesh")
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            multi["finals"])[0]:
        shards = leaf.addressable_shards
        owners = {s.device for s in shards}
        _check(len(owners) == 4 and all(s.data.shape[0] == 1
                                        for s in shards),
               f"{jax.tree_util.keystr(path)}: chain blocks on "
               f"{len(owners)} devices, shard shapes "
               f"{[s.data.shape for s in shards]}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    print(f"placement: every leaf has one chain on each of 4 devices; "
          f"bytes_in_use per device {in_use}", flush=True)
    _check(None not in in_use
           and in_use[0] <= 1.5 * min(in_use[1:]) + (64 << 20),
           f"device 0 holds {in_use[0]} bytes in use vs {in_use[1:]}")
    multi_f = [np.asarray(x, np.float64)
               for x in jax.tree.leaves(jax.device_get(multi["finals"]))]
    ref_f = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref_finals)]
    n = sum(r[0].size for r in ref_f)

    def dist(c, d):
        """RMS over every parameter: 4-chip chain c vs reference chain d."""
        return np.sqrt(sum(np.sum((m[c] - r[d]) ** 2)
                           for m, r in zip(multi_f, ref_f)) / n)

    rows = []
    for c in range(len(devs)):
        same = dist(c, c)
        other = min(dist(c, d) for d in range(len(devs)) if d != c)
        rows.append(f"chain {c} {same:.3e} vs {other:.3e}")
        _check(np.isfinite(same) and same <= MULTI_RATIO * other,
               f"4-chip chain {c} is {same:.3e} from its reference and "
               f"{other:.3e} from the nearest other chain")
    worst = max(float(np.max(np.abs(m - r)) / np.max(np.abs(r)))
                for m, r in zip(multi_f, ref_f))
    print(f"4 chips vs one device, RMS distance to its own reference chain "
          f"vs to the nearest other one (limit {MULTI_RATIO:g} of it): "
          + "; ".join(rows) + f"; max |diff| / max |param| {worst:.3e}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        _fail(f"no repro package under {REPO / 'src'}")
    sys.path.insert(0, str(REPO / "src"))

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    _check(dev.platform == "tpu",
           f"JAX found no TPU (first device: {dev.platform})")
    from repro.launch import train
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compiles = Compiles(jax)
    if args.chips == 4:
        four_chips(jax, train, compiles)
    else:
        one_chip(jax, jnp, train, compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
