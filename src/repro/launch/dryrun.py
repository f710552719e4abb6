import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: prove the distribution config is coherent without real
# hardware. For every (architecture x input shape) the step function is
# lowered + compiled against the production mesh; memory_analysis() proves
# the per-device footprint, cost_analysis() feeds the roofline table.
#
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Do not set this flag globally: smoke tests and
# benches must see the single real CPU device.

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_NAMES, SHAPES, SamplerConfig, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import (input_specs, long_context_eligible,  # noqa: E402
                                params_shape, train_batch_specs)
from repro.launch.steps import (make_serve_step, make_surrogate_state,  # noqa: E402
                                make_train_step)
from repro.sharding import (batch_specs, cache_specs, param_specs,  # noqa: E402
                            param_shardings)

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def _shardings(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs)


def lower_one(arch: str, shape_name: str, mesh, sampler: SamplerConfig):
    """Lower + compile one (arch, shape, mesh) combination.

    Returns (lowered, compiled) or the string 'skip' for ineligible pairs.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not long_context_eligible(cfg):
        return "skip"

    pshape = params_shape(cfg)
    pspecs = param_specs(pshape, mesh, serve=(shape.kind == "decode"))
    pshard = _shardings(pspecs, mesh)

    if shape.kind == "decode":
        # serving consumes bf16 checkpoints (posterior samples are cast
        # once at export): halves resident weight bytes + gather traffic.
        pshape = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, pshape)
        pshard = _shardings(param_specs(pshape, mesh, serve=True), mesh)
        ins = input_specs(cfg, shape)
        cache_shard = _shardings(cache_specs(ins["cache"], mesh), mesh)
        tok_shard = _shardings(batch_specs(
            {"token": ins["token"], "pos": ins["pos"]}, mesh), mesh)
        serve = make_serve_step(cfg)
        args = [pshape, ins["cache"], ins["token"], ins["pos"]]
        in_sh = [pshard, cache_shard, tok_shard["token"], tok_shard["pos"]]
        if "enc_out" in ins:
            args.append(ins["enc_out"])
            in_sh.append(_shardings(batch_specs(
                {"e": ins["enc_out"]}, mesh), mesh)["e"])
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                serve, in_shardings=tuple(in_sh),
                out_shardings=(tok_shard["pos"], cache_shard),
            ).lower(*args)
    elif shape.kind == "prefill":
        # inference-prefill: forward-only (no grads / surrogates / remat
        # residuals). Lowers make_prefill_step.
        from repro.launch.steps import make_prefill_step
        batch = train_batch_specs(cfg, shape)
        batch.pop("labels")
        bshard = _shardings(batch_specs(batch, mesh), mesh)
        prefill = make_prefill_step(cfg)
        out_shard = _shardings(batch_specs(
            {"t": jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)},
            mesh), mesh)["t"]
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                prefill, in_shardings=(pshard, bshard),
                out_shardings=out_shard,
            ).lower(pshape, batch)
    else:
        batch = train_batch_specs(cfg, shape)
        bshard = _shardings(batch_specs(batch, mesh), mesh)
        surr = make_surrogate_state(pshape)
        surr_shard = {"mu_g": pshard, "mu_s": pshard,
                      "lam_g": jax.tree.map(
                          lambda _: NamedSharding(mesh, P()), surr["lam_g"]),
                      "lam_s": jax.tree.map(
                          lambda _: NamedSharding(mesh, P()), surr["lam_s"])}
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step = make_train_step(cfg, sampler, scale=1_000_000.0,
                               f_s=1.0 / sampler.num_shards)

        def step_key(params, surr, batch, key_data):
            return step(params, surr, batch,
                        jax.random.wrap_key_data(key_data))

        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step_key,
                in_shardings=(pshard, surr_shard, bshard,
                              NamedSharding(mesh, P())),
                out_shardings=(pshard, NamedSharding(mesh, P())),
            ).lower(pshape, surr, batch, key)

    with jax.set_mesh(mesh):
        compiled = lowered.compile()
    return lowered, compiled


def collective_bytes_from_text(txt: str) -> dict:
    """Body-once collective bytes (text occurrence, NOT loop-scaled; the
    loop-scaled numbers come from roofline.hlo_analysis)."""
    totals = {}
    for line in txt.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        # output shape(s) of the op: f32[128,1024]{...} possibly tuple
        lhs = line.split("=", 1)[1]
        nbytes = 0
        for t, dims in re.findall(r"(f64|f32|bf16|f16|s32|u32|s8|u8|pred)"
                                  r"\[([0-9,]*)\]", lhs.split("(")[0]):
            size = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                    "u32": 4, "s8": 1, "u8": 1, "pred": 1}[t]
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * size
        totals[kind] = totals.get(kind, 0) + nbytes
    return totals


def analyze(lowered, compiled) -> dict:
    from repro.roofline.hlo_analysis import analyze_text
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = collective_bytes_from_text(txt)
    static = analyze_text(txt)
    # NOTE: memory_analysis numbers are PER DEVICE. On XLA:CPU,
    # temp_size_in_bytes is cumulative transient allocation, while
    # peak_memory_in_bytes is the true high-water mark (the quantity that
    # must fit in the 16 GiB of a v5e chip).
    return {
        # raw XLA numbers (scan bodies counted once — see hlo_analysis doc)
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll,
        # loop-scaled static analysis (the roofline inputs)
        "static_flops": static["flops"],
        "static_hbm_bytes": static["hbm_bytes"],
        "static_collective_bytes": static["collective_bytes"],
        "static_collective_total": static["collective_total"],
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 multi-pod mesh")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    sampler = SamplerConfig(method="fsgld", num_shards=16)
    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    results = {}
    fail = 0
    for arch in archs:
        for shp in shapes:
            tag = f"{arch}|{shp}|{'pod2' if args.multi_pod else 'pod1'}"
            t0 = time.time()
            try:
                out = lower_one(arch, shp, mesh, sampler)
                if out == "skip":
                    print(f"SKIP  {tag} (full attention at 524k)",
                          flush=True)
                    results[tag] = {"status": "skip"}
                    continue
                lowered, compiled = out
                info = analyze(lowered, compiled)
                info["status"] = "ok"
                info["compile_s"] = round(time.time() - t0, 1)
                results[tag] = info
                print(f"OK    {tag} compile={info['compile_s']}s "
                      f"flops={info['static_flops']:.3e} "
                      f"hbm={info['static_hbm_bytes']:.3e} "
                      f"coll={info['static_collective_total']:.3e} "
                      f"args/dev={info['argument_size_bytes']/2**30:.2f}GiB "
                      f"peak/dev={info['peak_bytes']/2**30:.2f}GiB",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                fail += 1
                results[tag] = {"status": "fail", "error": str(e)[:500]}
                print(f"FAIL  {tag}: {type(e).__name__}: {str(e)[:300]}",
                      flush=True)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1)
    print(f"done: {sum(1 for r in results.values() if r['status']=='ok')} ok,"
          f" {sum(1 for r in results.values() if r['status']=='skip')} skip,"
          f" {fail} fail")
    return 1 if fail else 0


if __name__ == "__main__":
    sys.exit(main())
