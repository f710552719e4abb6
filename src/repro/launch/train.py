"""End-to-end FSGLD training driver (large-model mode).

Phases (paper Algorithm 1 + Sec 3.1):
  1. local surrogate fitting — short SGLD runs per client shard against the
     local likelihood, fit per-tensor scalar-precision Gaussians (bf16
     storage), combine into the global product q (computed once,
     communicated once);
  2. FSGLD sampling — EVERY chain count (1..C) runs on the mesh-parallel
     chain engine through the ``repro.api`` facade: chains shard over the
     mesh 'data' axis, the scheduler reassigns chains to clients in-scan,
     and the chain takes ``local_updates`` Langevin steps per round with
     the conducive correction. The old single-chain host loop and the
     ppermute federated round are retired — both scales share one
     reassignment/collective path.

The mesh is built from the devices present: ``data`` = device count,
``model`` = 1, so chains spread over every local chip. ``--smoke`` picks
the toy configuration of the family (the CPU tests); without it the
published configuration runs at its published widths, and ``--layers N``
cuts its depth to fit one chip (printed as a cut).

KNOWN LIMIT (ROADMAP open item): the chain engine places chains on the
mesh 'data' axis and keeps parameters REPLICATED over 'model' (that axis
carries surrogate-refresh work only), so truly-billion-parameter archs
that need tensor-parallel weights per chain do not fit yet — the
model-axis param sharding lives in the pjit ``make_train_step`` lowering
path (launch/dryrun.py) and still has to be nested under the engine's
data-axis shard_map.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --rounds 10 --method fsgld
    python -m repro.launch.train --arch h2o-danube-1.8b --layers 2 \
        --use-kernel --chains 1 --seq 1024 --batch 4   # on a TPU chip
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, checkpoint
from repro.configs import get_config, get_smoke_config
from repro.data import token_shards
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_sim_mesh
from repro.models import init_params, log_lik_fn
from repro.obs import trace as obs_trace
from repro.obs import write_metrics_jsonl, write_prometheus


def _sample_into_bank(fsgld, key, params, cfg, args, federation):
    """Streaming chain→server sampling: run the schedule in SEGMENTS of
    ``--bank-every`` rounds, carrying the stacked per-chain states across
    segments (``engine.run(stacked=True)``), and append chain 0's
    parameters to the versioned draw bank after every segment — one
    thinned posterior draw per segment, served hot by any
    ``repro.launch.serve --bank`` process watching the directory.

    Each segment is its own engine dispatch with a folded sub-key, so
    the total stream is NOT bit-identical to one monolithic run (the
    reassignment permutation and, for sghmc, the momenta restart per
    segment) — the price of draws becoming visible while sampling runs.
    Returns the final stacked (C, ...) parameter states."""
    seg = max(1, args.bank_every)
    state, stacked = params, False
    done, i = 0, 0
    while done < args.rounds:
        r = min(seg, args.rounds - done)
        finals = fsgld.engine.run(
            jax.random.fold_in(key, i), state, r, n_chains=args.chains,
            reassign="permutation", collect=False, stacked=stacked,
            federation=federation, stream=fsgld.execution.stream)
        # sghmc returns (theta, momentum) chain-state pairs; the bank
        # stores parameters only (a draw is a draw, not a chain state)
        theta = finals[0] if args.kernel == "sghmc" else finals
        done += r
        i += 1
        state, stacked = theta, True
        draw = jax.tree.map(lambda t: t[0], theta)
        meta = checkpoint.DrawMeta(
            method=args.method, round=done,
            scenario=(args.federation or "identity"), seed=args.seed,
            dtype=str(jax.tree.leaves(draw)[0].dtype), arch=cfg.name,
            chain=0)
        path = checkpoint.save_draw(args.draw_bank, draw, meta, step=done)
        print(f"draw {i - 1} (round {done}) -> {path}", flush=True)
    return theta


def main(argv=None):
    run(argv)
    return 0


def run(argv=None, *, devices=None) -> dict:
    """Parse ``argv`` and run the job on a (len(devices), 1) mesh (default:
    every local device). Returns what an in-process caller checks: ``cfg``
    (the configuration run, cut included), ``params`` (the initial state),
    ``finals`` (the stacked (C, ...) final chain parameters, on device),
    ``probe`` (the batch ll/token is measured on) and ``ll_per_token``
    (per chain, on that batch)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="the family's toy config (CPU tests)")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut: replace num_layers of the config "
                         "and nothing else (every width stays)")
    ap.add_argument("--method", default="fsgld",
                    choices=["sgld", "dsgld", "fsgld", "fald"])
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--chains", type=int, default=1,
                    help="parallel chains on the mesh chain engine "
                         "(core/engine.py); chains shard over the mesh "
                         "'data' axis (any count — odd counts are padded "
                         "over the axis), reassignment is the "
                         "collision-free SPMD permutation")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route chain updates through the fused Pallas "
                         "kernel executors")
    ap.add_argument("--packed", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="with --use-kernel: packed single-launch steps "
                         "(one pallas_call per step for the whole chain "
                         "block; any floating param dtypes — non-fp32 "
                         "leaves quantize back per step). --no-packed "
                         "keeps the per-leaf kernel path")
    ap.add_argument("--kernel", default="sgld",
                    choices=["sgld", "sghmc"],
                    help="transition dynamics: 'sgld' (Langevin) or "
                         "'sghmc' (federated SGHMC — momenta ride the "
                         "chain state; composes with every executor, "
                         "packed included)")
    ap.add_argument("--friction", type=float, default=0.1,
                    help="SGHMC friction alpha_f (with --kernel sghmc)")
    ap.add_argument("--federation", default=None,
                    help="named federation scenario from the "
                         "repro.fed registry (e.g. 'delayed-5x', "
                         "'partial-50%%', 'topk-1%%'): communication "
                         "schedule + payload compression lowered into "
                         "the engine's scan. Partition scenarios are for "
                         "pooled-data drivers; token shards here are "
                         "already per-client, so schedule/compression "
                         "scenarios only")
    ap.add_argument("--local-updates", type=int, default=4)
    ap.add_argument("--num-shards", type=int, default=4)
    ap.add_argument("--clients", type=int, default=None,
                    help="streamed client axis: synthesize this many LAZY "
                         "clients (repro.fed.SyntheticClientSource — each "
                         "client's rows are a pure function of (seed, id), "
                         "generated on demand) instead of materializing "
                         "--num-shards token shards up front. Scales to "
                         "~10^6 clients; pair with --resident to bound "
                         "device memory")
    ap.add_argument("--resident", type=int, default=None,
                    help="streamed client axis: keep only this many "
                         "clients resident on device; the host prefetches "
                         "the next window's shards while the scan segment "
                         "runs. Fault-free streamed runs are bitwise "
                         "identical to the resident path. Must not exceed "
                         "the client count (--clients / --num-shards)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--shard-size", type=int, default=64)
    ap.add_argument("--step-size", type=float, default=1e-5)
    ap.add_argument("--fit-steps", type=int, default=20)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--draw-bank", default=None,
                    help="versioned draw-bank DIRECTORY: sampling runs in "
                         "segments of --bank-every rounds, writing chain "
                         "0's parameters as one DrawMeta-enveloped draw "
                         "per segment — a server pointed at the same "
                         "directory (repro.launch.serve --bank) hot-swaps "
                         "the fresh draws in between requests, while "
                         "sampling is still running")
    ap.add_argument("--bank-every", type=int, default=1,
                    help="rounds per draw-bank segment (thinning: one "
                         "draw every this many rounds)")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="preemption safety: atomically snapshot the "
                         "full scan carry (chains, key, federation "
                         "state, health, trace) every N rounds into "
                         "--snapshot-dir; a killed run resumes with "
                         "--resume, bitwise identical to uninterrupted")
    ap.add_argument("--snapshot-dir", default=None,
                    help="directory for --snapshot-every / --resume "
                         "snapshots")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest valid snapshot in "
                         "--snapshot-dir (fresh run when none exists)")
    ap.add_argument("--metrics-dir", default=None,
                    help="observability: run with in-scan telemetry "
                         "(repro.obs.Telemetry — bitwise identical to a "
                         "telemetry-off run) and write metrics.jsonl, "
                         "metrics.prom (Prometheus textfile), and "
                         "trace.jsonl (host spans/events) into this "
                         "directory")
    ap.add_argument("--log-every", type=int, default=None,
                    help="periodic progress: echo one engine.progress "
                         "line (round counter, steps/s, per-metric "
                         "means) every N rounds during the run — "
                         "segmentation is bitwise-lossless")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    obs = args.metrics_dir is not None or args.log_every is not None
    if obs and args.draw_bank:
        raise SystemExit(
            "--metrics-dir/--log-every instrument the facade's one "
            "engine dispatch; --draw-bank runs its own segment loop — "
            "pick one")
    if obs and args.resident is not None:
        raise SystemExit(
            "--metrics-dir/--log-every (in-scan telemetry) do not "
            "compose with --resident (streamed clients) yet — drop one")
    if args.log_every is not None and args.snapshot_every:
        raise SystemExit(
            "--log-every and --snapshot-every both segment the run — "
            "pick ONE segmentation driver (snapshots already log a "
            "span per segment)")
    if (args.snapshot_every or args.resume) and not args.snapshot_dir:
        raise SystemExit("--snapshot-every/--resume need --snapshot-dir")
    if (args.snapshot_every or args.resume) and args.draw_bank:
        raise SystemExit(
            "--snapshot-every/--resume run the schedule as one resumable "
            "engine dispatch; --draw-bank runs its own segment loop — "
            "pick one")
    n_clients = args.clients if args.clients is not None else args.num_shards
    if args.resident is not None and args.resident > n_clients:
        flag = "--clients" if args.clients is not None else "--num-shards"
        raise SystemExit(
            f"--resident {args.resident} exceeds the client count "
            f"({n_clients}): the resident set is the on-device SUBSET of "
            f"clients — lower --resident to at most {n_clients}, or raise "
            f"{flag} (did you mean {flag} {args.resident}?)")
    if args.resident is not None and (args.snapshot_every or args.resume):
        raise SystemExit(
            "--resident (streamed clients) does not compose with "
            "--snapshot-every/--resume: snapshots capture the full scan "
            "carry and the resident window is host-managed — drop "
            "--resident to snapshot")
    if args.clients is not None and args.method == "fsgld":
        raise SystemExit(
            "--clients streams lazy synthetic clients; surrogate fitting "
            "(--method fsgld) needs materialized shard data — pick "
            "--method dsgld or fald, or pass a prefit bank through the "
            "api facade")

    enable_compile_cache()
    telemetry = api.Telemetry(log_every=args.log_every) if obs else None
    if args.metrics_dir is not None:
        os.makedirs(args.metrics_dir, exist_ok=True)
        obs_trace.configure(
            os.path.join(args.metrics_dir, "trace.jsonl"),
            echo=args.log_every is not None)
    elif args.log_every is not None:
        obs_trace.configure(echo=True)
    try:
        return _train(args, telemetry, devices or jax.devices())
    finally:
        obs_trace.configure()  # don't leak the tracer to callers


def _train(args, telemetry, devices):
    obs = telemetry is not None
    n_clients = args.clients if args.clients is not None else args.num_shards
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.layers is not None:
        print(f"cut: num_layers {cfg.num_layers} -> {args.layers} "
              f"(every width as configured)")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    mesh = make_sim_mesh(data=len(devices), model=1, devices=devices)
    key = jax.random.PRNGKey(args.seed)
    k_param, k_data, k_fit, k_run = jax.random.split(key, 4)

    print(f"arch={cfg.name} method={args.method} shards={n_clients} "
          f"mesh={dict(mesh.shape)} "
          f"device={devices[0].platform}"
          + (f" resident={args.resident}" if args.resident else ""))
    params = init_params(cfg, k_param)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M")

    if args.clients is not None:
        # lazy per-client source: only the resident window is ever
        # materialized (the streamed-scale data contract)
        from repro.fed import SyntheticClientSource
        shards = SyntheticClientSource(
            k_data, num_clients=args.clients,
            shard_size=args.shard_size, seq_len=args.seq,
            vocab_size=cfg.vocab_size)
    else:
        shards = token_shards(
            k_data, num_shards=args.num_shards, shard_size=args.shard_size,
            seq_len=args.seq, vocab_size=cfg.vocab_size)

    # ---- the one front door: declarative facade over the chain engine ----
    minibatch = min(args.batch, args.shard_size)
    if not args.use_kernel:
        executor = "vmap"
    elif args.packed is False:
        executor = "per_leaf"
    else:
        executor = "packed"
    federation = None
    if args.federation:
        federation = api.get_scenario(args.federation)
        if federation.partition is not None:
            raise SystemExit(
                f"--federation {args.federation}: partition scenarios "
                "need pooled data; this driver builds per-client token "
                "shards — pick a schedule/compression scenario")
    # block-cyclic visiting supports any chain count in permutation mode
    reassign = "permutation"
    fsgld = api.FSGLD(
        api.Posterior(lambda p, b: log_lik_fn(p, cfg, b),
                      prior_precision=1.0),
        shards, minibatch=minibatch, step_size=args.step_size,
        method=args.method, kernel=args.kernel, friction=args.friction,
        surrogate=(api.SurrogateSpec(
            kind="scalar", fit="local_sgld", fit_steps=args.fit_steps,
            fit_minibatch=minibatch) if args.method == "fsgld"
            else api.SurrogateSpec(kind="none")),
        schedule=api.Schedule(
            rounds=args.rounds, local_steps=args.local_updates,
            n_chains=args.chains, reassign=reassign),
        execution=api.Execution(
            mesh=mesh, executor=executor, collect=False,
            dtype=jnp.dtype(cfg.surrogate_dtype),
            snapshot_every=args.snapshot_every,
            snapshot_path=args.snapshot_dir, resume=args.resume,
            stream=(api.Stream(resident=args.resident)
                    if args.resident is not None else None),
            telemetry=telemetry),
        federation=federation)

    # ---- phase 1: surrogates (once, before sampling) ----
    if args.method == "fsgld":
        t0 = time.time()
        jax.block_until_ready(fsgld.fit(k_fit, params))
        print(f"surrogates fitted in {time.time()-t0:.1f}s "
              f"(compilation included) "
              f"(communicated once; means stored as "
              f"{cfg.surrogate_dtype})")

    # ---- phase 2: FSGLD rounds on the chain engine ----
    t0 = time.time()
    if args.draw_bank:
        finals = _sample_into_bank(fsgld, k_run, params, cfg, args,
                                   federation)
    else:
        finals = fsgld.sample(k_run, params)
        frame = None
        if obs:
            finals, frame = finals
        if args.kernel == "sghmc":
            # collect=False sghmc returns (theta, momentum) chain-state
            # pairs; the ll probe (and the checkpoint) wants parameters
            finals = finals[0]
        if args.metrics_dir is not None:
            mj = os.path.join(args.metrics_dir, "metrics.jsonl")
            mp = os.path.join(args.metrics_dir, "metrics.prom")
            write_metrics_jsonl(frame, mj)
            write_prometheus(frame, mp)
            print(f"metrics -> {mj} + {mp} "
                  f"({frame.rounds} rounds x {frame.n_chains} chains x "
                  f"{len(frame.names)} metrics)")
    jax.block_until_ready(finals)
    dt = time.time() - t0
    probe_rows = (shards.rows(np.arange(1)) if args.clients is not None
                  else shards)
    probe = jax.tree.map(lambda d: d[0][:args.batch], probe_rows)
    lls = jax.jit(jax.vmap(lambda p, b: log_lik_fn(p, cfg, b),
                           in_axes=(0, None)))(finals, probe)
    lls = np.asarray(lls) / probe["tokens"].size
    for c, ll in enumerate(lls):
        print(f"chain {c:3d} ll/token={float(ll):8.4f}")
    steps = args.rounds * args.local_updates * args.chains
    print(f"{args.chains} chain(s) x {args.rounds} rounds "
          f"({steps} chain-steps) in {dt:.1f}s "
          f"= {steps / dt:.1f} steps/s, compilation included "
          f"[reassign={reassign} executor={executor}"
          f"{' federation=' + args.federation if args.federation else ''}]")
    if args.ckpt:
        checkpoint.save(args.ckpt,
                        jax.tree.map(lambda t: t[0], finals),
                        step=args.rounds,
                        extra={"method": args.method, "arch": cfg.name,
                               "chains": args.chains})
        print(f"checkpoint -> {args.ckpt}")
    print(f"final ll/token {float(np.mean(lls)):.4f}")
    return {"cfg": cfg, "params": params, "finals": finals,
            "probe": probe, "ll_per_token": lls}


if __name__ == "__main__":
    raise SystemExit(main())
