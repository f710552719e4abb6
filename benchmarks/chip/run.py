"""Run one benchmark cell once, on the accelerator it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file, ``traffic/<traffic>.json``, ``limits/<cell>.json`` and,
for each metric, the reader ``metrics/<metric>.py``. With ``--trace 0``
the result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the whole window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit);
the last lines of standard error repeat the numbers compared. Without an
accelerator, with fewer chips than the cell asks for, or without the
program beside it, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr)
    return 2


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless JAX_COMPILATION_CACHE_DIR names one."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None, *, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}")
    if args.seed < 0:
        return fail("--seed must be a non-negative integer")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    enable_cache()
    import jax

    import cell as cellmod
    import checks
    import counts
    import xtrace

    devices = jax.devices()
    cell = cellmod.Cell(args.workload, args.seed)
    chips = cell.entry["chips"]
    if require_chip and (devices[0].platform == "cpu"
                         or len(devices) < chips):
        return fail(f"cell {args.workload} needs {chips} accelerator "
                    f"chip(s); JAX found {len(devices)} "
                    f"{devices[0].platform} device(s)")
    trace_dir = None
    if args.trace:
        trace_dir = ROOT / ".bench_trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = cell.run(args.seconds, t_start=T_START, devices=devices,
                   trace_dir=trace_dir)
    reduced = None
    if trace_dir is not None:
        reduced = xtrace.reduce_file(xtrace.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = cell.traffic
    ctx = types.SimpleNamespace(
        window=res.window, setup_s=res.setup_s, peak_bytes=res.peak_bytes,
        trace=reduced, arch=cell.arch, traffic=t,
        steps_per_round=cell.steps_per_round,
        step_flops=counts.step_flops(cell.arch, t["minibatch"],
                                     t["seq_len"]),
        update_bytes=counts.update_bytes(cell.arch),
        update_flops=counts.update_flops(cell.arch),
        peaks=counts.peaks(devices[0].device_kind),
        log=print)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.bench[kind]:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = checks.judge(res.checks, cell.limits)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": res.peak_bytes}
    out = {"correct": bool(correct), "attempted": res.window.rounds,
           "failed": 0, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ops = sorted(reduced.self_ns.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in reduced.gaps[:10]]}
    out["checks"] = {k: {"value": res.checks[k], "limit": cell.limits[k]}
                     for k in checks.NAMES}
    for k in checks.NAMES:
        print(f"check {k} {res.checks[k]!r} limit {cell.limits[k]!r}",
              file=sys.stderr)
    print(f"check correct {correct}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
