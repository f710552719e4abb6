"""Benchmark harness — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (benchmarks/common.py).

  fig1  — gradient-estimator variance (Bernoulli, non-IID shards)
  fig2/3 — Gaussian mean: DSGLD mixture-collapse vs FSGLD under named
           delayed-communication federation scenarios
  fig4  — bound constants eps_s^2 vs gamma_s^2
  fig5  — Bayesian metric learning (class-disjoint shards)
  table1 — Bayesian MLP, IID vs non-IID label imbalance
  f1    — Bayesian linear regression (App. F.1)
  remark1 — alpha exploration knob sweep
  kernel — fused FSGLD Pallas update micro-bench
  chains — mesh chain-runtime scaling (chains x shards)
  calib — K-draw ensemble calibration gates (NLL/ECE/coverage with
          absolute calib-floor=/calib-ceiling= bounds in the notes,
          enforced by check_regression.py; fixed sizes, SCALE ignored)
  frontier — rival samplers head-to-head (DSGLD / FSGLD / FA-LD across
          federation scenarios): posterior-mean MSE vs wire bytes per
          round, with absolute frontier-floor=/frontier-ceiling= gates
          (check_regression.py; fixed sizes, SCALE ignored)
  clients — streamed client-axis scaling (10^2/10^4/10^6 synthetic
          clients, resident-K windows): steps/s + peak device/host
          memory with absolute client-floor=/client-ceiling= gates
          (check_regression.py; fixed sizes, SCALE ignored)
  obs   — observability overhead: telemetry-on vs -off steps/s (gated
          < 5% via speedup-floor=0.95) + 0/1 span-export indicators
          (streamed prefetch overlap, serving latency) with absolute
          obs-floor= gates (check_regression.py)

REPRO_BENCH_SCALE=10 approaches paper-scale chain lengths;
REPRO_BENCH_SCALE=0.01 is the CI bench-smoke setting.

Exit status is the CI gate: non-zero when any sub-benchmark raises OR
emits a non-finite row (a NaN throughput is a failed measurement, not a
result). ``--json`` writes the standard BENCH envelope for artifact
upload; ``--only kernel,chains`` selects lanes.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    from benchmarks import (bench_calibration, bench_chains,
                            bench_clients, bench_frontier, bench_kernel,
                            bench_obs, f1_linreg, fig1_variance,
                            fig2_3_gaussian, fig4_epsilon,
                            fig5_metric_learning, remark1_alpha,
                            table1_bnn)
    from benchmarks.common import write_json
    from repro.launch.cache import enable_compile_cache

    modules = [
        ("fig1", fig1_variance), ("fig2_3", fig2_3_gaussian),
        ("fig4", fig4_epsilon), ("fig5", fig5_metric_learning),
        ("table1", table1_bnn), ("f1", f1_linreg),
        ("remark1", remark1_alpha), ("kernel", bench_kernel),
        ("chains", bench_chains), ("calib", bench_calibration),
        ("frontier", bench_frontier), ("clients", bench_clients),
        ("obs", bench_obs),
    ]
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write aggregated BENCH json here")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.only:
        wanted = set(args.only.split(","))
        unknown = wanted - {name for name, _ in modules}
        if unknown:
            print(f"unknown benchmarks: {sorted(unknown)}", file=sys.stderr)
            return 2
        modules = [(n, m) for n, m in modules if n in wanted]

    print("name,us_per_call,derived")
    all_rows = []
    failures = 0
    lane_seconds = {}
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = list(mod.run())
        except Exception:  # noqa: BLE001 - count and keep going
            failures += 1
            lane_seconds[name] = time.time() - t0
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
            continue
        bad = [r for r in rows if not r.ok()]
        for row in rows:
            print(row.csv(), flush=True)
        if bad:
            failures += 1
            print(f"# {name} FAILED: non-finite rows "
                  f"{[r.name for r in bad]}", flush=True)
        all_rows.extend(rows)
        lane_seconds[name] = time.time() - t0
        print(f"# {name} done in {lane_seconds[name]:.1f}s", flush=True)
    if args.json:
        write_json(all_rows, args.json, failures=failures,
                   lane_seconds=lane_seconds)
    if failures:
        print(f"# {failures} benchmark(s) FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
