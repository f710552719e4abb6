"""Readings that set the limits of ``correct`` (not run by the benchmark).

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 12 --faults 3 [--first-seed N] [--out FILE]

One process, on the chip: for each seed, the program's first two rounds
at the cell's own size against the float32 reference (the lower
readings); on the first ``--faults`` seeds also the control, the
reference computed in bfloat16 (parameters, gradient pass and chain state
in bfloat16) in the program's place, and the faults planted in the
reference in the program's place: half of the batch left out and the
rest doubled, and the state returned unchanged (upper readings). Prints
one line per reading and writes them all as JSON.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import run
    run.enable_cache()
    import jax
    import jax.numpy as jnp

    import cell as cellmod
    import checks

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("calibrate: no accelerator", file=sys.stderr)
        return 2
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "program": [], "control": [], "half_batch": [], "unchanged": []}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        c = cellmod.Cell(args.workload, seed)
        round_fn, state, held, _ = c.start(devices)
        del state, round_fn
        gc.collect()
        with jax.default_matmul_precision("highest"):
            theta0 = c.weights()
            r2, r4 = c.reference_rounds(theta0)
            z2 = c.reference_rounds(theta0, zero_grad=True, rounds=1)[0]
            unit = c.grad_unit()
            rows = {"program": checks.numbers(theta0, held[0], held[1], r2,
                                              r4, z2, unit)}
            del held
            if i < args.faults:
                p2, p4 = c.reference_rounds(theta0, mode="bf16",
                                            store=jnp.bfloat16)
                rows["control"] = checks.numbers(theta0, p2, p4, r2, r4, z2,
                                                 unit)
                del p2, p4
                p2, p4 = c.reference_rounds(theta0, fault="half_batch")
                rows["half_batch"] = checks.numbers(theta0, p2, p4, r2, r4,
                                                    z2, unit)
                del p2, p4
                rows["unchanged"] = checks.numbers(theta0, theta0, theta0,
                                                   r2, r4, z2, unit)
        for k, v in rows.items():
            out[k].append({"seed": seed, **v})
            print(f"calibrate {args.workload} seed {seed} {k}: "
                  + " ".join(f"{n} {v[n]!r}" for n in checks.NAMES),
                  flush=True)
        print(f"calibrate seed {seed}: {time.perf_counter() - t0:.1f} s",
              flush=True)
        del theta0, r2, r4, z2, c
        gc.collect()
    for k in ("program", "control", "half_batch", "unchanged"):
        if out[k]:
            print(f"calibrate {args.workload} {k} max: " + " ".join(
                f"{n} {max(r[n] for r in out[k])!r}" for n in checks.NAMES)
                + " min: " + " ".join(
                f"{n} {min(r[n] for r in out[k])!r}" for n in checks.NAMES))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
