#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...
        [--variant-seeds 1,2,3] [--variants control,half_batch]
        --out <file.json>

For each seed it makes a short run of the cell (set-up, the checked
steps, a window of ``--seconds``) and records the numbers compared with
the reference. On the variant seeds it also reads, against the same
reference, the control (the reference computed in float8_e4m3) and the
planted faults: ``half_batch`` (half of each batch left out, the mean
taken over the rest) and ``no_exchange`` (each chip's tokens meet its
own experts instead of crossing to the chip of the expert they chose;
four-chip cells). A step that returns its state unchanged reads 1 by the
measure and needs no run. The benchmark's own runs never do this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
VARIANTS = {"control": ("fp8", ""), "half_batch": ("f32", "half_batch"),
            "no_exchange": ("f32", "no_exchange")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant-seeds", default="")
    ap.add_argument("--variants", default="control,half_batch")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out_dir = os.path.join(HERE, ".out")
    os.makedirs(out_dir, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import harness
    cell = harness.load_cell(args.workload)
    kind = harness.kind_module(cell.traffic["kind"])
    vseeds = {int(s) for s in args.variant_seeds.split(",") if s}
    variants = [(v, *VARIANTS[v]) for v in args.variants.split(",") if v]
    rows = []
    for s in [int(x) for x in args.seeds.split(",")]:
        t = time.perf_counter()
        r = kind.run(cell, s, args.seconds, False, t_process=t,
                     out_dir=out_dir,
                     variants=variants if s in vseeds else ())
        rows.append({"seed": s, "correct": r["correct"],
                     "checks": r["checks"], "variants": r.get("variants"),
                     "metrics": r["metrics"], "wall_s": time.perf_counter()
                     - t})
        print(json.dumps(rows[-1]), flush=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    print(f"calibrate: {len(rows)} seeds in "
          f"{time.perf_counter() - T_PROCESS:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
