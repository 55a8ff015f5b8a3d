#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Runs one cell of BENCHMARK.json in this process on the chips JAX finds,
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a short window after the measured one. The
numbers compared with the plain reference, each with its limit, are the
result's last key and the last lines of standard error.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for. A cell's configuration, traffic mix and per-layer
metrics are found by the names BENCHMARK.json gives:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``; the traffic's ``kind`` names the module
that runs it, ``bench/cells/<kind>.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    out_dir = os.path.join(HERE, ".out")
    os.makedirs(out_dir, exist_ok=True)
    # libtpu's logs go inside the checkout, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(out_dir, "tpu_logs"))
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    cell = harness.load_cell(args.workload)
    kind = harness.kind_module(cell.traffic["kind"])
    result = kind.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, out_dir=out_dir)
    sys.stdout.flush()
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
