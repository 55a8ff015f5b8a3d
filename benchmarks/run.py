"""Benchmark entry — one function per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run            # fast versions
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale (slow)

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import json
import os
import traceback

from benchmarks.common import ART, csv_row


def bench_table1(fast):
    from benchmarks.table1_alltoall import main
    return main(fast)


def bench_table2(fast):
    from benchmarks.table2_wmt10 import main
    return main(fast)


def bench_table3(fast):
    from benchmarks.table3_throughput import main
    return main(fast)


def bench_table4(fast):
    from benchmarks.table4_multiling import main
    return main(fast)


def bench_fig6(fast):
    from benchmarks.fig6_rate_sweep import main
    return main(fast)


def bench_table5(fast):
    from benchmarks.table5_backends import main
    return main(fast)


def bench_table6(fast):
    from benchmarks.table6_decode import main
    return main(fast)


def bench_table7(fast):
    from benchmarks.table7_trainloop import main
    return main(fast)


def bench_table8(fast):
    from benchmarks.table8_serving import main
    return main(fast)


def bench_table10(fast):
    from benchmarks.table10_paged import main
    return main(fast)


def bench_table12(fast):
    from benchmarks.table12_obs import main
    return main(fast)


def bench_roofline(fast):
    from benchmarks.roofline import analyze, bottleneck_note, load_joined
    recs = load_joined("pod256")
    if not recs:
        csv_row("roofline/skipped", 0.0, "no dryrun artifacts yet")
        return {}
    out = []
    for r in recs:
        a = analyze(r)
        out.append(a)
        step = max(a["t_compute_s"], a["t_memory_s"], a["t_collective_s"])
        csv_row(f"roofline/{a['arch']}/{a['shape']}", step * 1e6,
                f"dominant={a['dominant']};useful={a['useful_flops_ratio']:.2f};"
                f"roofline_frac={a['roofline_frac']:.3f}")
    return out


def bench_kernels(fast):
    """Micro-bench the Pallas kernels (interpret mode; CPU) vs jnp refs."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import timeit
    from repro.kernels import grouped_matmul, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 256, 256))
    t_k = timeit(lambda: grouped_matmul(x, w, interpret=True), iters=3)
    t_r = timeit(lambda: jax.jit(ref.grouped_matmul_ref)(x, w), iters=3)
    csv_row("kernels/grouped_matmul_interpret", t_k * 1e6,
            f"jnp_ref_us={t_r*1e6:.1f} (interpret mode: correctness only)")
    return {"kernel_us": t_k * 1e6, "ref_us": t_r * 1e6}


BENCHES = {
    "table1": bench_table1,
    "table2": bench_table2,
    "table3": bench_table3,
    "table4": bench_table4,
    "fig6": bench_fig6,
    "table5": bench_table5,
    "table6": bench_table6,
    "table7": bench_table7,
    "table8": bench_table8,
    "table10": bench_table10,
    "table12": bench_table12,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)
    os.makedirs(ART, exist_ok=True)
    print("name,us_per_call,derived")
    results = {}
    failed = []
    for name in names:
        try:
            results[name] = BENCHES[name](not args.full)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            csv_row(f"{name}/FAILED", 0.0, f"{type(e).__name__}:{e}")
            traceback.print_exc()
    with open(os.path.join(ART, "bench_results.json"), "w") as f:
        json.dump(results, f, indent=1, default=str)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == '__main__':
    main()
