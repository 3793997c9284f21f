"""Benchmark driver: one function per paper table/figure + kernel benches
+ the decode-throughput bench + the roofline summary.  Prints
``name,value,reference`` CSV.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run --only fig9 --stats measured
  PYTHONPATH=src python -m benchmarks.run --only decode
  PYTHONPATH=src python -m benchmarks.run --dry       # CI smoke (fast)
"""

from __future__ import annotations

import argparse
import math


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated figure keys (fig2,...,table1,"
                         "kernels,decode,serve,roofline)")
    ap.add_argument("--stats", default="preset",
                    choices=["preset", "measured", "both"])
    ap.add_argument("--roofline-dir", default="results/dryrun")
    ap.add_argument("--dry", action="store_true",
                    help="CI smoke: import every bench registry and run a "
                         "tiny decode bench — catches wiring/API drift "
                         "without the full measurement cost")
    args = ap.parse_args()

    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()

    from benchmarks.decode_bench import ALL_DECODE_BENCHES, decode_bench
    from benchmarks.kernel_bench import ALL_KERNEL_BENCHES
    from benchmarks.paper_figures import ALL_FIGURES
    from benchmarks.serve_bench import ALL_SERVE_BENCHES

    if args.dry:
        import json
        names = (list(ALL_FIGURES) + [f"kernels.{k}" for k in
                                      ALL_KERNEL_BENCHES]
                 + list(ALL_DECODE_BENCHES)
                 + list(ALL_SERVE_BENCHES))
        print(f"# dry run: {len(names)} bench groups registered "
              f"({','.join(names)})")
        print("name,value,paper_reference")
        rows = list(decode_bench(batch=1, prompt_len=8, new_tokens=4,
                                 repeats=1))
        for name, val, _ in rows:
            print(f"{name},{val:.4f},")
        # machine-readable summary for the bench-drift gate
        # (tools/bench_check.py vs benchmarks/baselines/run_dry.json);
        # `registered` catches a bench group silently dropping out
        print("# json " + json.dumps(
            {"bench": "run_dry",
             "rows": dict({"registered_groups": float(len(names))},
                          **{n: float(v) for n, v, _ in rows})}))
        # paged-attention kernel gate (baselines/paged_attn.json): token
        # parity + gather-traffic savings are EXACT rows, wall times
        # advisory — cheap enough (<2 s interpreted) to run in the smoke
        from benchmarks.kernel_bench import paged_attn_gate_rows
        print("# json " + json.dumps(
            {"bench": "paged_attn",
             "rows": {n: float(v)
                      for n, v in paged_attn_gate_rows().items()}}))
        return

    only = set(args.only.split(",")) if args.only else None

    def want(key):
        return only is None or key in only

    print("name,value,paper_reference")
    sources = ["preset", "measured"] if args.stats == "both" else [args.stats]

    for key, fn in ALL_FIGURES.items():
        if not want(key):
            continue
        if key == "table1":
            rows = fn()
        else:
            rows = []
            for src in sources:
                rows += fn(stats_source=src)
        for name, val, ref in rows:
            ref_s = "" if (isinstance(ref, float) and math.isnan(ref)) else f"{ref:.4g}"
            print(f"{name},{val:.4f},{ref_s}")

    if want("kernels"):
        for fn in ALL_KERNEL_BENCHES.values():
            for name, us, derived in fn():
                d = "" if (isinstance(derived, float) and math.isnan(derived)) \
                    else f"{derived:.4g}"
                print(f"kernels.{name},{us:.2f},{d}")

    if want("decode"):
        for fn in ALL_DECODE_BENCHES.values():
            for name, val, _ in fn():
                print(f"{name},{val:.4f},")

    if want("serve"):
        for fn in ALL_SERVE_BENCHES.values():
            for name, val, _ in fn():
                print(f"{name},{val:.4f},")

    if want("roofline"):
        import os
        if os.path.isdir(args.roofline_dir):
            from repro.launch.roofline import load_rows
            for r in load_rows(args.roofline_dir):
                print(f"roofline.{r.arch}.{r.shape}.{r.mesh}.bound_s,"
                      f"{r.bound_s:.4f},{r.dominant}")
                print(f"roofline.{r.arch}.{r.shape}.{r.mesh}.useful_frac,"
                      f"{r.useful_fraction:.4f},")


if __name__ == "__main__":
    main()
