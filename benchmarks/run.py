"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Paper-model benches assert
reproduction tolerances; the roofline bench summarizes the dry-run artifacts
(run ``python -m repro.launch.dryrun --all`` first to populate them).

``--smoke`` runs the CI-sized subset (kernel + PE-table + engine-autotune
benches; no dry-run artifacts needed); ``--json PATH`` records per-bench
status for the CI artifact.
"""
from __future__ import annotations

import argparse
import json
import traceback

from benchmarks import (bench_adaptive, bench_engine_autotune,
                        bench_fig6_widening, bench_kernels, bench_kvcache,
                        bench_serving, bench_table2_pe, bench_table3_alexnet,
                        bench_table4_resnet, bench_table5_device_compare,
                        roofline)

BENCHES = [
    ("table2", bench_table2_pe.main),
    ("table3", bench_table3_alexnet.main),
    ("table4", bench_table4_resnet.main),
    ("table5", bench_table5_device_compare.main),
    ("fig6", bench_fig6_widening.main),
    ("kernels", bench_kernels.main),
    ("engine_autotune", bench_engine_autotune.main),
    ("serving", bench_serving.main),
    ("kvcache", bench_kvcache.main),
    ("adaptive", bench_adaptive.main),
    ("roofline", roofline.main),
]

SMOKE = ("table2", "kernels", "engine_autotune")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help=f"run only the CI-sized subset {SMOKE}")
    ap.add_argument("--json", default=None,
                    help="write per-bench status to this JSON file")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    statuses = {}
    failures = []
    for name, fn in BENCHES:
        if args.smoke and name not in SMOKE:
            continue
        print(f"## bench:{name}")
        try:
            fn()
            statuses[name] = "ok"
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            statuses[name] = f"failed: {type(e).__name__}"
            print(f"{name}_FAILED,0,{type(e).__name__}")
            traceback.print_exc()
    print(f"## done, failures={failures}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "benches": statuses}, f, indent=1)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
