# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller tensors / fewer cases")
    ap.add_argument("--only", default="",
                    help="comma list: mttkrp,cpapr,storage,format,"
                         "kernels,roofline,dist,autotune,carry,serving,"
                         "outofcore,incremental")
    args = ap.parse_args()

    from repro import caches
    caches.use_compile_cache()
    from benchmarks import (bench_autotune, bench_cpapr, bench_dist,
                            bench_format_generation, bench_incremental,
                            bench_kernels, bench_mttkrp,
                            bench_mttkrp_formats, bench_outofcore,
                            bench_roofline, bench_serving, bench_storage)

    suites = {
        "mttkrp": bench_mttkrp_formats.run,      # paper Fig. 9
        "cpapr": bench_cpapr.run,                # paper Figs. 10/11
        "storage": bench_storage.run,            # paper Fig. 12
        "format": bench_format_generation.run,   # paper Fig. 13
        "kernels": bench_kernels.run,            # Pallas hot-spots
        "roofline": bench_roofline.run,          # EXPERIMENTS §Roofline
        "dist": bench_dist.run,                  # docs/distributed.md
        "autotune": bench_autotune.run,          # docs/autotuning.md
        "carry": bench_mttkrp.run,               # one-hot vs scratch-carry
        "serving": bench_serving.run,            # docs/serving.md
        "outofcore": bench_outofcore.run,        # docs/out-of-core.md
        "incremental": bench_incremental.run,    # docs/dynamic-tensors.md
    }
    wanted = [s for s in args.only.split(",") if s] or list(suites)

    print("name,us_per_call,derived")
    failed = 0
    for key in wanted:
        try:
            suites[key](quick=args.quick)
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
            print(f"{key}/SUITE_FAILED,0,", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
