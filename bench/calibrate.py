#!/usr/bin/env python3
"""Readings of the output check over many seeds, in one process: the
program as configured, the control and the planted faults
(``bench/faults.py``). Runs set-up and the check only, no window.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --variants program,control,half [--chunk 2] [--precision highest]

Prints one JSON line per (variant, seed) with the three numbers compared.
The limits in ``bench/workloads/<cell>.json`` are set from these
readings (PERF.md gives them).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
sys.path[:0] = [os.path.dirname(BENCH_DIR),
                os.path.join(os.path.dirname(BENCH_DIR), "src")]

from bench import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--chunk", type=int, default=0,
                    help="rounds a segment, in place of the cell's")
    ap.add_argument("--precision", default="",
                    help="jax_default_matmul_precision for the program")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    if args.chunk:
        spec["cell"]["chunk_rounds"] = args.chunk
    if args.precision:
        import jax
        jax.config.update("jax_default_matmul_precision", args.precision)
    status = 0
    for variant in args.variants.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            try:
                if variant == "control":
                    res = run.run_control(spec, seed)
                else:
                    res = run.run_cell(spec, seed, 0.0, False, window=False,
                                       **faults.variant_kwargs(variant,
                                                               spec))
                line = {"workload": args.workload, "variant": variant,
                        "seed": seed, "chunk": spec["cell"]["chunk_rounds"],
                        "precision": args.precision, "device": res["device"],
                        "phases": res["phases"], "losses": res["losses"],
                        "observed": res["observed"],
                        **{k: v["value"] for k, v in res["checks"].items()}}
            except run.NoChip as e:
                print(f"calibrate: {e}", file=sys.stderr)
                return 3
            except Exception:
                traceback.print_exc()
                line = {"workload": args.workload, "variant": variant,
                        "seed": seed, "error": traceback.format_exc()[-800:]}
                status = 1
            print(json.dumps(line), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
