"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each fig module for
the mapping to the paper's tables/figures).  ``python -m benchmarks.run``
runs everything; ``--only fig7`` filters."""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module names")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (engine_bench, fig6_fast_tx, fig7_stamp,
                            fig8_stmbench7, fig9_wait, fig11_scalability,
                            fig13_capacity, fig14_det_training, roofline)
    mods = [fig6_fast_tx, fig7_stamp, fig8_stmbench7, fig9_wait,
            fig11_scalability, fig13_capacity, fig14_det_training,
            roofline, engine_bench]
    print("name,us_per_call,derived")
    failed = []
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        if args.only and args.only not in name:
            continue
        try:
            mod.run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
