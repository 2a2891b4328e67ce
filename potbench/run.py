"""Pot's chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python potbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on and refuses anything but a TPU with
as many chips as the cell asks for (exit 2, no result).  The last line of
standard output is the result as one JSON object: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profile of the window.  The numbers that decide ``correct`` are
printed beside their limits as the last lines of standard error and under
``checks`` in the result.  JAX's persistent compilation cache lives in
``.jax_cache/`` at the checkout's root.

``--control lane_order`` puts the control in the program's place for the
comparison (it has to come out not correct); ``--keep-trace PATH`` keeps a
copy of the profile.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from potbench import registry
    bench = registry.benchmark(ROOT)
    cell = registry.cell(bench, args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: it keeps an access-time file beside each entry, and one
    # entry without its file fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    t_jax = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"potbench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        from potbench.harness import run_cell
    except ImportError as e:
        print(f"potbench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), T_START, control=args.control,
                   keep_trace=args.keep_trace, marks={"jax": t_jax})
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
