"""Find the knee of an open-loop cell: the highest offered rate its served
path sustains.

    python potbench/sweep.py --workload ycsb_c.zipf99.open80 --seed <n> \\
        --seconds 8 --rates 3000,4000,5000

One process sets the cell up once (store, session, warm-up), then runs one
open-loop window per rate, in order, on the same store.  Each line gives
the offered rate, the committed rate, latency p50 and p95 from the due
time, the generator's admission lag, the mean batch and what was still
parked when the window closed; a rate is sustained while the committed rate
keeps up with it and nothing piles up.  The last line checks the whole
history against the reference.  The knee goes into the mix file by hand,
with the sweep's lines in ``PERF.md``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
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
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    import numpy as np
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: it keeps an access-time file beside each entry, and one
    # entry without its file fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from potbench import reference, registry
    from potbench.served import ServedRun
    bench = registry.benchmark(ROOT)
    cell = registry.cell(bench, args.workload)
    config = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])
    if mix["arrival"]["mode"] != "open":
        print("sweep: the cell's mix is not open-loop", file=sys.stderr)
        return 2
    run = ServedRun(config, mix, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    run.warm_up(args.seconds * sum(rates) / run.stream.rate)
    gc.collect()
    gc.freeze()
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in rates:
        run.stream.rate = rate
        w = run.open_window(args.seconds)
        lat = w["latency_s"] * 1e3
        print(json.dumps({
            "offered_tps": rate,
            "committed_tps": w["committed"] / w["window_s"],
            "p50_ms": float(np.quantile(lat, 0.5)),
            "p95_ms": float(np.quantile(lat, 0.95)),
            "p99_ms": float(np.quantile(lat, 0.99)),
            "lag_p95_ms": float(np.quantile(w["lag_s"], 0.95)) * 1e3,
            "mean_batch": w["committed"] / max(w["batches"], 1),
            "parked_at_close": w["parked_at_close"], "due": w["due"]}),
            flush=True)
    got = run.result()
    checks = reference.compare(got, run.events, run.formed, run.programs,
                               config, run.seed_words)
    print(json.dumps({"correct": reference.correct(checks), "checks": checks}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
