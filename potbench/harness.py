"""One run of one cell: set-up, the measured window, the optional trace,
and the comparison with the reference that decides ``correct``."""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from potbench import control as control_mod
from potbench import reference, registry
from potbench import trace as trace_mod
from potbench.served import CompileCounter, GcPauses, ServedRun


def _quantile(x: np.ndarray, q: float) -> float:
    return float(np.quantile(x, q)) if len(x) else float("nan")


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, config: dict | None = None,
             mix: dict | None = None, control: str | None = None,
             prepare=None,
             keep_trace: str | None = None, marks: dict | None = None,
             log=print) -> dict:
    """Run ``cell_name`` once and return the result line as a dict.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock,
    ``marks["jax"]`` the moment JAX had found its devices.
    ``config`` and ``mix`` replace the cell's own (tests run them small).
    ``control`` puts the named control in the program's place for the
    comparison.  ``prepare(run)`` is called on the ``ServedRun`` before
    warm-up (tests plant faults under the served path with it)."""
    cell = registry.cell(bench, cell_name)
    config = config or registry.config(bench, cell["config"])
    mix = mix or registry.mix(cell["traffic"])
    parts = {"start_to_jax": (marks or {}).get("jax", t_start) - t_start}
    t = time.perf_counter()
    run = ServedRun(config, mix, seed)
    jax.block_until_ready(run.sess.store)
    parts["store_and_session"] = time.perf_counter() - t
    if prepare is not None:
        prepare(run)
    t = time.perf_counter()
    run.warm_up(seconds)
    parts["warm_up"] = time.perf_counter() - t
    # what set-up left on the heap is not collected again: the window's
    # collections scan only what the window makes
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    compiles = CompileCounter()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="potbench_trace_")
        # host spans come from TraceAnnotation; the Python tracer would
        # add an event per Python call and swamp the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        run.spans.annotate = True
    run.spans.reset()
    setup_s = time.perf_counter() - t_start
    parts["other"] = setup_s - sum(parts.values())
    open_mix = mix["arrival"]["mode"] == "open"
    pauses.on = True
    with run.spans("window"):
        window = (run.open_window(seconds) if open_mix
                  else run.backlog_window(seconds))
    pauses.on = False
    pauses.close()
    red = None
    if trace:
        jax.profiler.stop_trace()
        run.spans.annotate = False
    window_compiles = compiles.n
    compiles.close()
    run.finish()
    dev = jax.devices()[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    counters = run.counters(window)
    t0 = time.perf_counter()
    got = run.result()
    gc.collect()
    if trace:
        xplane = trace_mod.find_xplane(log_dir)
        if keep_trace:
            shutil.copy(xplane, keep_trace)
        red = trace_mod.reduce(xplane)
        shutil.rmtree(log_dir, ignore_errors=True)
    if control is not None:
        got = control_mod.output(control, got, run, config)
    checks = reference.compare(got, run.events, run.formed, run.programs,
                               config, run.seed_words)
    ref_s = time.perf_counter() - t0
    attempted = len(run.programs)
    committed = len(got["log"])
    record = {"window": window,
              "spans": {"seconds": dict(run.spans.seconds),
                        "txns": dict(run.spans.txns)},
              "counters": counters, "trace": red}
    if trace:
        metrics = {}
        for m in registry.per_layer_for(bench, cell_name):
            v = registry.metric_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"commit_tps": window["committed"] / window["window_s"],
               "setup_s": setup_s}
        if open_mix:
            e2e["latency_p50_ms"] = _quantile(window["latency_s"], 0.5) * 1e3
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in registry.end_to_end_for(bench, cell_name)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    diag = {"window_s": window["window_s"], "committed": window["committed"],
            "batches": window["batches"], "rounds": counters["rounds"],
            "compiles_in_window": window_compiles,
            "session_compile_count": counters["compile_count"],
            "evicted": run.evicted, "reference_s": ref_s,
            "gc_pause_s": pauses.seconds, "gc_collections": pauses.counts,
            "setup_s": setup_s, "setup_parts": parts}
    if open_mix:
        diag.update(due=window["due"],
                    admit_lag_p50_ms=_quantile(window["lag_s"], 0.5) * 1e3,
                    admit_lag_p95_ms=_quantile(window["lag_s"], 0.95) * 1e3,
                    latency_p95_ms=_quantile(window["latency_s"], 0.95) * 1e3,
                    mean_batch=window["committed"] / max(window["batches"], 1))
    log("run " + " ".join(f"{k}={v}" for k, v in diag.items()),
        file=sys.stderr)
    out = {"correct": reference.correct(checks), "attempted": attempted,
           "failed": attempted - committed, "metrics": metrics,
           "device": device}
    if red is not None:
        out["breakdown"] = trace_mod.breakdown(red)
    out["diagnostics"] = diag
    out["checks"] = checks
    return out
