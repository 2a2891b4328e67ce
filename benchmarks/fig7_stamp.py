"""Fig. 7 analog: cost of deterministic execution on the STAMP-analog
suite, normalized to the nondeterministic OCC baseline (lower is better).

Engines: DeSTM-analog, PoGL, Pot- (ordered commits only), Pot* (+ fast
head), Pot (+ simultaneous-fast prefix).  The Pot variants share one
engine run; they differ in which commits get the uninstrumented fast
cost, mirroring the paper's ablation (§4.1.2).  "Time" is the
deterministic critical-path op-slot count (DESIGN.md §2)."""

from __future__ import annotations

import numpy as np

from benchmarks.common import emit, run_engines
from repro.core import workloads as W
from repro.core import metrics as M
from repro.launch.compile_cache import enable_compile_cache


def pot_variants(wl):
    """critical-path for Pot- / Pot* / Pot from one engine trace."""
    import jax.numpy as jnp
    from repro.core import (MODE_FAST, MODE_PREFIX, RoundRobinSequencer,
                            make_store, pcc_execute, run_all)
    store = make_store(wl.n_objects)
    seq = jnp.asarray(RoundRobinSequencer(
        n_root_lanes=wl.n_lanes).order_for(wl.lanes.tolist()), jnp.int32)
    res = run_all(wl.batch, store.values)
    rn, wn = np.asarray(res.rn), np.asarray(res.wn)
    n_ins = np.asarray(wl.batch.n_ins)

    def cp(tr, fast_mask):
        cost = M._txn_cost(n_ins, rn, wn, fast=False)
        cost[fast_mask] = n_ins[fast_mask]
        commit_round = np.asarray(tr.commit_round)
        first_round = np.asarray(tr.first_round)
        total = 0.0
        for r in range(int(tr.rounds)):
            in_flight = (first_round <= r) & (commit_round >= r)
            if in_flight.any():
                total += float(np.max(cost[in_flight]))
        return total

    # the paper's three configurations, now run as REAL engine ablations:
    # Pot- = ordered commits only (no fast cost, no promotion);
    # Pot* = + fast/prefix modes (no promotion);
    # Pot  = + live promotion (§2.2.3).
    _, tr_np = pcc_execute(store, wl.batch, seq, live_promotion=False)
    mode_np = np.asarray(tr_np.mode)
    _, tr_lp = pcc_execute(store, wl.batch, seq)
    mode_lp = np.asarray(tr_lp.mode)
    none_fast = np.zeros(len(n_ins), bool)
    return {"pot-": cp(tr_np, none_fast),
            "pot*": cp(tr_np, (mode_np == MODE_FAST)
                       | (mode_np == MODE_PREFIX)),
            "pot": cp(tr_lp, (mode_lp == MODE_FAST)
                      | (mode_lp == MODE_PREFIX))}


def run() -> None:
    lanes_sweep = (2, 4, 8, 16)
    for name, gen in W.STAMP.items():
        for n_lanes in lanes_sweep:
            wl = gen(n_lanes=n_lanes, seed=42)
            reports = run_engines(wl)
            base = reports["occ"].critical_path or 1.0
            pv = pot_variants(wl)
            emit(f"fig7_stamp[{name},lanes={n_lanes}]",
                 reports["pot"].critical_path,
                 "slowdown_vs_occ:"
                 f"destm={reports['destm'].critical_path/base:.2f}x,"
                 f"pogl={reports['pogl'].critical_path/base:.2f}x,"
                 f"pot-={pv['pot-']/base:.2f}x,"
                 f"pot*={pv['pot*']/base:.2f}x,"
                 f"pot={pv['pot']/base:.2f}x")


if __name__ == "__main__":
    enable_compile_cache()
    run()
