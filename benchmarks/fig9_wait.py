"""Fig. 9 analog: time transactions spend waiting to enforce determinism,
DeSTM vs Pot (higher ratio = better for Pot).

The paper counts per-transaction wall time between finishing the read
phase and committing.  Our deterministic unit: wait-rounds (rounds spent
executed-but-not-committed for Pot; barrier-idle members for DeSTM)."""

from __future__ import annotations

from benchmarks.common import emit, run_engines
from repro.core import workloads as W
from repro.launch.compile_cache import enable_compile_cache


def run() -> None:
    for name, gen in W.STAMP.items():
        for n_lanes in (2, 4, 8, 16):
            wl = gen(n_lanes=n_lanes, seed=13)
            reports = run_engines(wl, engines=("pot", "destm"))
            pot_wait = reports["pot"].total_wait_rounds
            destm_wait = reports["destm"].total_wait_rounds
            ratio = destm_wait / max(pot_wait, 1)
            emit(f"fig9_wait[{name},lanes={n_lanes}]", pot_wait,
                 f"destm_wait={destm_wait},ratio={ratio:.2f}x")


if __name__ == "__main__":
    enable_compile_cache()
    run()
