"""Fig. 8 analog: STMBench7 throughput (r / rw / w workloads), normalized
to the nondeterministic OCC baseline (higher is better)."""

from __future__ import annotations

from benchmarks.common import emit, run_engines
from repro.core import workloads as W
from repro.launch.compile_cache import enable_compile_cache


def run() -> None:
    for mode in ("r", "rw", "w"):
        for n_lanes in (2, 4, 8, 16):
            wl = W.stmbench7_like(mode, n_txns=96, n_lanes=n_lanes, seed=7)
            reports = run_engines(wl)
            base = reports["occ"].throughput or 1.0
            emit(f"fig8_stmbench7[{mode},lanes={n_lanes}]",
                 reports["pot"].critical_path,
                 "throughput_vs_occ:"
                 f"destm={reports['destm'].throughput/base:.2f}x,"
                 f"pogl={reports['pogl'].throughput/base:.2f}x,"
                 f"pot={reports['pot'].throughput/base:.2f}x")


if __name__ == "__main__":
    enable_compile_cache()
    run()
