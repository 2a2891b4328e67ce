"""Fig. 11/12 analog: scalability — speedup over a single-lane serial
baseline as lanes grow (higher is better; >1 = faster than 1 lane)."""

from __future__ import annotations

from benchmarks.common import emit, run_engines
from repro.core import workloads as W
from repro.launch.compile_cache import enable_compile_cache


def run() -> None:
    suites = dict(W.STAMP)
    suites["stmbench7-rw"] = lambda **kw: W.stmbench7_like("rw", **kw)
    for name, gen in suites.items():
        base_cp = None
        rows = []
        for n_lanes in (1, 2, 4, 8, 16):
            wl = gen(n_lanes=n_lanes, seed=21)
            reports = run_engines(wl, engines=("pot", "destm", "pogl"))
            if n_lanes == 1:
                base_cp = reports["pogl"].critical_path or 1.0
            rows.append((n_lanes,
                         base_cp / max(reports["pot"].critical_path, 1e-9),
                         base_cp / max(reports["destm"].critical_path,
                                       1e-9)))
        derived = ";".join(
            f"lanes{n}:pot={p:.2f}x,destm={d:.2f}x" for n, p, d in rows)
        emit(f"fig11_scalability[{name}]", rows[-1][1], derived)


if __name__ == "__main__":
    enable_compile_cache()
    run()
