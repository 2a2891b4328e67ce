"""§Perf hillclimb runner: re-lower a cell under a named variant and
report the roofline-term deltas vs the recorded baseline.

Variants (sharding/schedule changes, not model changes):
  base        — the swept configuration (FSDP+TP+SP)
  pure_dp     — use the model axis as extra data: 256-way FSDP, no TP/SP
  mb2 / mb1   — fewer microbatches (fewer per-step parameter regathers)
  pure_dp_mb1 — combined
"""

from __future__ import annotations

import json
import os
import sys

from repro.launch.compile_cache import enable_compile_cache

VARIANTS = {
    "base": {},
    "pure_dp": {"profile_patch": {"pure_dp": True}},
    "mb2": {"n_mb_override": 2},
    "mb1": {"n_mb_override": 1},
    "pure_dp_mb1": {"profile_patch": {"pure_dp": True}, "n_mb_override": 1},
    "pure_dp_mb2": {"profile_patch": {"pure_dp": True}, "n_mb_override": 2},
    "bf16_params": {"force_huge": True},
    "pure_dp_bf16": {"profile_patch": {"pure_dp": True},
                     "n_mb_override": 1, "force_huge": True},
    "cf1": {"cfg_patch": {"capacity_factor": 1.0}},
    "cf1_bf16": {"cfg_patch": {"capacity_factor": 1.0},
                 "force_huge": True},
}


def run_variant(arch: str, shape: str, variant: str, out_dir: str):
    from repro.launch import dryrun as D
    from repro.configs import get_config
    cfg = get_config(arch)
    kw = VARIANTS[variant]
    c1, m1 = D.lower_cell(arch, shape, multi_pod=False, n_groups=1,
                          unroll=True, train_mode="baseline",
                          verbose=False, **kw)
    s1 = D.summarize(c1, 256)
    del c1
    c2, _ = D.lower_cell(arch, shape, multi_pod=False, n_groups=2,
                         unroll=True, train_mode="baseline",
                         verbose=False, **kw)
    s2 = D.summarize(c2, 256)
    del c2
    # full-depth fit check for the variant
    cf, mf = D.lower_cell(arch, shape, multi_pod=False, train_mode="pot",
                          verbose=False, **kw)
    mem = cf.memory_analysis()
    del cf
    units = D.depth_units(cfg)
    ex = D.extrapolate(s1, s2, units)
    rec = {"arch": arch, "shape": shape, "variant": variant,
           "analysis": {"g1": s1, "g2": s2, "depth_units": units,
                        "extrapolated": ex},
           "single_pod": {"meta": mf, "memory": {
               "argument_bytes": int(mem.argument_size_in_bytes),
               "temp_bytes": int(mem.temp_size_in_bytes),
               "peak_bytes": int(mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes)}}}
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{variant}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    enable_compile_cache()
    arch, shape, variant = sys.argv[1], sys.argv[2], sys.argv[3]
    rec = run_variant(arch, shape, variant, "results/perf")
    ex = rec["analysis"]["extrapolated"]
    coll = ex["collectives"]
    print(f"{arch}/{shape} [{variant}]  flops={ex['flops']:.3e}  "
          f"coll_total={coll['total']/1e9:.1f}GB  "
          f"bf16wire={coll.get('total_bf16_wire', 0)/1e9:.1f}GB  "
          f"ag={coll['all-gather']/1e9:.1f} ar={coll['all-reduce']/1e9:.1f} "
          f"rs={coll['reduce-scatter']/1e9:.1f} a2a={coll['all-to-all']/1e9:.1f}  "
          f"temp={rec['single_pod']['memory']['temp_bytes']/1e9:.1f}GB")


if __name__ == "__main__":
    main()
