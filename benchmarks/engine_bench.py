"""Engine-loop benchmark (PR 2 + PR 3 + PR 4) -> BENCH_engines.json.

Times every engine four ways on the same workloads:

* ``scan``        — the preserved pre-refactor implementations
                    (repro.core.legacy_scan): per-round K-step commit scan
                    with an O(n_objects) bitmap probe + lax.cond write-back
                    per transaction;
* ``rebuild``     — the PR 2 vectorized commit pipeline with a from-scratch
                    round: full-batch ``run_all`` + rebuilt conflict
                    analysis every round (``incremental=False``);
* ``incremental`` — the PR 3 RoundState loop: masked ``run_live`` over the
                    live transactions only, carried conflict table with
                    delta updates (``compact=False``);
* ``compact``     — the PR 4 gather-compacted cascade: once the live set
                    fits a compact-ladder rung, the read phase gathers it
                    into a (C, L) block and executes THAT — device work
                    scales with the live set, not K.

Axes: K (batch size) × contention (low/med) × engine (pcc/occ/destm),
plus sweeps over store slot width S, transaction length L and lane count
at fixed K.  Each row records wall-clock txns/sec AND the read-phase
device-work model: ``read_phase_slots`` = Σ rounds Σ live instruction
slots (the rebuild loop pays ``rounds × Σ n_ins``; the incremental loop
pays only the live rows) and ``walked_slots`` = Σ rounds executor width
× L — the slots the device actually walks (K·L masked, C·L compact).

Two PR 4 sections ride along:

* live-fraction sweep (axis="live_fraction"): the read-phase PRIMITIVE —
  masked ``run_live`` vs gather-compacted ``run_live_compact`` — timed at
  live/K in {1/64, 1/8, 1/2, 1} on one batch, with results asserted
  bitwise-equal.  The compacted executor's walked slots scale with C
  (next_pow2 of the live count), the masked one's with K.
* ragged-stream compile counts (axis="ragged_stream"): a 32-shape ragged
  stream through PotSession with and without shape bucketing —
  compile_count() must stay <= the bucket-ladder size when bucketing.

One PR 5 section:

* shard sweep (axis="shards"): every engine's compact cascade on a
  store partitioned into S in {1, 4, 8} contiguous range shards
  (per-shard conflict tables OR-reduced in rank space + S independent
  write-back scatters), asserted bit-identical to the dense S=1 run,
  plus the write-back PRIMITIVE (``protocol.fused_write_back``) timed
  per S on one full committing round.

One PR 6 section:

* ingress (axis="ingress"): the deterministic serve loop — arrival
  journal -> IngressPool admission -> priority drain -> PotSession —
  timed against direct submission of the same pre-formed batches (the
  delta is the host-side ingress overhead) and the drain-only former;
  plus the occupancy-driven bucket-ladder auto-selection vs a pinned
  pow2 ladder (compile counts + padding waste, fingerprints asserted
  bit-identical).

One PR 7 section:

* pipeline (axis="pipeline"): cross-batch speculative pipelining —
  a PotSession with ``pipeline_depth=D`` executes batch n+1 against
  the pre-state snapshot while batch n commits, then validates n+1's
  logged read sets against n's committed writes (``versions >
  snap_gv`` via the rectangular conflict-strip kernels) and
  re-executes only invalidated rows.  Serial (D=0) vs D in {1, 2}
  stream throughput per engine × K × contention, with speculation
  observables (spec_executed / spec_invalidated / spec_rounds) per
  row; every pipelined stream is asserted bit-identical to the serial
  one (fingerprints + replay logs + full traces).

One PR 10 section:

* destm wave retries (axis="destm_wave"): the serial token walk (one
  retry EVENT per while_loop trip, the frozen-oracle port) vs
  wave-speculative retries (all of a trip's conflicting members
  re-execute at once against the committed-so-far store and the
  maximal provably-serial token prefix commits), K × contention ×
  lane count plus a blind write-write best case.  Every pair is
  asserted bitwise identical on stores and every trace field except
  the wave observables; rows carry retry_events / retry_waves and
  their reduction (waves == events only on fully serial chains).

``--shard-smoke`` (scripts/ci.sh --shard-smoke): asserts sharded ==
dense store fingerprints and traces across engines at S in {1, 2, 8},
and — when the host exposes multiple devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8) — the shard_map
per-device write-back path on a real mesh.

``--ingress-smoke`` (scripts/ci.sh --ingress-smoke): asserts two
IngressPool replicas fed the same arrival journal agree bitwise —
fingerprints + replay logs — across different drain budget schedules,
and that a full journal replay reproduces the formed batch stream.

``--pipeline-smoke`` (scripts/ci.sh --pipeline-smoke): replays one
ingress arrival journal through a serial session and pipelined
sessions (D in {1, 2}, engines pcc + occ) under different drain
budget schedules and asserts bitwise equality — fingerprints, replay
logs, and every pre-existing ExecTrace field (speculation cost may
only appear in the new spec_* observables).

``--destm-wave-smoke`` (scripts/ci.sh --destm-wave-smoke): asserts
wave-speculative DeSTM retries == the serial token walk bitwise
(stores + all non-wave trace fields) across K × contention × lanes,
with retry_waves <= retry events everywhere and a strict reduction on
the blind write-write best case.

``--smoke`` (scripts/ci.sh --bench-smoke): tiny K, asserts the four
implementations' store fingerprints and commit positions are bitwise
identical, and exercises the conflict-kernel delta path (skipped with a
message when the TPU kernel path is unavailable, so CPU-only CI still
runs the stage).

``--incremental-smoke`` (scripts/ci.sh --incremental-smoke): asserts
incremental == rebuild store fingerprints and traces across all three
engines.

``--compact-smoke`` (scripts/ci.sh --compact-smoke): asserts compact ==
masked (incremental) == rebuild store fingerprints and traces across all
three engines, plus run_live_compact == run_live at the primitive level.

Usage:
  python benchmarks/engine_bench.py [--out BENCH_engines.json]
  python benchmarks/engine_bench.py --smoke
  python benchmarks/engine_bench.py --incremental-smoke
  python benchmarks/engine_bench.py --compact-smoke
  python benchmarks/engine_bench.py --ingress-smoke
  python benchmarks/engine_bench.py --destm-wave-smoke
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.core import (RoundRobinSequencer, destm_execute, fingerprint,
                        legacy_scan, make_store, occ_execute, pcc_execute)
from repro.core import workloads as W
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def _workload(k: int, contention: str, seed: int = 0, *,
              n_reads: int = 2, n_writes: int = 2,
              n_lanes: int | None = None) -> W.Workload:
    """Array-of-counters microbenchmark (§4.1.1) at a given contention.

    low: uniform addresses over a store much larger than the batch's
    total footprint — speculation almost always wins (the regime the
    paper's "ordering as a blessing" argument targets).
    med: zipf-skewed addresses over a K-sized store — real abort chains,
    several engine rounds.
    """
    n_lanes = n_lanes if n_lanes is not None else min(8, k)
    if contention == "low":
        return W.counters(n_txns=k, n_objects=max(64, 8 * k),
                          n_reads=n_reads, n_writes=n_writes,
                          n_lanes=n_lanes, skew=0.0, seed=seed)
    return W.counters(n_txns=k, n_objects=max(16, k), n_reads=n_reads,
                      n_writes=n_writes, n_lanes=n_lanes, skew=0.9,
                      seed=seed)


def _seq_for(wl: W.Workload) -> jax.Array:
    seqr = RoundRobinSequencer(n_root_lanes=wl.n_lanes)
    return jnp.asarray(seqr.order_for(wl.lanes.tolist()), jnp.int32)


def _runners(wl: W.Workload, slot: int = 1):
    """{engine: {impl: zero-arg jitted callable -> (store, trace)}}."""
    store = make_store(wl.n_objects, slot=slot)
    seq = _seq_for(wl)
    arrival = jnp.argsort(seq)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    return store, {
        "pcc": {
            "scan": lambda: legacy_scan.pcc_execute_scan(store, wl.batch, seq),
            "rebuild": lambda: pcc_execute(store, wl.batch, seq,
                                           incremental=False),
            "incremental": lambda: pcc_execute(store, wl.batch, seq,
                                               compact=False),
            "compact": lambda: pcc_execute(store, wl.batch, seq),
        },
        "occ": {
            "scan": lambda: legacy_scan.occ_execute_scan(
                store, wl.batch, arrival),
            "rebuild": lambda: occ_execute(store, wl.batch, arrival,
                                           incremental=False),
            "incremental": lambda: occ_execute(store, wl.batch, arrival,
                                               compact=False),
            "compact": lambda: occ_execute(store, wl.batch, arrival),
        },
        "destm": {
            "scan": lambda: legacy_scan.destm_execute_scan(
                store, wl.batch, seq, lanes, wl.n_lanes),
            "rebuild": lambda: destm_execute(
                store, wl.batch, seq, lanes, wl.n_lanes, incremental=False),
            "incremental": lambda: destm_execute(
                store, wl.batch, seq, lanes, wl.n_lanes, compact=False),
            "compact": lambda: destm_execute(
                store, wl.batch, seq, lanes, wl.n_lanes),
        },
    }


def _commit_steps_model(impl: str, k: int) -> int:
    if impl == "scan":
        return k                                  # one scan step per txn
    return int(math.ceil(math.log2(max(k, 2)))) + 3   # matrix + reduce +
    #                                         assoc-scan depth + scatter


def _read_phase_slots(impl: str, trace, wl: W.Workload) -> int:
    """Read-phase device-work model: instruction slots actually walked by
    the round loop's speculative executions.  For the compact cascade this
    is the WALKED width (C·L per round — it scales with the live set, not
    K); the masked loops report the live-slot model (TPU-relevant: dead
    lanes are inert but still walked)."""
    total = int(np.asarray(wl.batch.n_ins).sum())
    if impl == "scan":
        return int(trace.rounds) * total   # legacy run_all every round
    if impl == "compact":
        return int(trace.walked_slots)     # C·L per round, C from ladder
    return int(trace.live_slots)           # rebuild: rounds*total; incr: live


def _row(engine, wl, impl, secs, trace, *, slot=1, axis="k_x_contention",
         **extra):
    k = wl.batch.n_txns
    lc = trace.live_counts()
    return dict(
        engine=engine, k=k, impl=impl, axis=axis,
        L=wl.batch.max_ins, slot=slot, n_lanes=wl.n_lanes,
        seconds=round(secs, 6), txns_per_sec=round(k / secs, 1),
        rounds=int(trace.rounds),
        commit_steps_per_round=_commit_steps_model(impl, k),
        read_phase_slots=_read_phase_slots(impl, trace, wl),
        walked_slots=int(trace.walked_slots),
        live_txns=int(trace.live_txns),
        wave_trips=int(trace.wave_trips),
        live_per_round=[int(x) for x in lc[:64]],
        live_per_round_truncated=bool(len(lc) > 64),
        **extra)


def _assert_equal(engine, k, cont, out_old, t_old, out_new, t_new, pair):
    fp_old, fp_new = int(fingerprint(out_old)), int(fingerprint(out_new))
    assert fp_old == fp_new, (
        f"{engine} K={k} {cont}: {pair[1]} fingerprint {fp_new:#x} diverged "
        f"from {pair[0]} {fp_old:#x}")
    for field in ("commit_pos", "retries"):
        a = np.asarray(getattr(t_old, field))
        b = np.asarray(getattr(t_new, field))
        assert np.array_equal(a, b), (
            f"{engine} K={k} {cont}: trace field {field!r} diverged "
            f"({pair[0]} vs {pair[1]})")


def _bench_grid(wl, cont, iters, results, *, impls, slot=1, axis):
    store, runners = _runners(wl, slot=slot)
    k = wl.batch.n_txns
    for engine, all_impls in runners.items():
        row_traces = {}
        for impl in impls:
            fn = all_impls[impl]
            secs = timeit(fn, warmup=2, iters=iters)
            out, trace = fn()
            row_traces[impl] = (out, trace)
            results.append(_row(engine, wl, impl, secs, trace, slot=slot,
                                axis=axis, contention=cont))
            print(f"{engine:6s} K={k:<5d} {cont:4s} L={wl.batch.max_ins:<3d} "
                  f"S={slot} lanes={wl.n_lanes:<3d} {impl:11s} "
                  f"{secs * 1e3:9.2f} ms  {k / secs:12.1f} txn/s  "
                  f"rounds={int(trace.rounds)} "
                  f"read_slots={_read_phase_slots(impl, trace, wl)}")
        base = impls[0]
        for impl in impls[1:]:
            _assert_equal(engine, k, cont, *row_traces[base],
                          *row_traces[impl], pair=(base, impl))


def run_bench(ks, contentions, iters: int) -> dict:
    results = []
    # primary grid: K × contention, all four implementations
    for k in ks:
        for cont in contentions:
            _bench_grid(_workload(k, cont), cont, iters, results,
                        impls=("scan", "rebuild", "incremental", "compact"),
                        axis="k_x_contention")
    # axis sweeps at fixed K: slot width, txn length L, lane count
    # (new-pipeline impls only; the scan baseline is covered above)
    k = 256
    for slot in (4,):
        _bench_grid(_workload(k, "low"), "low", iters, results,
                    impls=("rebuild", "incremental", "compact"), slot=slot,
                    axis="slot_width")
    for n_rw in (8,):
        _bench_grid(_workload(k, "low", n_reads=n_rw, n_writes=n_rw),
                    "low", iters, results,
                    impls=("rebuild", "incremental", "compact"),
                    axis="txn_length")
    for n_lanes in (2, 32):
        _bench_grid(_workload(k, "med", n_lanes=n_lanes), "med", iters,
                    results, impls=("rebuild", "incremental", "compact"),
                    axis="lane_count")
    live_fraction_sweep(iters, results)
    ragged_stream_bench(results)
    shard_sweep(iters, results)
    ingress_bench(iters, results)
    pipeline_bench(iters, results)
    destm_wave_bench(iters, results)
    return dict(results=results)


# ------------------------------------------------- PR 4 bench sections
def live_fraction_sweep(iters: int, results: list, k: int = 512,
                        fractions=(64, 8, 2, 1)) -> None:
    """Read-phase primitive at controlled sparsity: masked ``run_live``
    vs gather-compacted ``run_live_compact`` with live/K in
    {1/64, 1/8, 1/2, 1}.  The compact width C is next_pow2(live count) —
    the rung such a live set would run at.  Results asserted bitwise
    equal; the compacted executor must beat the masked one at
    live/K <= 1/8 (it walks C·L slots instead of K·L)."""
    from repro.core.txn import next_pow2, run_live, run_live_compact

    wl = _workload(k, "low", seed=17)
    store = make_store(wl.n_objects)
    cache = jax.block_until_ready(
        jax.jit(lambda b, v: run_live(b, v, jnp.ones((k,), bool)))(
            wl.batch, store.values))
    masked_fn = jax.jit(run_live)
    rng = np.random.default_rng(23)
    rows = {}
    for denom in fractions:
        n_live = max(1, k // denom)
        live = np.zeros(k, bool)
        live[rng.choice(k, n_live, replace=False)] = True
        live = jnp.asarray(live)
        width = next_pow2(n_live)
        compact_fn = jax.jit(functools.partial(run_live_compact,
                                               width=width))
        t_masked = timeit(lambda: masked_fn(wl.batch, store.values, live,
                                            cache), warmup=2, iters=iters)
        t_compact = timeit(lambda: compact_fn(wl.batch, store.values, live,
                                              cache), warmup=2, iters=iters)
        ref = masked_fn(wl.batch, store.values, live, cache)
        got = compact_fn(wl.batch, store.values, live, cache)[0]
        for f in ("raddrs", "rn", "waddrs", "wvals", "wn"):
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(got, f))), (
                f"live-fraction sweep: run_live_compact diverged on {f} "
                f"at live/K=1/{denom}")
        length = wl.batch.max_ins
        for impl, secs, walked in (("masked", t_masked, k * length),
                                   ("compact", t_compact, width * length)):
            rows[(impl, denom)] = secs
            results.append(dict(
                engine="run_live", k=k, impl=impl, axis="live_fraction",
                L=length, slot=1, n_lanes=wl.n_lanes,
                contention="low", live_fraction=f"1/{denom}",
                n_live=n_live, compact_width=(width if impl == "compact"
                                              else k),
                seconds=round(secs, 6),
                txns_per_sec=round(k / secs, 1),
                read_phase_slots=walked, walked_slots=walked))
            print(f"run_live K={k} live=1/{denom:<3d} {impl:8s} "
                  f"{secs * 1e6:9.1f} us  walked_slots={walked}")
    for denom in fractions:
        if denom >= 8:
            assert rows[("compact", denom)] < rows[("masked", denom)], (
                f"compacted read phase slower than masked at live/K=1/"
                f"{denom}: {rows[('compact', denom)]:.6f}s vs "
                f"{rows[('masked', denom)]:.6f}s")


def ragged_stream_bench(results: list, n_shapes: int = 32) -> None:
    """Streaming compile-count benchmark: one N-shape ragged stream per
    engine through PotSession, bucketed vs exact shapes.  Bucketed
    streaming must compile at most ladder-size steps; outcomes are
    asserted bitwise identical."""
    from repro.core import PotSession

    rng = np.random.default_rng(31)
    batches, lanes = [], []
    for i in range(n_shapes):
        kk = int(rng.integers(1, 129))
        wl = W.counters(n_txns=kk, n_objects=256, n_reads=2, n_writes=2,
                        n_lanes=min(4, kk), skew=0.5, seed=1000 + i)
        batches.append(wl.batch)
        lanes.append(wl.lanes.tolist())
    for engine in ("pcc", "occ", "destm"):
        stats = {}
        for mode, bucket in (("bucketed", True), ("exact", False)):
            t0 = time.perf_counter()
            s = PotSession(256, engine=engine, n_lanes=4, bucket=bucket)
            s.run_stream(batches, lanes)
            jax.block_until_ready(s.store.values)
            secs = time.perf_counter() - t0
            stats[mode] = (s, secs)
            results.append(dict(
                engine=engine, impl=mode, axis="ragged_stream",
                n_shapes=n_shapes,
                distinct_shapes=len({(b.n_txns, b.max_ins)
                                     for b in batches}),
                compile_count=s.compile_count(),
                bucket_counts={str(kk): v
                               for kk, v in sorted(s.bucket_counts().items())},
                seconds=round(secs, 6)))
            print(f"{engine:6s} ragged x{n_shapes} {mode:9s} "
                  f"compiles={s.compile_count():<3d} {secs:8.2f} s")
        sb, se = stats["bucketed"][0], stats["exact"][0]
        assert sb.fingerprint() == se.fingerprint(), engine
        assert sb.replay_log() == se.replay_log(), engine
        # bucket ladder over K in [1, 128] has 8 pow2 rungs — the compile
        # count must stay within it no matter how ragged the stream is
        assert sb.compile_count() <= 8, (engine, sb.compile_count())


def shard_sweep(iters: int, results: list, k: int = 256,
                shard_counts=(1, 4, 8)) -> None:
    """PR 5 shards axis: every engine's compact cascade on a store
    partitioned into S contiguous range shards, asserted bit-identical
    to the dense S=1 run, plus per-shard write-back timing of the
    ``fused_write_back`` primitive on one full committing round (the
    stage that splits into S independent scatters — one per device
    under a mesh)."""
    from repro.core import protocol
    from repro.core.txn import run_all

    for cont in ("low", "med"):
        wl = _workload(k, cont, seed=29)
        seq = _seq_for(wl)
        arrival = jnp.argsort(seq)
        lanes = jnp.asarray(wl.lanes, jnp.int32)
        # shard-invariant write-back operands: one full committing round
        res = run_all(wl.batch, make_store(wl.n_objects).values)
        rank = jnp.arange(k, dtype=jnp.int32)
        committing = jnp.ones((k,), bool)
        baseline = {}
        for shards in shard_counts:
            store = make_store(wl.n_objects, shards=shards)
            runners = {
                "pcc": lambda: pcc_execute(store, wl.batch, seq),
                "occ": lambda: occ_execute(store, wl.batch, arrival),
                "destm": lambda: destm_execute(store, wl.batch, seq,
                                               lanes, wl.n_lanes),
            }
            for engine, fn in runners.items():
                secs = timeit(fn, warmup=2, iters=iters)
                out, trace = fn()
                if shards == 1:
                    baseline[engine] = (out, trace)
                else:
                    _assert_equal(engine, k, cont, *baseline[engine],
                                  out, trace, pair=("s1", f"s{shards}"))
                results.append(_row(engine, wl, "compact", secs, trace,
                                    axis="shards", contention=cont,
                                    shards=shards))
                print(f"{engine:6s} K={k:<5d} {cont:4s} S={shards} "
                      f"compact     {secs * 1e3:9.2f} ms  "
                      f"{k / secs:12.1f} txn/s")
            # write-back primitive at this S
            layout = store.layout
            wb = jax.jit(lambda v, ver: protocol.fused_write_back(
                v, ver, res.waddrs, res.wvals, res.wn, committing, rank,
                rank + 1, layout))
            secs = timeit(lambda: wb(store.values, store.versions),
                          warmup=2, iters=iters)
            results.append(dict(
                engine="fused_write_back", k=k, impl=f"s{shards}",
                axis="shards", L=wl.batch.max_ins, slot=1,
                n_lanes=wl.n_lanes, contention=cont, shards=shards,
                seconds=round(secs, 6),
                writes_per_sec=round(float(res.wn.sum()) / secs, 1)))
            print(f"write_back K={k} {cont:4s} S={shards}  "
                  f"{secs * 1e6:9.1f} us")


def _fill_pool(wl, fees, **pool_kwargs):
    """Admit a workload's transactions (with per-txn fees) into a fresh
    IngressPool — the arrival side of the PR 6 ingress axis."""
    from repro.core import IngressPool
    from repro.core.ingress import programs_from_batch

    pool = IngressPool(**pool_kwargs)
    for p, lane, fee in zip(programs_from_batch(wl.batch),
                            wl.lanes.tolist(), fees):
        pool.admit(p, lane=lane, fee=int(fee))
    return pool


def ingress_bench(iters: int, results: list, k: int = 256,
                  budget: int = 24) -> None:
    """PR 6 ingress axis: (a) the full serve loop — journal-fed
    admission + priority drain + batch forming + execution — against
    direct submission of the same pre-formed batches (the delta is the
    deterministic host-side ingress overhead) and against the drain-only
    former (its raw throughput); (b) the occupancy-driven bucket-ladder
    auto-selection against a pinned pow2 ladder on a mid-size drain
    tail: compile counts and padding waste, fingerprints asserted
    bit-identical (padding is vacant rows — the choice can never change
    committed state)."""
    from repro.core import IngressPool, PotSession

    wl = _workload(k, "low", seed=37)
    rng = np.random.default_rng(41)
    src = _fill_pool(wl, rng.integers(0, 8, k).tolist(),
                     capacity=4 * k)
    arrivals = src.arrival_journal()
    twin, _ = IngressPool.replay(arrivals)
    formed = twin.drain_all(budget)

    session = PotSession(wl.n_objects, engine="pcc", n_lanes=wl.n_lanes)

    def serve_path():
        pool, _ = IngressPool.replay(arrivals)
        return session.serve(pool, budget=budget)

    def direct_path():
        return [session._submit_seq(fb.batch, fb.seq, fb.lanes,
                                    ladder=fb.ladder) for fb in formed]

    def drain_only():
        pool, _ = IngressPool.replay(arrivals)
        return pool.drain_all(budget)

    direct_path()   # warm the step compile cache for both paths
    timings = {
        "serve": timeit(lambda: jax.block_until_ready(
            serve_path()[-1].commit_pos), warmup=1, iters=iters),
        "direct": timeit(lambda: jax.block_until_ready(
            direct_path()[-1].commit_pos), warmup=1, iters=iters),
        "drain_only": timeit(drain_only, warmup=1, iters=iters),
    }
    for impl, secs in timings.items():
        results.append(dict(
            engine="ingress", k=k, impl=impl, axis="ingress",
            L=wl.batch.max_ins, slot=1, n_lanes=wl.n_lanes,
            contention="low", budget=budget, n_batches=len(formed),
            seconds=round(secs, 6), txns_per_sec=round(k / secs, 1)))
        print(f"ingress K={k:<5d} budget={budget} {impl:11s} "
              f"{secs * 1e3:9.2f} ms  {k / secs:12.1f} txn/s")

    # (b) occupancy-driven ladder auto-selection vs pinned pow2
    fps = {}
    for mode, pin in (("auto", None), ("pow2", "pow2")):
        s = PotSession(wl.n_objects, engine="pcc", n_lanes=wl.n_lanes)
        pool, _ = IngressPool.replay(arrivals)
        s.serve(pool, budget=budget, ladder=pin)
        waste = sum(bk * c for (bk, _), c in
                    s.bucket_counts().items()) - k
        fps[mode] = s.fingerprint()
        results.append(dict(
            engine="ingress", k=k, impl=f"ladder_{mode}", axis="ingress",
            L=wl.batch.max_ins, slot=1, n_lanes=wl.n_lanes,
            contention="low", budget=budget,
            compile_count=s.compile_count(), padding_waste_rows=waste,
            bucket_counts={str(kk): v for kk, v in
                           sorted(s.bucket_counts().items())}))
        print(f"ingress K={k:<5d} budget={budget} ladder={mode:5s} "
              f"compiles={s.compile_count()} padding_waste={waste}")
    assert fps["auto"] == fps["pow2"], (
        "bucket-ladder choice changed committed state")


def _pipeline_stream(k: int, cont: str, n_batches: int = 8,
                     seed: int = 43):
    """A stream of same-contention batches sharing one hot set — the
    regime where cross-batch validation actually has conflicts to
    find.  Every batch gets the SAME n_objects (one store) but a
    distinct seed, so consecutive batches collide on the skewed head
    of the address space at med contention and are near-disjoint at
    low."""
    wls = [_workload(k, cont, seed=seed + i) for i in range(n_batches)]
    return (wls[0].n_objects, wls[0].n_lanes,
            [w.batch for w in wls], [w.lanes for w in wls])


def pipeline_bench(iters: int, results: list, ks=(64, 256),
                   depths=(1, 2)) -> None:
    """PR 7 pipeline axis: serial (D=0) vs speculative pipeline depth
    D in {1, 2} stream throughput for the seeded engines (pcc, occ),
    K × contention.  Every pipelined stream is asserted bit-identical
    to the serial one — fingerprints, replay logs, full traces — so
    the rows measure the cost/benefit of speculation, never a
    semantics change.  Rows carry the speculation observables: rows
    executed against the pre-state snapshot, rows invalidated by the
    cross-batch read-set check, and re-execution passes."""
    from repro.core import PotSession

    for k in ks:
        for cont in ("low", "med"):
            n_obj, n_lanes, batches, lanes = _pipeline_stream(k, cont)
            total = k * len(batches)
            base = {}
            for engine in ("pcc", "occ"):
                for depth in (0,) + tuple(depths):
                    def stream():
                        s = PotSession(n_obj, engine=engine,
                                       n_lanes=n_lanes,
                                       pipeline_depth=depth)
                        ts = s.run_stream(batches, lanes)
                        jax.block_until_ready(s.store.values)
                        return s, ts
                    secs = timeit(lambda: stream(), warmup=1,
                                  iters=iters)
                    s, traces = stream()
                    if depth == 0:
                        base[engine] = s
                    else:
                        sb = base[engine]
                        assert s.fingerprint() == sb.fingerprint(), (
                            f"pipeline {engine} K={k} {cont} D={depth}: "
                            "fingerprint diverged from serial")
                        assert s.replay_log() == sb.replay_log(), (
                            f"pipeline {engine} K={k} {cont} D={depth}: "
                            "replay log diverged from serial")
                    spec_exec = sum(int(t.spec_executed) for t in traces)
                    spec_inv = sum(int(t.spec_invalidated)
                                   for t in traces)
                    spec_rounds = sum(int(t.spec_rounds) for t in traces)
                    results.append(dict(
                        engine=engine, k=k, impl=f"depth{depth}",
                        axis="pipeline", L=batches[0].max_ins, slot=1,
                        n_lanes=n_lanes, contention=cont,
                        pipeline_depth=depth, n_batches=len(batches),
                        seconds=round(secs, 6),
                        txns_per_sec=round(total / secs, 1),
                        spec_executed=spec_exec,
                        spec_invalidated=spec_inv,
                        spec_rounds=spec_rounds))
                    print(f"{engine:6s} K={k:<5d} {cont:4s} pipeline "
                          f"D={depth}  {secs * 1e3:9.2f} ms  "
                          f"{total / secs:12.1f} txn/s  "
                          f"spec={spec_exec}/inv={spec_inv}")


def _assert_wave_equal(tag, out_s, t_s, out_w, t_w):
    """wave == serial-token-walk, bitwise, on everything but the wave
    observables (retry_waves / waves_per_round — the whole win)."""
    import dataclasses

    from repro.core.engine import ExecTrace
    assert int(fingerprint(out_s)) == int(fingerprint(out_w)), (
        f"{tag}: wave fingerprint diverged from serial walk")
    for f in dataclasses.fields(ExecTrace):
        if f.name in ("retry_waves", "waves_per_round"):
            continue
        assert np.array_equal(np.asarray(getattr(t_s, f.name)),
                              np.asarray(getattr(t_w, f.name))), (
            f"{tag}: trace field {f.name!r} diverged")
    events, waves = int(t_s.retry_waves), int(t_w.retry_waves)
    assert events == int(np.asarray(t_s.retries).sum()), tag
    assert waves <= events, f"{tag}: waves {waves} > events {events}"
    return events, waves


def _blind_ww_workload(k: int, n_lanes: int, width: int = 2) -> W.Workload:
    """Blind write-write contention: lane groups of ``width`` write the
    same object, no reads.  Every round is ``n_lanes/width`` independent
    WW conflicts — the serial walk pays one retry event per conflict,
    one wave clears them all (empty read sets are trivially
    serial-valid), so this is the wave mode's best case."""
    from repro.core import WRITE, make_batch
    progs = [[(WRITE, (i % n_lanes) // width, False, i + 1),
              (WRITE, n_lanes + i % n_lanes, False, i)]
             for i in range(k)]
    lanes = np.asarray([i % n_lanes for i in range(k)], np.int32)
    return W.Workload(name="blind_ww", batch=make_batch(progs),
                      lanes=lanes, n_lanes=n_lanes,
                      n_objects=2 * n_lanes + 8)


def destm_wave_bench(iters: int, results: list, ks=(64, 256),
                     lane_counts=(8, 32)) -> None:
    """PR 10 destm_wave axis: the serial token walk (one retry EVENT per
    while_loop trip — the frozen-oracle port) vs wave-speculative
    retries (all of a trip's conflicting members re-execute at once and
    the maximal provably-serial token prefix commits), K × contention ×
    lane count.  Every pair is asserted bitwise identical — store
    fingerprints and every trace field except the wave observables — so
    the rows measure pure retry-loop mechanics: ``retry_events`` (=
    serial trips = Σ retries), ``retry_waves`` (wave trips) and their
    reduction.  The ``blind_ww`` rows are the wave's best case (pure
    write-write conflicts, whole wave commits in one trip); the skewed
    counters rows show the realistic middle; fully serial RMW chains
    show no reduction by design (waves == events there)."""
    wave_wls = []
    for k in ks:
        for cont in ("low", "med"):
            for n_lanes in lane_counts:
                wave_wls.append((cont, _workload(k, cont, seed=31,
                                                 n_lanes=n_lanes)))
    for k in ks:
        wave_wls.append(("ww", _blind_ww_workload(k, n_lanes=16)))
    for cont, wl in wave_wls:
        k = wl.batch.n_txns
        store = make_store(wl.n_objects)
        seq = _seq_for(wl)
        lanes = jnp.asarray(wl.lanes, jnp.int32)
        fns = {
            "serial_walk": lambda: destm_execute(
                store, wl.batch, seq, lanes, wl.n_lanes, wave=False),
            "wave": lambda: destm_execute(
                store, wl.batch, seq, lanes, wl.n_lanes),
        }
        outs = {impl: fn() for impl, fn in fns.items()}
        events, waves = _assert_wave_equal(
            f"destm_wave {wl.name} K={k} {cont} lanes={wl.n_lanes}",
            *outs["serial_walk"], *outs["wave"])
        for impl, fn in fns.items():
            secs = timeit(fn, warmup=2, iters=iters)
            _, trace = outs[impl]
            results.append(dict(
                engine="destm", k=k, impl=impl, axis="destm_wave",
                L=wl.batch.max_ins, slot=1, n_lanes=wl.n_lanes,
                contention=cont, seconds=round(secs, 6),
                txns_per_sec=round(k / secs, 1),
                rounds=int(trace.rounds),
                retries=int(np.asarray(trace.retries).sum()),
                retry_events=events,
                retry_waves=int(trace.retry_waves),
                wave_reduction=events - waves,
                waves_per_round=[int(x) for x in trace.wave_counts()[:64]]))
            print(f"destm  K={k:<5d} {cont:4s} lanes={wl.n_lanes:<3d} "
                  f"{impl:11s} {secs * 1e3:9.2f} ms  "
                  f"{k / secs:12.1f} txn/s  rounds={int(trace.rounds)} "
                  f"events={events} waves={waves}")


def summarize(results) -> dict:
    speedups = {}
    for row in results:
        if row["impl"] != "compact" or row["axis"] == "live_fraction":
            continue
        for base in ("scan", "rebuild", "incremental"):
            old = next(
                (r for r in results
                 if r["impl"] == base and r["engine"] == row["engine"]
                 and r["k"] == row["k"] and r["axis"] == row["axis"]
                 and r["contention"] == row["contention"]
                 and r["L"] == row["L"] and r["slot"] == row["slot"]
                 and r["n_lanes"] == row["n_lanes"]), None)
            if old is None:
                continue
            key = f'{row["engine"]}/K{row["k"]}/{row["contention"]}'
            if row["axis"] != "k_x_contention":
                # sweep rows: disambiguate by the swept coordinate
                key += (f'/{row["axis"]}/L{row["L"]}S{row["slot"]}'
                        f'lanes{row["n_lanes"]}')
            key += f"/{base}_to_compact"
            speedups[key] = dict(
                time=round(old["seconds"] / row["seconds"], 2),
                read_phase_slots=round(
                    old["read_phase_slots"]
                    / max(row["read_phase_slots"], 1), 2))
    return speedups


# ------------------------------------------------------------- smoke gates
def _kernel_smoke() -> str:
    """Exercise the conflict-kernel delta path (interpret mode — the TPU
    kernel's reference semantics) with a PARTIAL live mask, so both the
    recompute branch and the stale-tile carry branch run.  Only kernel
    construction/lowering sits inside the try: CPU-only CI must run the
    smoke stage even where the Pallas kernel path is unavailable, but a
    kernel that lowers and answers WRONG must still fail the gate."""
    from repro.kernels import conflict as C
    from repro.kernels import ref
    rng = np.random.default_rng(0)
    k, w = max(C.BI, C.BJ), C.BW
    mk = lambda d: jnp.asarray((rng.random((k, w)) < d) *
                               rng.integers(0, 2**31, (k, w)), jnp.int32)
    old_write = mk(0.05)
    old_foot = mk(0.2) | old_write
    live = jnp.asarray(rng.random(k) < 0.3, jnp.int32)
    keep = live[:, None].astype(bool)
    new_write = jnp.where(keep, mk(0.05), old_write)
    new_foot = jnp.where(keep, mk(0.2) | new_write, old_foot)
    try:
        old = C.conflict_matrix_bits(old_foot, old_write, interpret=True)
        delta = C.conflict_matrix_bits_delta(
            new_foot, new_write, old.astype(jnp.int32), live,
            interpret=True)
        delta = np.asarray(delta)
    except Exception as e:  # pragma: no cover - depends on jax build
        return (f"SKIP conflict-kernel check: TPU kernel path unavailable "
                f"({type(e).__name__}: {e})")
    lv = np.asarray(live).astype(bool)
    exp = np.where(lv[:, None] | lv[None, :],
                   np.asarray(ref.conflict_matrix_bits_ref(
                       new_foot, new_write)),
                   np.asarray(old))
    assert np.array_equal(delta != 0, exp), (
        "conflict-kernel delta diverged from the pure-jnp reference")
    return "conflict-kernel delta path OK (interpret mode, partial live)"


def run_smoke() -> None:
    """Equivalence gate: every engine, all four implementations, must
    agree bitwise."""
    for k in (2, 8):
        for cont in ("low", "med"):
            wl = _workload(k, cont, seed=k)
            _, runners = _runners(wl)
            for engine, impls in runners.items():
                outs = {name: fn() for name, fn in impls.items()}
                for name in ("rebuild", "incremental", "compact"):
                    _assert_equal(engine, k, cont, *outs["scan"],
                                  *outs[name], pair=("scan", name))
    print("bench-smoke OK: scan, rebuild, incremental and compact agree "
          "bitwise (engines: pcc, occ, destm; K in {2, 8}; low/med "
          "contention)")
    print(_kernel_smoke())


def run_incremental_smoke() -> None:
    """CI gate: the RoundState incremental loop == the from-scratch
    rebuild, on store fingerprints and traces, across all engines."""
    for k in (2, 8, 64):
        for cont in ("low", "med"):
            wl = _workload(k, cont, seed=3 * k + 1)
            _, runners = _runners(wl)
            for engine, impls in runners.items():
                out_reb, t_reb = impls["rebuild"]()
                out_inc, t_inc = impls["incremental"]()
                _assert_equal(engine, k, cont, out_reb, t_reb,
                              out_inc, t_inc, pair=("rebuild", "incremental"))
                assert int(t_inc.live_txns) <= int(t_reb.live_txns), (
                    engine, k, cont)
    print("incremental-smoke OK: RoundState loop == per-round rebuild "
          "(engines: pcc, occ, destm; K in {2, 8, 64}; low/med contention)")


def run_compact_smoke() -> None:
    """CI gate (scripts/ci.sh --compact-smoke): the gather-compacted
    cascade == the masked incremental loop == the from-scratch rebuild,
    on store fingerprints and traces, across all engines — and the
    compact read-phase primitive == the masked one on partial live
    sets (including sizes 0 and 1)."""
    from repro.core.txn import next_pow2, run_all, run_live, run_live_compact

    for k in (2, 8, 64):
        for cont in ("low", "med"):
            wl = _workload(k, cont, seed=7 * k + 5)
            _, runners = _runners(wl)
            for engine, impls in runners.items():
                out_reb, t_reb = impls["rebuild"]()
                out_inc, t_inc = impls["incremental"]()
                out_cpt, t_cpt = impls["compact"]()
                _assert_equal(engine, k, cont, out_inc, t_inc,
                              out_cpt, t_cpt, pair=("incremental",
                                                    "compact"))
                _assert_equal(engine, k, cont, out_reb, t_reb,
                              out_cpt, t_cpt, pair=("rebuild", "compact"))
                assert int(t_cpt.walked_slots) <= int(t_inc.walked_slots), (
                    engine, k, cont)
    # primitive: gather-execute-scatter == masked, sparse live sets
    wl = _workload(64, "low", seed=2)
    store = make_store(wl.n_objects)
    cache = run_all(wl.batch, store.values)
    rng = np.random.default_rng(9)
    for n_live in (0, 1, 5, 64):
        live = np.zeros(64, bool)
        live[rng.choice(64, n_live, replace=False)] = True
        live = jnp.asarray(live)
        ref = run_live(wl.batch, store.values, live, cache)
        got = run_live_compact(wl.batch, store.values, live, cache,
                               max(1, next_pow2(n_live)))[0]
        for f in ("raddrs", "rn", "waddrs", "wvals", "wn"):
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(got, f))), (n_live, f)
    print("compact-smoke OK: compact == masked == rebuild (engines: pcc, "
          "occ, destm; K in {2, 8, 64}; low/med contention) and "
          "run_live_compact == run_live (live in {0, 1, 5, 64})")


def run_shard_smoke() -> None:
    """CI gate (scripts/ci.sh --shard-smoke): the sharded store ==
    the dense store, bit for bit, across engines and both code paths —
    store fingerprints, commit positions and retries at S in {1, 2, 8},
    K in {2, 8, 64}, low/med contention, compact cascade AND masked
    loop.  When the host exposes >= 2 devices (the CI stage sets
    XLA_FLAGS=--xla_force_host_platform_device_count=8), the per-shard
    write-back additionally runs one-shard-per-device under
    jax.shard_map on a real mesh and must stay bit-identical."""
    from repro.core import shard_store

    for k in (2, 8, 64):
        for cont in ("low", "med"):
            wl = _workload(k, cont, seed=11 * k + 3)
            seq = _seq_for(wl)
            arrival = jnp.argsort(seq)
            lanes = jnp.asarray(wl.lanes, jnp.int32)
            dense = make_store(wl.n_objects)
            runners = lambda store: {
                "pcc": {
                    "compact": lambda: pcc_execute(store, wl.batch, seq),
                    "masked": lambda: pcc_execute(store, wl.batch, seq,
                                                  compact=False),
                },
                "occ": {
                    "compact": lambda: occ_execute(store, wl.batch,
                                                   arrival),
                    "masked": lambda: occ_execute(store, wl.batch,
                                                  arrival, compact=False),
                },
                "destm": {
                    "compact": lambda: destm_execute(
                        store, wl.batch, seq, lanes, wl.n_lanes),
                    "masked": lambda: destm_execute(
                        store, wl.batch, seq, lanes, wl.n_lanes,
                        compact=False),
                },
            }
            base = {(e, i): fn() for e, impls in runners(dense).items()
                    for i, fn in impls.items()}
            for shards in (2, 8):
                sharded = runners(shard_store(dense, shards))
                for engine, impls in sharded.items():
                    for impl, fn in impls.items():
                        _assert_equal(engine, k, cont,
                                      *base[(engine, impl)], *fn(),
                                      pair=(f"dense/{impl}",
                                            f"s{shards}/{impl}"))
    n_dev = len(jax.devices())
    if n_dev >= 2:
        s = min(8, n_dev)
        mesh = make_host_mesh(s, "shard")
        wl = _workload(32, "med", seed=19)
        seq = _seq_for(wl)
        dense = make_store(wl.n_objects)
        out_d, tr_d = pcc_execute(dense, wl.batch, seq)
        out_m, tr_m = pcc_execute(shard_store(dense, s, mesh=mesh),
                                  wl.batch, seq)
        _assert_equal("pcc", 32, "med", out_d, tr_d, out_m, tr_m,
                      pair=("dense", f"mesh_s{s}"))
        mesh_msg = (f"shard_map write-back validated on a {s}-device "
                    f"mesh")
    else:
        mesh_msg = ("single-device host: shard_map mesh path SKIPPED "
                    "(run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    print("shard-smoke OK: sharded == dense (engines: pcc, occ, destm; "
          "S in {2, 8}; K in {2, 8, 64}; low/med contention; compact + "
          f"masked paths); {mesh_msg}")


def run_ingress_smoke() -> None:
    """CI gate (scripts/ci.sh --ingress-smoke): two IngressPool replicas
    fed the same arrival journal, drained under DIFFERENT budget
    schedules covering the same prefix, must produce bit-identical batch
    streams, store fingerprints and replay logs through PotSession — and
    a full journal replay must reproduce the exact FormedBatch stream
    (sequence numbers, txn ids, ladder choices)."""
    from repro.core import IngressPool, PotSession

    wl = _workload(48, "med", seed=13)
    rng = np.random.default_rng(7)
    src = _fill_pool(wl, rng.integers(0, 9, 48).tolist(), capacity=64)
    arrivals = src.arrival_journal()
    outs = []
    for budgets in ([48], [5, 9, 3, 31], [7] * 7):
        pool, _ = IngressPool.replay(arrivals)
        s = PotSession(wl.n_objects, engine="pcc", n_lanes=wl.n_lanes)
        for b in budgets:
            fb = pool.drain(b)
            if fb is None:
                break
            s._submit_seq(fb.batch, fb.seq, fb.lanes, ladder=fb.ladder)
        assert pool.depth == 0, "drain schedule left txns behind"
        outs.append((s.fingerprint(), s.replay_log()))
    assert outs[0] == outs[1] == outs[2], (
        "ingress replicas diverged across drain budget schedules")
    # journal replay reproduces the formed stream bit-exactly
    pool, _ = IngressPool.replay(arrivals)
    formed = pool.drain_all(11)
    _, replayed = IngressPool.replay(pool.journal())
    assert len(replayed) == len(formed)
    for a, b in zip(formed, replayed):
        assert np.array_equal(a.txn_ids, b.txn_ids), "txn_ids diverged"
        assert np.array_equal(a.seq, b.seq), "seq diverged"
        assert np.array_equal(a.lanes, b.lanes), "lanes diverged"
        assert a.ladder == b.ladder, "ladder choice diverged"
    print("ingress-smoke OK: replicas on one arrival journal agree "
          "bitwise across drain schedules ([48], [5,9,3,31], [7]*7) — "
          "fingerprints + replay logs — and journal replay reproduces "
          f"the {len(formed)}-batch formed stream exactly")


def run_pipeline_smoke() -> None:
    """CI gate (scripts/ci.sh --pipeline-smoke): one ingress arrival
    journal replayed through a serial session and pipelined sessions
    (D in {1, 2}) for both seeded engines, under different drain budget
    schedules, must agree bitwise — store fingerprints, replay logs,
    and every pre-existing ExecTrace field (the speculation cost may
    only surface in the new spec_* observables, which must be zero on
    the serial run).  Also covers the ragged direct-stream path and the
    blocked OCC wave solve (wave_trips must drop, decisions must not
    change)."""
    import dataclasses

    from repro.core import IngressPool, PotSession, occ_execute
    from repro.core.engine import ExecTrace

    wl = _workload(48, "med", seed=13)
    rng = np.random.default_rng(7)
    arrivals = _fill_pool(wl, rng.integers(0, 9, 48).tolist(),
                          capacity=64).arrival_journal()
    for engine in ("pcc", "occ"):
        per_budget = []
        for budget in (48, 13, 7):   # three drain partitions
            runs = {}
            for depth in (0, 1, 2):
                pool, _ = IngressPool.replay(arrivals)
                s = PotSession(wl.n_objects, engine=engine,
                               n_lanes=wl.n_lanes, pipeline_depth=depth)
                ts = s.serve(pool, budget=budget)
                assert pool.depth == 0, "serve left txns behind"
                runs[depth] = (s, ts)
            s0, t0 = runs[0]
            for depth in (1, 2):
                s, ts = runs[depth]
                assert s.fingerprint() == s0.fingerprint(), (
                    f"pipeline-smoke {engine} budget={budget} "
                    f"D={depth}: fingerprint diverged from serial")
                assert s.replay_log() == s0.replay_log(), (
                    f"pipeline-smoke {engine} budget={budget} "
                    f"D={depth}: replay log diverged from serial")
                assert len(ts) == len(t0)
                for i, (a, b) in enumerate(zip(t0, ts)):
                    for f in dataclasses.fields(ExecTrace):
                        if f.name.startswith("spec_"):
                            assert int(np.asarray(
                                getattr(a, f.name)).sum()) == 0, (
                                f"serial run charged {f.name}")
                            continue
                        assert np.array_equal(
                            np.asarray(getattr(a, f.name)),
                            np.asarray(getattr(b, f.name))), (
                            f"pipeline-smoke {engine} budget={budget} "
                            f"D={depth}: trace field {f.name!r} "
                            f"diverged on batch {i}")
            per_budget.append((s0.fingerprint(), s0.replay_log()))
        # Budget-partition invariance holds at any pipeline depth
        # because it holds serially and pipelined == serial above.
        # PCC-only (matching --ingress-smoke): OCC's retry waves are
        # batch-scoped — a conflicting txn re-runs in a later wave of
        # ITS batch — so the baseline's commit order legitimately
        # depends on how the drain prefix is partitioned.
        if engine == "pcc":
            assert per_budget[0] == per_budget[1] == per_budget[2], (
                f"pipeline-smoke {engine}: drain partitions diverged")
    # ragged direct stream (run_stream path) + blocked wave solve
    n_obj, n_lanes, batches, lanes = _pipeline_stream(32, "med",
                                                      n_batches=5)
    s0 = PotSession(n_obj, engine="pcc", n_lanes=n_lanes)
    s0.run_stream(batches, lanes)
    s2 = PotSession(n_obj, engine="pcc", n_lanes=n_lanes,
                    pipeline_depth=2)
    s2.run_stream(batches, lanes)
    assert s0.fingerprint() == s2.fingerprint()
    assert s0.replay_log() == s2.replay_log()
    wlc = _workload(64, "med", seed=23)
    arrival = jnp.argsort(_seq_for(wlc))
    store = make_store(wlc.n_objects)
    out1, tr1 = occ_execute(store, wlc.batch, arrival, wave_block=1)
    out8, tr8 = occ_execute(store, wlc.batch, arrival, wave_block=8)
    _assert_equal("occ", 64, "med", out1, tr1, out8, tr8,
                  pair=("block1", "block8"))
    assert int(tr8.wave_trips) <= int(tr1.wave_trips)
    print("pipeline-smoke OK: pipelined (D in {1, 2}) == serial on one "
          "arrival journal across drain budgets (48, 13, 7) — "
          "fingerprints + replay logs + full traces (engines: pcc, "
          "occ) — and the blocked OCC wave solve is decision-identical "
          f"(trips {int(tr1.wave_trips)} -> {int(tr8.wave_trips)})")


def run_destm_wave_smoke() -> None:
    """CI gate (scripts/ci.sh --destm-wave-smoke): wave-speculative
    DeSTM retries == the serial token walk, bitwise — store
    fingerprints and every trace field except the wave observables —
    across K × contention × lane count, with retry_waves <= retry
    events everywhere and a strict reduction on the blind-WW best
    case."""
    total_events = total_waves = 0
    cases = [(k, cont, n_lanes)
             for k in (16, 48) for cont in ("low", "med")
             for n_lanes in (1, 8)]
    for k, cont, n_lanes in cases:
        wl = _workload(k, cont, seed=41, n_lanes=n_lanes)
        store = make_store(wl.n_objects)
        seq = _seq_for(wl)
        lanes = jnp.asarray(wl.lanes, jnp.int32)
        out_s = destm_execute(store, wl.batch, seq, lanes, wl.n_lanes,
                              wave=False)
        out_w = destm_execute(store, wl.batch, seq, lanes, wl.n_lanes)
        ev, wv = _assert_wave_equal(
            f"destm-wave-smoke K={k} {cont} lanes={wl.n_lanes}",
            *out_s, *out_w)
        total_events += ev
        total_waves += wv
    wl = _blind_ww_workload(48, n_lanes=16)
    store = make_store(wl.n_objects)
    seq = _seq_for(wl)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    out_s = destm_execute(store, wl.batch, seq, lanes, wl.n_lanes,
                          wave=False)
    out_w = destm_execute(store, wl.batch, seq, lanes, wl.n_lanes)
    ev, wv = _assert_wave_equal("destm-wave-smoke blind_ww",
                                *out_s, *out_w)
    assert wv < ev, (
        f"destm-wave-smoke blind_ww: expected a strict wave reduction, "
        f"got events={ev} waves={wv}")
    total_events += ev
    total_waves += wv
    print(f"destm-wave-smoke OK: wave == serial token walk bitwise "
          f"(stores + traces) across K x contention x lanes; retry "
          f"events {total_events} -> waves {total_waves}")


def run() -> None:
    """benchmarks/run.py entry point: one incremental-vs-rebuild-vs-
    compact row per engine at K=256 low contention, a shards row
    (sharded-vs-dense step time + write-back split), plus a
    ragged-stream compile-count row (CSV: name,us_per_call,derived)."""
    from benchmarks.common import emit
    from repro.core import PotSession
    wl = _workload(256, "low")
    _, runners = _runners(wl)
    for engine, impls in runners.items():
        t_reb = timeit(impls["rebuild"], warmup=1, iters=3)
        t_inc = timeit(impls["incremental"], warmup=1, iters=3)
        t_cpt = timeit(impls["compact"], warmup=1, iters=3)
        _, trace = impls["compact"]()
        emit(f"engine_bench_{engine}_k256_low_compact", t_cpt * 1e6,
             f"rebuild_over_compact={t_reb / t_cpt:.2f}x;"
             f"incremental_over_compact={t_inc / t_cpt:.2f}x;"
             f"live_txns={int(trace.live_txns)};"
             f"walked_slots={int(trace.walked_slots)};"
             f"rounds={int(trace.rounds)}")
    # sharded store: step time at S=8 vs dense (must stay bit-identical;
    # the interesting number on CPU is the overhead of the OR-reduce,
    # on a real mesh the per-device write-back win)
    seq = _seq_for(wl)
    dense = make_store(wl.n_objects)
    sharded = make_store(wl.n_objects, shards=8)
    t_d = timeit(lambda: pcc_execute(dense, wl.batch, seq), warmup=1,
                 iters=3)
    t_s = timeit(lambda: pcc_execute(sharded, wl.batch, seq), warmup=1,
                 iters=3)
    out_d, _ = pcc_execute(dense, wl.batch, seq)
    out_s, _ = pcc_execute(sharded, wl.batch, seq)
    assert int(fingerprint(out_d)) == int(fingerprint(out_s))
    emit("engine_bench_pcc_k256_low_shards8", t_s * 1e6,
         f"dense_over_sharded={t_d / t_s:.2f}x;bitwise_equal=1")
    # ragged-stream compile counts: 8 shapes is enough for a CSV row
    rng = np.random.default_rng(3)
    batches = []
    for i in range(8):
        kk = int(rng.integers(1, 65))
        batches.append(W.counters(n_txns=kk, n_objects=128, n_lanes=1,
                                  skew=0.5, seed=i).batch)
    for mode, bucket in (("bucketed", True), ("exact", False)):
        t0 = time.perf_counter()
        s = PotSession(128, engine="pcc", bucket=bucket)
        s.run_stream(batches)
        jax.block_until_ready(s.store.values)
        emit(f"engine_bench_ragged8_{mode}",
             (time.perf_counter() - t0) * 1e6,
             f"compiles={s.compile_count()}")
    # ingress serve loop vs direct submit of the pre-formed batches
    from repro.core import IngressPool
    wl2 = _workload(128, "low", seed=6)
    rng2 = np.random.default_rng(5)
    arrivals = _fill_pool(wl2, rng2.integers(0, 8, 128).tolist(),
                          capacity=512).arrival_journal()
    twin, _ = IngressPool.replay(arrivals)
    formed = twin.drain_all(24)
    s = PotSession(wl2.n_objects, engine="pcc", n_lanes=wl2.n_lanes)
    direct = lambda: [s._submit_seq(fb.batch, fb.seq, fb.lanes,
                                    ladder=fb.ladder) for fb in formed]
    direct()   # warm the step compiles
    t_direct = timeit(lambda: jax.block_until_ready(
        direct()[-1].commit_pos), warmup=1, iters=3)
    t_serve = timeit(lambda: jax.block_until_ready(
        s.serve(IngressPool.replay(arrivals)[0],
                budget=24)[-1].commit_pos), warmup=1, iters=3)
    emit("engine_bench_ingress_serve_k128", t_serve * 1e6,
         f"direct_over_serve={t_direct / t_serve:.2f}x;"
         f"batches={len(formed)};budget=24;"
         f"ladder={formed[0].ladder}")
    # cross-batch speculative pipeline: serial vs D=2 on one stream
    n_obj, n_lanes, batches3, lanes3 = _pipeline_stream(
        128, "med", n_batches=6)

    def pipe_stream(depth):
        s = PotSession(n_obj, engine="pcc", n_lanes=n_lanes,
                       pipeline_depth=depth)
        ts = s.run_stream(batches3, lanes3)
        jax.block_until_ready(s.store.values)
        return s, ts

    s_ser, _ = pipe_stream(0)
    t_ser = timeit(lambda: pipe_stream(0), warmup=1, iters=3)
    t_pipe = timeit(lambda: pipe_stream(2), warmup=1, iters=3)
    s_pipe, traces = pipe_stream(2)
    assert s_pipe.fingerprint() == s_ser.fingerprint()
    emit("engine_bench_pipeline_k128_med_d2", t_pipe * 1e6,
         f"serial_over_pipelined={t_ser / t_pipe:.2f}x;"
         f"spec_executed={sum(int(t.spec_executed) for t in traces)};"
         f"spec_invalidated="
         f"{sum(int(t.spec_invalidated) for t in traces)};"
         f"bitwise_equal=1")
    # wave-speculative DeSTM retries: serial token walk vs wave mode on
    # a contended round structure (bitwise-asserted, the wave-count
    # reduction is the derived observable)
    wl4 = _workload(128, "med", seed=31, n_lanes=16)
    store4 = make_store(wl4.n_objects)
    seq4 = _seq_for(wl4)
    lanes4 = jnp.asarray(wl4.lanes, jnp.int32)
    serial4 = lambda: destm_execute(store4, wl4.batch, seq4, lanes4,
                                    wl4.n_lanes, wave=False)
    wave4 = lambda: destm_execute(store4, wl4.batch, seq4, lanes4,
                                  wl4.n_lanes)
    ev4, wv4 = _assert_wave_equal("run destm_wave", *serial4(), *wave4())
    t_serial4 = timeit(serial4, warmup=1, iters=3)
    t_wave4 = timeit(wave4, warmup=1, iters=3)
    emit("engine_bench_destm_wave_k128_med", t_wave4 * 1e6,
         f"serial_over_wave={t_serial4 / t_wave4:.2f}x;"
         f"retry_events={ev4};retry_waves={wv4};"
         f"wave_reduction={ev4 - wv4};bitwise_equal=1")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny K, equivalence assertions only (CI stage)")
    ap.add_argument("--incremental-smoke", action="store_true",
                    help="assert incremental == rebuild across engines")
    ap.add_argument("--compact-smoke", action="store_true",
                    help="assert compact == masked == rebuild across "
                         "engines (+ primitive equality)")
    ap.add_argument("--shard-smoke", action="store_true",
                    help="assert sharded store == dense store across "
                         "engines and paths (+ shard_map mesh when "
                         "multiple devices are exposed)")
    ap.add_argument("--ingress-smoke", action="store_true",
                    help="assert ingress replicas on one arrival journal "
                         "agree bitwise across drain budget schedules "
                         "and that journal replay reproduces the formed "
                         "batch stream")
    ap.add_argument("--pipeline-smoke", action="store_true",
                    help="assert pipelined sessions (D in {1, 2}) == "
                         "serial on one arrival journal across drain "
                         "budgets — fingerprints, replay logs and full "
                         "traces — plus the blocked OCC wave solve")
    ap.add_argument("--destm-wave-smoke", action="store_true",
                    help="assert wave-speculative DeSTM retries == the "
                         "serial token walk bitwise across K x "
                         "contention x lanes, retry_waves <= retry "
                         "events, strict reduction on the WW best case")
    ap.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "..", "BENCH_engines.json"))
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    if args.smoke:
        run_smoke()
        return
    if args.incremental_smoke:
        run_incremental_smoke()
        return
    if args.compact_smoke:
        run_compact_smoke()
        return
    if args.shard_smoke:
        run_shard_smoke()
        return
    if args.ingress_smoke:
        run_ingress_smoke()
        return
    if args.pipeline_smoke:
        run_pipeline_smoke()
        return
    if args.destm_wave_smoke:
        run_destm_wave_smoke()
        return

    ks = (64, 256, 1024)
    bench = run_bench(ks, ("low", "med"), args.iters)
    bench["meta"] = dict(
        backend=jax.default_backend(),
        devices=len(jax.devices()),
        note="scan = pre-PR2 legacy per-txn commit scans; rebuild = PR2 "
             "batched pipeline with a from-scratch round (full run_all + "
             "rebuilt conflict analysis); incremental = PR3 RoundState "
             "loop (masked run_live over live txns, carried conflict "
             "table with delta updates, compact=False); compact = PR4 "
             "gather-compacted cascade (the live tail executes at ladder "
             "width C, device work scales with the live set).  "
             "read_phase_slots is the read-phase device-work model; "
             "walked_slots the slots the executor actually walks (K*L "
             "masked, C*L compact); live_per_round proves settled txns "
             "are skipped.  The masked executor walks the full (K, L) "
             "grid on every backend (static shapes) — the compact "
             "cascade is what turns the sparse-tail slot win into "
             "wall-clock (see axis=live_fraction for the primitive).  "
             "axis=ragged_stream: PotSession shape bucketing, compile "
             "counts bucketed vs exact.  axis=shards: the store "
             "partitioned into S contiguous range shards (per-shard "
             "conflict tables OR-reduced + S independent write-back "
             "scatters, decisions in rank space) — bit-identical to "
             "S=1 by assertion; fused_write_back rows time the "
             "primitive that runs one-scatter-per-device under a "
             "shard_map mesh.  axis=pipeline: cross-batch speculative "
             "pipelining — PotSession(pipeline_depth=D) executes batch "
             "n+1 against the pre-state snapshot while batch n "
             "commits, validates its logged read sets against "
             "committed writes (versions > snap_gv, rank-space strip "
             "kernels) and re-executes only invalidated rows; rows "
             "carry spec_executed / spec_invalidated / spec_rounds "
             "and every pipelined stream is asserted bit-identical "
             "to serial.",
        commit_steps_model="scan: K sequential device steps per round; "
                           "rebuild/incremental: ceil(log2 K) + 3 batched "
                           "stages (PCC/DeSTM; OCC: conflict-chain depth, "
                           "see wave_trips)",
    )
    bench["speedup_to_compact"] = summarize(bench["results"])
    with open(args.out, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
