"""Chip smoke: Pot's served path on a TPU at a 2^20-record store.

A key-value deployment of 1,048,576 one-word records takes 2,048
counter transactions (2 reads + 2 read-modify-writes each, Zipf skew
0.99, 8 client lanes), all made from ``--seed``.  Each transaction is
admitted on its client's lane into an ``IngressPool``; ``PotSession.serve``
drains it in batches of 256 and commits them.  Every phase is checked
against a plain serial NumPy executor of the transaction VM run in the
formed batches' sequence order: store values and versions, ``gv``, the
commit order (``replay_log()``), and the fingerprints across phases.

    python chip_smoke.py                # one chip: pcc, occ, destm, pogl at
                                        # pipeline depth 0; pcc, destm at 2
    python chip_smoke.py --four-chips   # pcc on a 4-shard store over 4 chips,
                                        # against the dense one-chip session

Phase lines report compile seconds, persistent-cache hits, device memory
and a wall time; the wall time is a smoke timing, not a benchmark number.
The last line is one JSON object naming the device.  With no TPU the
script exits non-zero before serving anything and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import IngressPool, PotSession, protocol  # noqa: E402
from repro.core import workloads as W  # noqa: E402
from repro.core.ingress import programs_from_batch  # noqa: E402
from repro.core.tstore import unshard_store  # noqa: E402
from repro.core.txn import NOP, READ, RMW, WRITE, TxnBatch, next_pow2  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402

N_RECORDS = 1 << 20
N_TXNS = 2048
BUDGET = 256
N_LANES = 8
SKEW = 0.99
ONE_CHIP_PHASES = (("pcc", 0), ("occ", 0), ("destm", 0), ("pogl", 0),
                   ("pcc", 2), ("destm", 2))
# pogl walks the batch serially: its step runs no conflict analysis, so
# its HLO carries no kernel to look for
KERNEL_ENGINES = ("pcc", "occ", "destm")


# ------------------------------------------------------------- reference
def serial_reference(init: np.ndarray, programs: list,
                     stamps: list[int]) -> dict:
    """Execute ``programs`` one after another against ``init`` (O, slot).

    The transaction VM, written out plainly: the accumulator starts at 0;
    READ adds the value read (the transaction's own latest deferred write
    to that address, else the store) and remembers its first word; WRITE
    defers ``acc + operand``; RMW is a READ then a WRITE of the same
    address.  An indirect address is ``|addr + last read word| mod O``.
    Deferred writes install at commit, the last one per address winning,
    and stamp the address with the transaction's entry of ``stamps``."""
    values = np.array(init, np.int32)
    n = values.shape[0]
    versions = np.zeros((n,), np.int32)
    with np.errstate(over="ignore"):
        for prog, stamp in zip(programs, stamps, strict=True):
            acc = np.zeros(values.shape[1], np.int32)
            last = np.int32(0)
            wbuf: dict[int, np.ndarray] = {}
            for op, addr, indirect, operand in prog:
                if op == NOP:
                    continue
                if indirect:
                    eff = int(np.abs(np.int32(addr) + last)) % n
                else:
                    eff = int(addr) % n
                if op in (READ, RMW):
                    rval = wbuf[eff] if eff in wbuf else values[eff]
                    acc = acc + rval
                    last = rval[0]
                if op in (WRITE, RMW):
                    wbuf[eff] = acc + np.int32(operand)
            for a, v in wbuf.items():
                values[a] = v
                versions[a] = stamp
    return {"values": values, "versions": versions, "gv": len(programs)}


def fnv1a(values: np.ndarray) -> int:
    """FNV-1a over the store image's words, as ``tstore.fingerprint``
    defines it."""
    h = 0x811C9DC5
    for x in values.astype(np.uint32).reshape(-1).tolist():
        h = ((h ^ x) * 0x01000193) & 0xFFFFFFFF
    return h


# --------------------------------------------------------------- traffic
def make_traffic(seed: int, n_records: int, n_txns: int) -> dict:
    """The deployment's data: initial record values and the admitted
    transactions with their client lanes."""
    wl = W.counters(n_txns=n_txns, n_objects=n_records, n_reads=2,
                    n_writes=2, n_lanes=N_LANES, skew=SKEW, seed=seed)
    init = np.random.default_rng(seed + 1).integers(
        0, 1 << 16, size=(n_records, 1), dtype=np.int32)
    return {"programs": programs_from_batch(wl.batch),
            "lanes": np.asarray(wl.lanes), "init": init,
            "n_records": n_records}


class CacheEvents:
    """Counts JAX's persistent compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def counts(self) -> tuple[int, int]:
        return self.hits, self.misses


def _compile_served_step(sess: PotSession, k: int, length: int):
    """AOT-compile the step ``serve`` will run for a (k, length) formed
    batch: the engine step at depth 0, the seeded step when pipelined."""
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    batch = TxnBatch(opcodes=sds((k, length), i32),
                     addrs=sds((k, length), i32),
                     indirect=sds((k, length), jnp.bool_),
                     operands=sds((k, length), i32), n_ins=sds((k,), i32))
    args = [sess.store, batch, sds((k,), i32), sds((k,), i32), sess.n_lanes]
    step = sess._step
    if sess.pipeline_depth > 0:
        step = sess._spec_step
        args.append(jax.eval_shape(protocol.spec_execute, sess.store, batch))
    return step.lower(*args).compile()


def serve_phase(traffic: dict, engine: str, depth: int, *, budget: int,
                cache: CacheEvents, shards: int = 1, mesh=None,
                require_kernels: bool = True) -> dict:
    """Admit the traffic into a fresh pool and serve it through a fresh
    session; return the session, the formed batches and the phase line's
    numbers."""
    pool = IngressPool(capacity=len(traffic["programs"]))
    for prog, lane in zip(traffic["programs"], traffic["lanes"]):
        pool.admit(prog, lane=int(lane))
    sess = PotSession(traffic["n_records"], init=traffic["init"],
                      engine=engine, n_lanes=N_LANES, pipeline_depth=depth,
                      shards=shards, mesh=mesh)
    length = next_pow2(max(len(p) for p in traffic["programs"]))
    h0, m0 = cache.counts()
    t0 = time.perf_counter()
    compiled = _compile_served_step(sess, next_pow2(budget), length)
    compile_s = time.perf_counter() - t0
    h1, m1 = cache.counts()
    n_kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    if require_kernels and engine in KERNEL_ENGINES and n_kernels == 0:
        raise AssertionError(
            f"{engine} depth={depth}: the served step's HLO holds no "
            f"tpu_custom_call — the engine took the off-TPU formulation")

    t0 = time.perf_counter()
    traces = sess.serve(pool, budget=budget)
    jax.block_until_ready(sess.store)
    wall = time.perf_counter() - t0
    h2, m2 = cache.counts()
    _, formed = IngressPool.replay(pool.journal())
    committed = sum(int((np.asarray(t.commit_pos) >= 0).sum())
                    for t in traces)
    stats = jax.devices()[0].memory_stats() or {}
    line = {
        "engine": engine, "depth": depth, "shards": shards,
        "records": traffic["n_records"], "committed": committed,
        "batches": len(traces), "rounds": sum(int(t.rounds) for t in traces),
        "spec_executed": sum(int(t.spec_executed) for t in traces),
        "compile_s": compile_s,
        "compile_cache_hit": h1 > h0,
        "cache_hits_aot": h1 - h0, "cache_misses_aot": m1 - m0,
        "cache_hits_serve": h2 - h1, "cache_misses_serve": m2 - m1,
        "tpu_custom_calls": n_kernels,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "smoke_wall_s": wall,
    }
    if depth > 0 and line["spec_executed"] == 0:
        raise AssertionError(f"{engine} depth={depth}: nothing speculated")
    return {"session": sess, "formed": formed, "line": line}


def commit_order(engine: str, formed, n_lanes: int) -> list[int] | None:
    """The global row ids (batch offset + row, as ``replay_log()`` names
    them) in the order ``engine`` serializes the formed batches.

    pcc and pogl commit in the preorder: each batch's sequence order.
    DeSTM commits round by round: a round holds the first pending
    transaction of every lane, its members commit in sequence order, and
    a barrier separates rounds — so a batch whose lanes do not alternate
    commits out of its sequence order.  OCC is the paper's baseline of
    traditional transactions: a transaction that conflicts aborts and
    commits in a later wave, so its order is decided at run time (None)."""
    if engine == "occ":
        return None
    out, offset = [], 0
    for fb in formed:
        rows = [int(r) for r in np.argsort(fb.seq, kind="stable")]
        if engine == "destm":
            queues: dict[int, list[int]] = {}
            for r in rows:
                queues.setdefault(int(fb.lanes[r]) % n_lanes, []).append(r)
            rows = []
            while any(queues.values()):
                heads = [q.pop(0) for q in queues.values() if q]
                rows.extend(sorted(heads, key=lambda r: fb.seq[r]))
        out.extend(offset + r for r in rows)
        offset += fb.n_txns
    return out


def check_phase(phase: dict, traffic: dict, refs: dict) -> None:
    """Hold a served session to the serial reference run in the order
    its engine serializes the formed batches: the commit log must be
    that order (OCC: some permutation), and the store's values, versions,
    ``gv`` and fingerprint must equal the reference's.  ``refs`` caches
    references by order."""
    sess, formed, line = phase["session"], phase["formed"], phase["line"]
    label = f"{line['engine']} depth={line['depth']} shards={line['shards']}"
    got = sess.replay_log()
    want = commit_order(line["engine"], formed, N_LANES)
    if want is not None and got != want:
        raise AssertionError(f"{label}: commit order differs from the "
                             f"engine's serialization order")
    row_txn = np.concatenate([fb.txn_ids for fb in formed])
    if sorted(got) != list(range(len(row_txn))):
        raise AssertionError(f"{label}: commit log is not a permutation")
    # a commit stamps what it writes with its sequence number; OCC's
    # serialization order is its commit order, so it stamps positions
    if line["engine"] == "occ":
        stamps = list(range(1, len(got) + 1))
    else:
        row_seq = np.concatenate([fb.seq for fb in formed])
        stamps = [int(row_seq[g]) for g in got]
    key = (tuple(got), tuple(stamps))
    if key not in refs:
        ref = serial_reference(
            traffic["init"],
            [traffic["programs"][int(row_txn[g])] for g in got], stamps)
        ref["fingerprint"] = fnv1a(ref["values"])
        refs[key] = ref
    ref = refs[key]
    store = unshard_store(sess.store)
    if not np.array_equal(np.asarray(store.values), ref["values"]):
        raise AssertionError(f"{label}: store values differ from reference")
    if not np.array_equal(np.asarray(store.versions), ref["versions"]):
        raise AssertionError(f"{label}: versions differ from reference")
    if sess.gv != ref["gv"]:
        raise AssertionError(f"{label}: gv {sess.gv} != {ref['gv']}")
    if sess.fingerprint() != ref["fingerprint"]:
        raise AssertionError(f"{label}: fingerprint differs")
    line["fingerprint"] = f"0x{ref['fingerprint']:08x}"
    line["commit_order_is_preorder"] = got == commit_order("pcc", formed,
                                                           N_LANES)


def run_phases(traffic: dict, phases, *, budget: int, cache: CacheEvents,
               require_kernels: bool = True, out=print) -> list[dict]:
    """Serve and check each (engine, depth[, shards, mesh]) phase in turn
    (sessions are dropped once checked), then require one fingerprint per
    engine across depths and layouts, shared by the preordered engines."""
    refs: dict = {}
    lines = []
    out("phase lines: compile_s is the served step's compile; smoke_wall_s "
        "is one serve call (dispatch, host work and any other compile "
        "included) — a smoke timing, not a benchmark number")
    for engine, depth, *layout in phases:
        shards, mesh = layout if layout else (1, None)
        phase = serve_phase(traffic, engine, depth, budget=budget,
                            cache=cache, shards=shards, mesh=mesh,
                            require_kernels=require_kernels)
        check_phase(phase, traffic, refs)
        phase["line"]["equals_reference"] = True
        if mesh is not None:
            phase["line"]["shard_bytes_per_device"] = {
                str(s.device): s.data.nbytes
                for s in phase["session"].store.values.addressable_shards}
            phase["line"]["bytes_in_use_per_device"] = {
                str(d): (d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat}
        out("phase " + json.dumps(phase["line"]))
        lines.append(phase["line"])
        del phase
    prints: dict[str, set] = {}
    for line in lines:
        family = "preorder" if line["engine"] in ("pcc", "pogl") \
            else line["engine"]
        prints.setdefault(family, set()).add(line["fingerprint"])
    if any(len(fps) != 1 for fps in prints.values()):
        raise AssertionError(f"fingerprints differ within an engine: {prints}")
    out("every phase equals the serial reference in its engine's commit "
        "order (values, versions, gv, commit log); fingerprints: "
        + json.dumps({k: v.pop() for k, v in prints.items()}))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve pcc on a 4-shard store over a 4-chip mesh "
                         "and compare it with the dense one-chip session")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" or jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"compile cache: {cache_dir}")
    cache = CacheEvents()
    traffic = make_traffic(args.seed, N_RECORDS, N_TXNS)
    if args.four_chips:
        if len(jax.devices()) < 4:
            print(f"chip_smoke: --four-chips needs 4 chips, JAX sees "
                  f"{len(jax.devices())}", file=sys.stderr)
            return 1
        mesh = make_host_mesh(4, "shard")
        phases = (("pcc", 0), ("pcc", 0, 4, mesh))
    else:
        phases = ONE_CHIP_PHASES
    run_phases(traffic, phases, budget=BUDGET, cache=cache)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
