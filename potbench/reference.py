"""The plain reference that decides ``correct``.  It imports nothing of the
program and takes nothing the program made.

- ``init_values``: the store's initial words, a hash of (seed, record, word),
  the NumPy twin of ``store.init_values_device``.
- ``drain_order``: the ingress pool's drain rule written out plainly: at
  each drain, repeatedly take the lane head with the smallest key
  ``(-(fee*fee_weight - size*size_weight + age_weight*(age // age_unit)),
  lane)``, ``age`` being the latest admission stamp minus the head's stamp.
- ``serial_execute``: the transaction VM run one transaction after another
  in the preorder (``chip_smoke.serial_reference``, over the records the
  history touches).
- ``compare``: the numbers that decide ``correct``, each with its limit.
"""

from __future__ import annotations

from collections import deque

import numpy as np

NOP, READ, WRITE, RMW = 0, 1, 2, 3
GROUP = 4096    # most transactions run together, to bound the temporaries

_M1, _M2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


def _fmix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(13))
    x = x * _M2
    return x ^ (x >> np.uint32(16))


def init_values(seed_lo: int, seed_hi: int, rows: np.ndarray,
                slot: int, block: int = 4096) -> np.ndarray:
    """(len(rows), slot) int32 initial words of ``rows``, each in [0, 2^16).
    Worked in blocks of rows, so that the temporaries stay in cache."""
    rows = np.asarray(rows, np.uint32)
    out = np.empty((len(rows), slot), np.uint32)
    with np.errstate(over="ignore"):
        s = _fmix(np.uint32(seed_lo) ^ _fmix(np.uint32(seed_hi)
                                             + np.uint32(0x165667B1)))
        c = _fmix(np.arange(slot, dtype=np.uint32) * np.uint32(0x27D4EB2F) ^ s)
        for lo in range(0, len(rows), block):
            r = rows[lo:lo + block] * np.uint32(0x9E3779B1)
            h = _fmix(r[:, None] ^ c)
            np.right_shift(h, np.uint32(16), out=out[lo:lo + block])
    return out.view(np.int32)


def drain_order(events: list[tuple], ingress: dict) -> list[list[int]]:
    """Admission ids per drain, in drain order, from the harness's event
    record: ``("admit", lane, size)`` (ids and stamps count admissions
    from 0 and 1) and ``("drain", budget)``.  Every fee is 0."""
    fw, aw = int(ingress["fee_weight"]), int(ingress["age_weight"])
    au, sw = int(ingress["age_unit"]), int(ingress["size_weight"])
    queues: dict[int, deque] = {}
    latest, out = 0, []
    for ev in events:
        if ev[0] == "admit":
            latest += 1
            queues.setdefault(int(ev[1]), deque()).append(
                (latest - 1, latest, int(ev[2])))
            continue
        picked = []
        while len(picked) < int(ev[1]):
            best, best_key = None, None
            for lane, q in queues.items():
                if not q:
                    continue
                _, stamp, size = q[0]
                age = (latest - stamp) // au if au > 0 else 0
                key = (-(0 * fw - size * sw + age * aw), lane)
                if best_key is None or key < best_key:
                    best, best_key = lane, key
            if best is None:
                break
            picked.append(queues[best].popleft()[0])
        if picked:
            out.append(picked)
    return out


def _as_arrays(programs: list) -> np.ndarray:
    """(N, L, 4) int64 instructions, shorter programs padded with NOPs."""
    length = max((len(p) for p in programs), default=1)
    if all(len(p) == length for p in programs):
        return np.asarray(programs, np.int64).reshape(len(programs), length, 4)
    out = np.zeros((len(programs), length, 4), np.int64)
    for i, p in enumerate(programs):
        out[i, :len(p)] = p
    return out


def serial_execute(programs: list, n_records: int, slot: int,
                   seed_words: tuple[int, int]) -> dict:
    """Run ``programs`` one after another from the initial store.

    The accumulator starts at 0; READ adds the value read (the
    transaction's own latest deferred write to that address, else the
    store); WRITE defers ``acc + operand``; RMW is a READ then a WRITE of
    the same address.  Deferred writes install at commit, the last one per
    address winning, and stamp the address with the transaction's position
    in the order (1-based).  Every address is direct (the traffic has no
    indirect instruction).

    Consecutive transactions that repeat no address within themselves, and
    of which none writes an address another of them touches, commute: each
    reads only what was committed before the first of them, so they run
    together, each one's accumulator a running sum over its instructions.
    A transaction that repeats an address runs instruction by instruction.
    Returns ``rows`` (the touched records, sorted), their final ``values``
    and ``versions``, and ``gv``."""
    arr = _as_arrays(programs)
    ops, addrs, ind, opnd = (arr[..., i] for i in range(4))
    if ind.any():
        raise ValueError("the reference runs direct addresses only")
    addrs = addrs % n_records
    rows = np.unique(addrs[ops != NOP])
    values = init_values(*seed_words, rows, slot)
    versions = np.zeros(len(rows), np.int64)
    idx = np.minimum(np.searchsorted(rows, addrs), max(len(rows) - 1, 0))
    reads = (ops == READ) | (ops == RMW)
    writes = (ops == WRITE) | (ops == RMW)
    opnd = opnd.astype(np.int32)

    def run_group(lo: int, hi: int) -> None:
        if hi <= lo:
            return
        acc = np.zeros((hi - lo, slot), np.int32)
        for j in range(arr.shape[1]):
            cur = values[idx[lo:hi, j]]
            r, w = reads[lo:hi, j], writes[lo:hi, j]
            acc[r] += cur[r]
            tgt = idx[lo:hi, j][w]
            values[tgt] = acc[w] + opnd[lo:hi, j][w][:, None]
            versions[tgt] = np.flatnonzero(w) + lo + 1

    def run_one(t: int) -> None:
        acc = np.zeros(slot, np.int32)
        wbuf: dict[int, np.ndarray] = {}
        for o, i, v in zip(ops[t], idx[t], opnd[t]):
            if o == READ or o == RMW:
                acc = acc + (wbuf[i] if i in wbuf else values[i])
            if o == WRITE or o == RMW:
                wbuf[i] = acc + v
        for i, v in wbuf.items():
            values[i] = v
            versions[i] = t + 1

    with np.errstate(over="ignore"):
        lo, seen, written = 0, set(), set()
        for t, (o_row, i_row) in enumerate(zip(ops.tolist(), idx.tolist())):
            live = [i for o, i in zip(o_row, i_row) if o != NOP]
            wr = [i for o, i in zip(o_row, i_row) if o in (WRITE, RMW)]
            distinct = len(set(live)) == len(live)
            if (not distinct or not written.isdisjoint(live)
                    or not seen.isdisjoint(wr) or t - lo >= GROUP):
                run_group(lo, t)
                lo, seen, written = t, set(), set()
            if not distinct:
                run_one(t)
                lo = t + 1
                continue
            seen.update(live)
            written.update(wr)
        run_group(lo, len(programs))
    return {"rows": rows, "values": values, "versions": versions,
            "gv": len(programs)}


def _check(value, limit) -> dict:
    return {"value": value, "limit": limit}


def compare(got: dict, events: list[tuple], formed: list[list[int]],
            programs: list, config: dict, seed_words: tuple[int, int],
            block_rows: int = 65536) -> dict:
    """Hold a run to the reference.

    ``got``: ``values`` (n_records, slot) and ``versions`` (n_records,) of
    the final store, ``gv``, and ``log``, the commit order as global row
    ids.  ``formed``: admission ids per formed batch as the pool drained
    them.  ``programs``: the admitted transactions by admission id.
    Returns {name: {"value", "limit"}}; the run is correct when every value
    is within its limit."""
    n, slot = int(config["records"]), int(config["slot"])
    want_formed = drain_order(events, config["ingress"])
    flat_got = [i for b in formed for i in b]
    flat_want = [i for b in want_formed for i in b]
    drain_bad = sum(a != b for a, b in zip(flat_got, flat_want)) \
        + abs(len(flat_got) - len(flat_want)) \
        + sum(len(a) != len(b) for a, b in zip(formed, want_formed))
    log = list(got["log"])
    log_bad = sum(a != b for a, b in zip(log, range(len(flat_want)))) \
        + abs(len(log) - len(flat_want))
    ref = serial_execute([programs[i] for i in flat_want], n, slot, seed_words)
    values, versions = got["values"], got["versions"]
    touched = ref["rows"]
    bad_rows = np.zeros(n, bool)
    bad_rows[touched] = (values[touched] != ref["values"]).any(axis=1)
    untouched = np.ones(n, bool)
    untouched[touched] = False
    for lo in range(0, n, block_rows):
        hi = min(n, lo + block_rows)
        rows = np.flatnonzero(untouched[lo:hi]) + lo
        if len(rows):
            init = init_values(*seed_words, rows, slot)
            bad_rows[rows] = (values[rows] != init).any(axis=1)
    want_ver = np.zeros(n, np.int64)
    want_ver[touched] = ref["versions"]
    return {
        "drain_order_mismatches": _check(int(drain_bad), 0),
        "commit_log_mismatches": _check(int(log_bad), 0),
        "record_values_mismatched": _check(int(bad_rows.sum()), 0),
        "record_versions_mismatched": _check(
            int((np.asarray(versions, np.int64) != want_ver).sum()), 0),
        "gv_gap": _check(abs(int(got["gv"]) - int(ref["gv"])), 0),
    }


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
