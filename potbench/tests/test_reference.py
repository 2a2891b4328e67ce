"""The reference against plainer versions of itself and against the
program's own ingress pool (the reference itself imports nothing of the
program; these tests may)."""

import jax.numpy as jnp
import numpy as np
import pytest

from potbench import reference, store, traffic

NOP, READ, WRITE, RMW = 0, 1, 2, 3
INGRESS = {"fee_weight": 16, "age_weight": 1, "age_unit": 64, "size_weight": 1}


def plain_vm(init: np.ndarray, programs: list) -> tuple:
    """The VM one instruction at a time over the whole store (as
    ``chip_smoke.serial_reference`` runs it)."""
    values = np.array(init, np.int32)
    versions = np.zeros(len(values), np.int64)
    with np.errstate(over="ignore"):
        for stamp, prog in enumerate(programs, start=1):
            acc = np.zeros(values.shape[1], np.int32)
            wbuf = {}
            for op, addr, _, operand in prog:
                if op == NOP:
                    continue
                eff = int(addr) % len(values)
                if op in (READ, RMW):
                    acc = acc + (wbuf[eff] if eff in wbuf else values[eff])
                if op in (WRITE, RMW):
                    wbuf[eff] = acc + np.int32(operand)
            for a, v in wbuf.items():
                values[a] = v
                versions[a] = stamp
    return values, versions


def random_programs(rng, n, n_records, hot):
    progs = []
    for _ in range(n):
        length = int(rng.integers(1, 7))
        progs.append(tuple(
            (int(rng.choice([NOP, READ, WRITE, RMW])),
             int(rng.integers(0, hot if rng.random() < 0.5 else n_records)),
             False, int(rng.integers(0, 1 << 16)))
            for _ in range(length)))
    return progs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_grouped_execution_equals_plain_vm(seed):
    rng = np.random.default_rng(seed)
    n, slot, words = 64, 3, (seed, 7)
    progs = random_programs(rng, 400, n, hot=4)
    ref = reference.serial_execute(progs, n, slot, words)
    values, versions = plain_vm(reference.init_values(*words, np.arange(n),
                                                      slot), progs)
    assert np.array_equal(values[ref["rows"]], ref["values"])
    assert np.array_equal(versions[ref["rows"]], ref["versions"])
    assert ref["gv"] == len(progs)


def test_init_values_match_the_device():
    lo, hi = traffic.seed_words(2**31 + 987654321)
    dev = np.asarray(store.init_values_device(jnp.uint32(lo), jnp.uint32(hi),
                                              777, 250))
    assert np.array_equal(dev, reference.init_values(lo, hi, np.arange(777),
                                                     250))
    assert dev.min() >= 0 and dev.max() < 1 << 16
    other = reference.init_values(*traffic.seed_words(5), np.arange(777), 250)
    assert not np.array_equal(dev, other)


@pytest.mark.parametrize("seed", [0, 1])
def test_drain_order_equals_the_pool(seed):
    from repro.core import IngressPool
    rng = np.random.default_rng(seed)
    pool = IngressPool(capacity=1 << 16, **INGRESS)
    events, got = [], []
    for _ in range(300):
        if rng.random() < 0.8:
            lane, size = int(rng.integers(0, 8)), int(rng.integers(1, 12))
            pool.admit([(READ, 0, False, 0)] * size, lane=lane)
            events.append(("admit", lane, size))
        else:
            b = int(rng.integers(1, 40))
            fb = pool.drain(b)
            events.append(("drain", b))
            if fb is not None:
                got.append([int(i) for i in fb.txn_ids])
    assert reference.drain_order(events, INGRESS) == got


def test_compare_counts_a_perturbed_record():
    n, slot, words = 32, 2, (3, 4)
    progs = [((WRITE, 5, False, 9), (READ, 6, False, 0)),
             ((RMW, 5, False, 1),), ((READ, 7, False, 0),)]
    events = [("admit", 0, len(p)) for p in progs] + [("drain", 8)]
    values, versions = plain_vm(reference.init_values(*words, np.arange(n),
                                                      slot), progs)
    cfg = {"records": n, "slot": slot, "ingress": INGRESS}
    got = {"values": values, "versions": versions, "gv": 3, "log": [0, 1, 2]}
    checks = reference.compare(got, events, [[0, 1, 2]], progs, cfg, words)
    assert reference.correct(checks)
    values[20, 1] += 1
    checks = reference.compare(got, events, [[0, 1, 2]], progs, cfg, words)
    assert checks["record_values_mismatched"]["value"] == 1
    assert not reference.correct(checks)
    got["log"] = [1, 0, 2]
    assert reference.compare(got, events, [[0, 1, 2]], progs, cfg,
                             words)["commit_log_mismatches"]["value"] == 2
