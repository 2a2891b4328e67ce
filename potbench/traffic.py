"""The one traffic generator: a cell's transactions and arrivals from its
configuration, its mix and ``--seed``.

What a transaction does is drawn apart from what it touches.  Its shape
(the key law's ranks, READ or WRITE per key, the client lane, the Poisson
arrival) comes from a generator with a fixed seed, the same in every run;
``--seed`` draws the permutation that sends each rank to a key, the
operands, and the store's initial words.  So every seed gets the same
work, with the same conflicts between the same positions, on different
records and data, and runs with different seeds differ no more than runs
of one seed.

The stream is cut into chunks of ``CHUNK`` transactions of NumPy arrays;
chunk ``c`` draws from generators seeded by ``c``, so a run takes as many
transactions as its window needs.  Each transaction draws ``keys_per_txn``
distinct ranks; its instructions are either the mix's fixed ``template`` or
one READ or WRITE per key drawn from ``op_mix``.  A key ``k`` of table
``t`` is the record ``t * n_keys + k``.
"""

from __future__ import annotations

import importlib

import numpy as np

# the transaction VM's opcodes, as repro.core.txn numbers them
OPCODES = {"NOP": 0, "READ": 1, "WRITE": 2, "RMW": 3}
CHUNK = 4096
SHAPE_SEED = 0x5EED


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) % (1 << 64) for w in words])


def seed_words(seed: int) -> tuple[int, int]:
    """The seed as two unsigned 32-bit words (low, high)."""
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


class Stream:
    """The seeded transaction stream of one cell."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.seed = seed
        self.n_keys = int(config["key_space"]["n_keys"])
        self.tables = config["key_space"]["tables"]
        self.lanes = int(config["client_lanes"])
        self.txn = mix["txn"]
        self.keys = mix["keys"]
        self.operand_range = int(mix["operand_range"])
        self.law = importlib.import_module(
            f"potbench.keylaws.{self.keys['law']}")
        self.perm = _rng(seed, 0).permutation(self.n_keys)
        arrival = mix["arrival"]
        self.rate = (float(arrival["knee_tps"]) * float(arrival["rate_share"])
                     if arrival["mode"] == "open" else None)
        self._chunks: dict[int, dict] = {}

    def chunk(self, c: int) -> dict:
        """Chunk ``c``: ``ops``, ``addrs`` and ``operands`` (CHUNK, L),
        ``lanes``, and ``gaps`` (standard exponential draws)."""
        if c in self._chunks:
            return self._chunks[c]
        shape = _rng(SHAPE_SEED, 1, c)
        data = _rng(self.seed, 1, c)
        kpt = int(self.txn["keys_per_txn"])
        keys = self.perm[self.law.draw(shape, self.n_keys, (CHUNK, kpt),
                                       self.keys)]
        if "template" in self.txn:
            tmpl = self.txn["template"]
            ops = np.tile([OPCODES[op] for op, _, _ in tmpl], (CHUNK, 1))
            addrs = np.stack([self.tables[t] * self.n_keys + keys[:, j]
                              for _, j, t in tmpl], axis=1)
        else:
            names = list(self.txn["op_mix"])
            p = np.asarray([self.txn["op_mix"][k] for k in names], np.float64)
            pick = shape.choice(len(names), size=(CHUNK, kpt), p=p / p.sum())
            ops = np.asarray([OPCODES[k] for k in names])[pick]
            addrs = self.tables[self.txn["table"]] * self.n_keys + keys
        out = {"ops": ops, "addrs": addrs,
               "operands": data.integers(0, self.operand_range,
                                         size=ops.shape),
               "lanes": shape.integers(0, self.lanes, size=CHUNK),
               "gaps": shape.standard_exponential(size=CHUNK)}
        self._chunks[c] = out
        return out

    def prefetch(self, n: int) -> None:
        """Draw the chunks that hold transactions ``[0, n)`` now."""
        for c in range(-(-n // CHUNK)):
            self.chunk(c)

    def take(self, i: int) -> tuple[tuple, int]:
        """Transaction ``i`` of the stream: (program, client lane)."""
        ch = self.chunk(i // CHUNK)
        j = i % CHUNK
        ops = ch["ops"][j].tolist()
        prog = tuple(zip(ops, ch["addrs"][j].tolist(), [False] * len(ops),
                         ch["operands"][j].tolist()))
        return prog, int(ch["lanes"][j])

    def gap(self, i: int) -> float:
        """Seconds between arrival ``i - 1`` and arrival ``i`` at ``rate``
        (open mixes): a standard exponential draw over the rate, so a sweep
        can change the rate between windows."""
        return float(self.chunk(i // CHUNK)["gaps"][i % CHUNK]) / self.rate

    def forget(self, below: int) -> None:
        """Drop the chunks wholly before transaction ``below``."""
        for c in [c for c in self._chunks if (c + 1) * CHUNK <= below]:
            del self._chunks[c]
