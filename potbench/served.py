"""One cell's run on Pot's served path.

Transactions from the cell's stream are admitted one by one with
``IngressPool.admit`` on their client's lane, and ``PotSession.serve(pool,
budget=B, max_batches=1)`` forms and runs one batch, up to
``jax.block_until_ready(sess.store)``.  ``serve`` only calls
``pool.drain(budget)``, so it is handed a proxy that times each drain and
records the batch the pool formed; the program is not changed.

- A backlog mix keeps at least ``B`` transactions parked: before every
  serve it admits up to ``2B``.  Every batch is full.
- An open mix admits each transaction once its Poisson due time has passed
  and serves whenever something is parked.  Latency runs from the due time
  to the return of the serve call that committed the transaction.

Programs are built from the stream before the timed ``admit`` calls.
Warm-up serves a batch of every size the window can form (the full budget
for a backlog mix, every size from 1 to ``B`` for an open one), so nothing
compiles in the window; its transactions are part of the checked history.
After the window the pool is served to empty, and every transaction ever
admitted is held to the reference.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from potbench import traffic
from potbench.store import init_values_device
from repro.core import IngressPool, PotSession

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Spans:
    """Harness spans around the calls into the program: total seconds and
    transactions per name, and, while a profile is taken, the same spans
    as ``TraceAnnotation``s in the profiler's trace."""

    def __init__(self):
        self.annotate = False
        self.seconds: dict[str, float] = {}
        self.txns: dict[str, int] = {}

    def reset(self) -> None:
        self.seconds.clear()
        self.txns.clear()

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield self
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t0

    def count(self, name: str, n: int) -> None:
        self.txns[name] = self.txns.get(name, 0) + n


class DrainProxy:
    """What ``serve`` sees as the pool: each drain is timed and recorded."""

    def __init__(self, pool: IngressPool, run: "ServedRun"):
        self._pool = pool
        self._run = run

    def drain(self, budget: int):
        with self._run.spans("drain"):
            fb = self._pool.drain(budget)
        self._run.events.append(("drain", int(budget)))
        if fb is not None:
            self._run.spans.count("drain", fb.n_txns)
            self._run.formed.append([int(i) for i in fb.txn_ids])
        return fb


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, by JAX's own
    monitoring events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """Seconds the interpreter's garbage collector held the process, by
    generation, while ``on``."""

    def __init__(self):
        self.on = False
        self.seconds = [0.0, 0.0, 0.0]
        self.counts = [0, 0, 0]
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t0
            self.counts[g] += 1

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


class ServedRun:
    """The served path of one cell, built from its configuration, its mix
    and the seed."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.stream = traffic.Stream(config, mix, seed)
        self.budget = int(mix["budget"])
        self.seed_words = traffic.seed_words(seed)
        n, slot = int(config["records"]), int(config["slot"])
        ing = config["ingress"]
        self.pool = IngressPool(
            capacity=int(ing["capacity"]), fee_weight=int(ing["fee_weight"]),
            age_weight=int(ing["age_weight"]), age_unit=int(ing["age_unit"]),
            size_weight=int(ing["size_weight"]))
        values = init_values_device(jnp.uint32(self.seed_words[0]),
                                    jnp.uint32(self.seed_words[1]), n, slot)
        sess = config["session"]
        self.sess = PotSession(
            n, slot=slot, init=values, engine=config["engine"],
            n_lanes=int(sess["n_lanes"]),
            pipeline_depth=int(sess["pipeline_depth"]),
            shards=int(sess["shards"]), bucket_ladder=sess["bucket_ladder"])
        self.ladder = sess["bucket_ladder"]
        self.spans = Spans()
        self.proxy = DrainProxy(self.pool, self)
        self.events: list[tuple] = []  # ("admit", lane, size), ("drain", b)
        self.programs: list[tuple] = []   # by admission id
        self.formed: list[list[int]] = []  # admission ids per formed batch
        self.next = 0                      # next stream index
        self.evicted = 0

    # ----------------------------------------------------------- calls
    def draw(self, count: int) -> list[tuple]:
        """The stream's next ``count`` transactions as (program, lane)."""
        items = [self.stream.take(self.next + j) for j in range(count)]
        self.next += count
        self.stream.forget(self.next)
        return items

    def admit(self, items: list[tuple]) -> list[int]:
        """Admit (program, lane) pairs in order; return their admission
        ids.  Only the ``pool.admit`` calls are inside the span."""
        with self.spans("admit"):
            res = [self.pool.admit(p, lane=lane) for p, lane in items]
        self.spans.count("admit", len(items))
        for (p, lane), r in zip(items, res):
            self.events.append(("admit", lane, len(p)))
            self.programs.append(p)
            self.evicted += len(r.evicted)
        return [r.txn_id for r in res]

    def serve_one(self) -> list[int]:
        """Form and run one batch; return its admission ids."""
        n0 = len(self.formed)
        with self.spans("serve"):
            self.sess.serve(self.proxy, budget=self.budget, max_batches=1,
                            ladder=self.ladder)
            jax.block_until_ready(self.sess.store)
        return self.formed[n0] if len(self.formed) > n0 else []

    # ---------------------------------------------------------- phases
    def warm_sizes(self) -> list[int]:
        if self.mix.get("warm_every_size"):
            return list(range(1, self.budget + 1))
        return [self.budget]

    def warm_up(self, seconds: float) -> None:
        """Serve one batch of each size the window forms, twice over the
        largest so that the steady call path has run before the window.
        An open mix's stream is drawn here for the whole window."""
        sizes = self.warm_sizes()
        if self.stream.rate is not None:
            self.stream.prefetch(sum(sizes) + sizes[-1]
                                 + int(1.25 * self.stream.rate * seconds))
        for k in sizes + [sizes[-1]]:
            self.admit(self.draw(k))
            self.serve_one()

    def backlog_window(self, seconds: float) -> dict:
        """Serve full batches for ``seconds``; parked stays >= the budget."""
        b = self.budget
        n_tr = len(self.sess.traces)
        t0 = time.perf_counter()
        committed = batches = 0
        while True:
            self.admit(self.draw(2 * b - len(self.pool)))
            committed += len(self.serve_one())
            batches += 1
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        return {"window_s": t1 - t0, "committed": committed,
                "batches": batches, "traces": (n_tr, len(self.sess.traces))}

    def open_window(self, seconds: float) -> dict:
        """Admit on the Poisson schedule for ``seconds`` and serve whatever
        is parked; then serve what was due in the window to the end."""
        n_tr = len(self.sess.traces)
        due: dict[int, float] = {}
        done: dict[int, float] = {}
        lag = []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        next_due = t0 + self.stream.gap(self.next)
        committed = batches = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if next_due <= now:
                dues = []
                while next_due <= now:
                    dues.append(next_due)
                    next_due += self.stream.gap(self.next + len(dues))
                ids = self.admit(self.draw(len(dues)))
                t = time.perf_counter()
                for i, d in zip(ids, dues):
                    due[i] = d
                    lag.append(t - d)
            if len(self.pool):
                ids = self.serve_one()
                t = time.perf_counter()
                for i in ids:
                    done[i] = t
                committed += len(ids)
                batches += 1
            else:
                time.sleep(max(0.0, min(next_due, t_end) - now))
        t1 = time.perf_counter()
        window = {"window_s": t1 - t0, "committed": committed,
                  "batches": batches, "traces": (n_tr, len(self.sess.traces)),
                  "parked_at_close": len(self.pool)}
        # what was due before the close is admitted and served, late
        dues = []
        while next_due < t_end:
            dues.append(next_due)
            next_due += self.stream.gap(self.next + len(dues))
        ids = self.admit(self.draw(len(dues)))
        t = time.perf_counter()
        for i, d in zip(ids, dues):
            due[i] = d
            lag.append(t - d)
        while len(self.pool):
            ids = self.serve_one()
            t = time.perf_counter()
            for i in ids:
                done[i] = t
        lat = np.asarray([done[i] - d for i, d in due.items() if i in done])
        window.update(latency_s=lat, due=len(due), lag_s=np.asarray(lag))
        return window

    def finish(self) -> None:
        """Serve the pool to empty (after the window, untimed)."""
        while len(self.pool):
            self.serve_one()

    # ------------------------------------------------------- read-out
    def counters(self, window: dict) -> dict:
        """ExecTrace counters summed over the window's batches."""
        lo, hi = window["traces"]
        traces = self.sess.traces[lo:hi]
        got = jax.device_get([(t.rounds, t.walked_slots) for t in traces])
        return {"batches": len(traces),
                "rounds": int(sum(int(r) for r, _ in got)),
                "walked_slots": int(sum(int(w) for _, w in got)),
                "compile_count": self.sess.compile_count()}

    def result(self) -> dict:
        """The final store and commit log on the host, for the reference.
        Drops the session, so that its device state is freed."""
        store = self.sess.store
        got = {"values": np.asarray(store.values),
               "versions": np.asarray(store.versions),
               "gv": int(store.gv), "log": self.sess.replay_log()}
        self.sess = None
        return got
