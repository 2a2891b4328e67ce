"""Faults planted under the served path, to show that ``correct`` catches
them.  Each wraps the session's jitted step; the harness runs on.

- ``stale_state``: the step returns the store it was given, unchanged.
- ``half_batch``: the second half of each batch's transactions is left out
  (their rows made vacant), the rest run as usual.
- ``altered_answer``: one word of the committed store is changed where the
  step produces it.
(One chip: no exchange between chips to leave out.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("stale_state", "half_batch", "altered_answer")


def plant(run, fault: str) -> None:
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    sess = run.sess
    step = sess._step

    def broken(store, batch, seq, lanes, n_lanes):
        if fault == "half_batch":
            live = batch.n_ins > 0
            rank = jnp.cumsum(live) - 1
            keep = rank < (jnp.sum(live) + 1) // 2
            batch = jax.tree.map(lambda x: x, batch)
            batch.n_ins = jnp.where(keep, batch.n_ins, 0)
        if fault == "stale_state":
            before = jax.tree.map(jnp.copy, store)
            _, trace = step(store, batch, seq, lanes, n_lanes)
            return before, trace
        new, trace = step(store, batch, seq, lanes, n_lanes)
        if fault == "altered_answer":
            new.values = new.values.at[batch.addrs[0, 0], 0].add(1)
        return new, trace

    sess._step = broken
