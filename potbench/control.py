"""The control: the reference put in the program's place with one stated
guarantee broken.  A benchmark whose comparison passes the control could
not tell a broken engine from a sound one.

``lane_order`` commits each formed batch lane by lane (rows sorted by
client lane, then by drain order) instead of in the preorder.  That is
still a serializable history, the one a later change that let lanes
commit on their own would give, but not the preorder that the
configuration guarantees.  On read-only traffic the store is the same and
only the commit log tells the two apart."""

from __future__ import annotations

import numpy as np

from potbench import reference

CONTROLS = ("lane_order",)


def output(name: str, got: dict, run, config: dict) -> dict:
    """The control's store and commit log, in the form of ``got``."""
    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}; have {CONTROLS}")
    lanes = [ev[1] for ev in run.events if ev[0] == "admit"]
    order, log, offset = [], [], 0
    for ids in run.formed:
        rows = sorted(range(len(ids)), key=lambda r: (lanes[ids[r]], r))
        order.extend(ids[r] for r in rows)
        log.extend(offset + r for r in rows)
        offset += len(ids)
    n, slot = int(config["records"]), int(config["slot"])
    ref = reference.serial_execute([run.programs[i] for i in order], n, slot,
                                   run.seed_words)
    values = reference.init_values(*run.seed_words, np.arange(n), slot)
    values[ref["rows"]] = ref["values"]
    versions = np.zeros(n, np.int64)
    versions[ref["rows"]] = ref["versions"]
    return {"values": values, "versions": versions, "gv": ref["gv"],
            "log": log}
