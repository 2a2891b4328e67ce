"""Reduction of the program's own spans in a profiler trace.

The served path marks its host layers with ``jax.profiler.TraceAnnotation``s
named ``pot.*`` (``repro/core/ingress.py``, ``repro/core/session.py``):

- ``pot.drain`` (stats ``batch``, ``k``): all of ``IngressPool.drain``;
  its self time is the heap selection and bookkeeping.
  - ``pot.drain.pack``: the NumPy fill of the batch's (K, L) arrays.
  - ``pot.drain.upload``: their host -> device copies.
- ``pot.step`` (stat ``batch``): ``PotSession._serve_formed``, one formed
  batch; its self time is the lane mapping and the step's launch.
  - ``pot.step.pad`` (stats ``k``, ``bk``): the bucket padding.
  - ``pot.step.record``: slicing the vacant rows off the trace.

``batch`` is the formed batch's first sequence number, the id a batch's
drain and step share.  The spans lie on ``/host:CPU`` on the device's
clock, beside the harness spans (``trace.py``).  Inside the harness's
``window`` span this gives, per span name: the total and self seconds
(self less the ``pot.*`` spans nested inside), the count, the summed
stats (all but the id ``batch``), and the device idle time that overlaps
the span's events (overlap: a parent's idle includes its children's).
Besides: the device's idle time in all, the idle time that overlaps no
harness or program span (waiting for work), and the five longest idle
gaps with the innermost span each falls in.  Idle numbers are averaged
over the device planes; on a trace with none they are ``None``.

    python -m potbench.program_spans TRACE.xplane.pb[.gz] ...

run from the checkout's root, prints one JSON line per trace: the
reduction and the layer numbers it gives (``numbers``).
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict

import numpy as np

from potbench.trace import (DEVICE_PREFIX, HOST_PLANE, HOST_SPANS, OPS_LINE,
                            WINDOW_SPAN, _union, load, self_times)

PREFIX = "pot."
ID_STAT = "batch"
OUTSIDE = "outside spans"


def _idle(ops: np.ndarray, w0: float, w1: float) -> np.ndarray:
    """The stretches of [w0, w1] in which no operation runs: sorted,
    disjoint (n, 2) intervals."""
    u = _union(np.clip(ops, w0, w1))
    u = u[u[:, 1] > u[:, 0]]
    gaps = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]]


def _before(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the sorted, disjoint intervals ``iv`` that lies before
    each time in ``t``."""
    csum = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])
    i = np.searchsorted(iv[:, 0], t, side="right")
    tail = np.where(i > 0, np.clip(iv[np.maximum(i - 1, 0), 1] - t, 0, None),
                    0.0)
    return csum[i] - tail


def _overlaps(iv: np.ndarray, spans: np.ndarray) -> np.ndarray:
    """Overlap of each (start, end) row of ``spans`` with ``iv``."""
    if len(iv) == 0 or len(spans) == 0:
        return np.zeros(len(spans))
    return _before(iv, spans[:, 1]) - _before(iv, spans[:, 0])


def _label(t: float, prog: list[tuple], host: dict) -> str:
    """The innermost program span running at ``t``, else the harness span,
    else ``outside spans``."""
    for group in (prog, [(n, s, e) for n, v in host.items() for s, e in v]):
        inside = [(s, -e, n) for n, s, e, *_ in group if s <= t < e]
        if inside:
            return max(inside)[2]
    return OUTSIDE


def reduce(xplane: str) -> dict:
    """The ``pot.*`` spans of one trace, inside its ``window`` span.

    Returns ``window_s``; ``spans`` ({name: {total_s, self_s, count,
    stats, idle_s}}); ``idle_s`` (all device idle time in the window),
    ``idle_outside_s`` (idle overlapping no harness or program span) and
    ``gaps`` (the five longest idle gaps, [innermost span, seconds]).
    Times in seconds."""
    pd = load(xplane)
    host: dict[str, list] = defaultdict(list)
    lines: list[list[tuple]] = []
    dev: list[np.ndarray] = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        evs.append((ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
                    elif ev.name in HOST_SPANS or ev.name == WINDOW_SPAN:
                        host[ev.name].append((ev.start_ns, ev.end_ns))
                lines.append(evs)
        elif plane.name.startswith(DEVICE_PREFIX):
            dev.append(np.asarray([(ev.start_ns, ev.end_ns)
                                   for line in plane.lines
                                   if line.name == OPS_LINE
                                   for ev in line.events],
                                  np.float64).reshape(-1, 2))
    if not host[WINDOW_SPAN]:
        raise ValueError(f"{xplane}: no '{WINDOW_SPAN}' span on {HOST_PLANE}")
    w0, w1 = max(host.pop(WINDOW_SPAN), key=lambda s: s[1] - s[0])
    prog: list[tuple] = []   # (name, start, end, stats, self ns)
    for evs in lines:
        evs = [(n, max(s, w0), min(e, w1), st) for n, s, e, st in evs
               if min(e, w1) > max(s, w0)]
        own = self_times([(s, e) for _, s, e, _ in evs])
        prog += [ev + (d,) for ev, d in zip(evs, own)]
    idles = [_idle(ops, w0, w1) for ops in dev]
    spans: dict[str, dict] = {}
    for name in sorted({ev[0] for ev in prog}):
        evs = [ev for ev in prog if ev[0] == name]
        iv = np.asarray([(s, e) for _, s, e, _, _ in evs], np.float64)
        stats: dict[str, int] = defaultdict(int)
        for ev in evs:
            for k, v in ev[3].items():
                if k != ID_STAT:
                    stats[k] += int(v)
        spans[name] = {
            "total_s": float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9,
            "self_s": sum(ev[4] for ev in evs) * 1e-9,
            "count": len(evs), "stats": dict(stats),
            "idle_s": (float(np.mean([_overlaps(g, iv).sum()
                                      for g in idles])) * 1e-9
                       if idles else None)}
    out = {"window_s": (w1 - w0) * 1e-9, "spans": spans, "idle_s": None,
           "idle_outside_s": None, "gaps": []}
    if idles:
        covered = _union(np.asarray(
            [(s, e) for _, s, e, *_ in prog]
            + [se for v in host.values() for se in v],
            np.float64).reshape(-1, 2))
        idle = [float((g[:, 1] - g[:, 0]).sum()) for g in idles]
        inside = [float(_overlaps(g, covered).sum()) for g in idles]
        out["idle_s"] = float(np.mean(idle)) * 1e-9
        out["idle_outside_s"] = (float(np.mean(idle))
                                 - float(np.mean(inside))) * 1e-9
        gaps = np.concatenate(idles)
        gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:5]]
        out["gaps"] = [[_label((g0 + g1) / 2, prog, host), (g1 - g0) * 1e-9]
                       for g0, g1 in gaps]
    return out


def numbers(red: dict) -> dict:
    """The layer numbers the spans give (``None`` where a span is
    missing): per drained transaction, the drain's selection (self time of
    ``pot.drain``), pack and upload in microseconds; per step, the
    padding and the trace slicing in milliseconds; the device idle share
    inside ``pot.step`` and inside ``pot.drain``; the padded rows' share of
    the buckets."""
    sp = red["spans"]

    def get(name, field, default=None):
        return sp[name][field] if name in sp else default

    k = get("pot.drain", "stats", {}).get("k")
    steps = get("pot.step", "count")
    pad = get("pot.step.pad", "stats", {})

    def per(name, field, den, scale):
        v = get(name, field)
        return v / den * scale if v is not None and den else None

    def idle(name):
        v = get(name, "idle_s")
        return v / red["window_s"] if v is not None else None

    return {
        "ingress.select_us_per_txn": per("pot.drain", "self_s", k, 1e6),
        "ingress.pack_us_per_txn": per("pot.drain.pack", "total_s", k, 1e6),
        "ingress.upload_us_per_txn": per("pot.drain.upload", "total_s", k,
                                         1e6),
        "ingress.drain_us_per_txn": per("pot.drain", "total_s", k, 1e6),
        "session.pad_ms_per_batch": per("pot.step.pad", "total_s", steps,
                                        1e3),
        "session.record_ms_per_batch": per("pot.step.record", "total_s",
                                           steps, 1e3),
        "session.idle_share": idle("pot.step"),
        "ingress.idle_share": idle("pot.drain"),
        "session.pad_rows_share": ((pad["bk"] - pad["k"]) / pad["bk"]
                                   if pad.get("bk") else None),
        "device.idle_share": (red["idle_s"] / red["window_s"]
                              if red["idle_s"] is not None else None),
    }


def main(paths: list[str]) -> int:
    for path in paths:
        red = reduce(path)
        print(json.dumps({"trace": path, "numbers": numbers(red), **red}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
