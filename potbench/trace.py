"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the breakdown read.

- Device planes are those named ``/device:TPU:<n>``; an operation is an
  event of their ``XLA Ops`` line, named by its HLO instruction (the text
  before `` = `` in the event's name, e.g. ``conflict_matrix_bits_delta.10``
  or ``fusion.629``).  The events nest (a ``while`` spans its body), so an
  operation's time is its self time, less the operations inside it.  Busy
  time is the union of the operations' intervals inside the traced window,
  averaged over the chips.
- The traced window is the harness's ``window`` span on the host plane
  (``/host:CPU``); the harness spans ``admit``, ``drain`` and ``serve`` lie
  on the same clock there.
- Idle gaps are the stretches of the window in which no operation runs on
  the device; each is put down to the harness span it overlaps most
  (``serve`` counting only where no ``drain`` runs inside it), or to
  ``outside spans``.
- Kernel events are found by the names a metric's reader gives.
"""

from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("admit", "drain", "serve")
WINDOW_SPAN = "window"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` ``jax.profiler`` wrote under ``log_dir``."""
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    """The trace as ``jax.profiler.ProfileData``; ``.gz`` is unpacked."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (n, 2) intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _overlap(a0: float, a1: float, iv: np.ndarray) -> float:
    if len(iv) == 0:
        return 0.0
    return float(np.clip(np.minimum(iv[:, 1], a1) - np.maximum(iv[:, 0], a0),
                         0, None).sum())


def op_name(event_name: str) -> str:
    """``%fusion.629 = s32[...] fusion(...)`` -> ``fusion.629``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events: list[tuple]) -> list[float]:
    """Self time of each (start, end) of a line whose events nest."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out = [e - s for s, e in events]
    stack: list[tuple[float, int]] = []
    for i in order:
        s, e = events[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= e - s
        stack.append((e, i))
    return out


def reduce(xplane: str) -> dict:
    """Everything the readers use, from one trace file.

    Returns ``window_s``, ``busy_s`` (averaged over the device planes),
    ``n_devices``, ``ops`` ({instruction: device self seconds in the
    window, averaged over the chips}), ``op_events`` ({instruction:
    count}), ``idle`` ({label: seconds}) and ``gaps`` (the longest idle
    gaps, [label, seconds]).  Times in seconds."""
    pd = load(xplane)
    host = defaultdict(list)
    dev_ops: list[np.ndarray] = []
    ops = defaultdict(float)
    op_events = defaultdict(int)
    raw_ops: list[list[tuple]] = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS or ev.name == WINDOW_SPAN:
                        host[ev.name].append((ev.start_ns, ev.end_ns))
        elif plane.name.startswith(DEVICE_PREFIX):
            evs = [(op_name(ev.name), ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            raw_ops.append(evs)
            dev_ops.append(np.asarray([(s, e) for _, s, e in evs],
                                      np.float64).reshape(-1, 2))
    if not host[WINDOW_SPAN]:
        raise ValueError(f"{xplane}: no '{WINDOW_SPAN}' span on {HOST_PLANE}")
    if not dev_ops:
        raise ValueError(f"{xplane}: no {DEVICE_PREFIX}* plane")
    w0, w1 = max(host[WINDOW_SPAN], key=lambda s: s[1] - s[0])
    for evs in raw_ops:
        evs = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
               if min(e, w1) > max(s, w0)]
        for (name, _, _), d in zip(evs, self_times([(s, e)
                                                    for _, s, e in evs])):
            ops[name] += d * 1e-9 / len(raw_ops)
            op_events[name] += 1
    spans = {k: _union(np.asarray(v, np.float64).reshape(-1, 2))
             for k, v in host.items() if k in HOST_SPANS}
    busy, idle, gaps = [], defaultdict(float), []
    for iv in dev_ops:
        u = _union(np.clip(iv, w0, w1))
        u = u[u[:, 1] > u[:, 0]]
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        for g0, g1 in edges:
            if g1 <= g0:
                continue
            ov = {k: _overlap(g0, g1, v) for k, v in spans.items()}
            if "serve" in ov:
                ov["serve"] -= ov.get("drain", 0.0)
            label = max(ov, key=ov.get) if ov and max(ov.values()) > 0 \
                else "outside spans"
            idle[label] += (g1 - g0) * 1e-9
            gaps.append([label, (g1 - g0) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": float(np.mean(busy)) * 1e-9,
            "n_devices": len(dev_ops), "ops": dict(ops),
            "op_events": dict(op_events), "idle": dict(idle),
            "gaps": gaps[:10]}


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: the ten device operations that took
    most time, and the idle time by what the host was doing, then the
    longest single gaps, ten entries in all."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["idle"].items(), key=lambda kv: -kv[1])
    longest = [[f"longest gap: {lab}", s] for lab, s in red["gaps"]]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": ([[f"all gaps: {k}", v] for k, v in idle]
                          + longest)[:10]}


def kernel_seconds(red: dict, prefixes) -> tuple[float, int]:
    """Device seconds and event count of the operations whose instruction
    name starts with any of ``prefixes``."""
    s = n = 0
    for op, sec in red["ops"].items():
        if op.startswith(tuple(prefixes)):
            s += sec
            n += red["op_events"][op]
    return s, n
