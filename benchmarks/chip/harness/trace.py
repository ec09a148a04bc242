"""From a profiler trace to the numbers a traced run reports.

``extract`` reads a ``.xplane.pb`` into a plain record: per device, the
(name, start_ns, end_ns) of every operation on its ``XLA Ops`` line,
named ``<program>:<instruction>`` (``jit_run:fusion.12``) by the
``XLA Modules`` event that holds it, and the host's ``bench.*`` spans
(``TraceAnnotation``s the benchmark put around its own calls).  ``reduce`` turns such a record into:

- ``window_s``: the length of the ``bench.window`` span;
- ``busy_s``: the union of the device's operation intervals inside
  the window, averaged over the devices;
- ``device_ops``: the ten operation names with the most device time,
  leaving out the control operations (``while``, ``conditional``,
  ``call``) whose time is that of the operations inside them;
- ``idle_gaps``: the ten longest gaps of a microsecond or more in that
  union, each named by the ``bench.*`` span (other than the window)
  that overlaps it most, the innermost of equals, or ``host`` where
  none does.  On a v5e the
  device's clock and the host's agree to about a millisecond, so the
  name of a shorter gap is a guess.

The record is JSON-able, so a small one is kept as a test fixture.
"""
from __future__ import annotations

import numpy as np

WINDOW = "bench.window"
CONTROL = ("while", "conditional", "call")
MIN_GAP_NS = 1000


def _named_ops(plane) -> list:
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted((int(e.start_ns), int(e.end_ns), e.name.split("(")[0])
                  for e in lines.get("XLA Modules", []))
    starts = np.asarray([m[0] for m in mods], np.int64)
    ops = []
    for e in lines.get("XLA Ops", []):
        s, t = int(e.start_ns), int(e.end_ns)
        i = int(np.searchsorted(starts, s, side="right")) - 1
        mod = mods[i][2] if i >= 0 and mods[i][1] >= t else "?"
        ops.append([f"{mod}:{e.name.split(' = ')[0].lstrip('%')}", s, t])
    return ops


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            devices.append({"name": plane.name, "ops": _named_ops(plane)})
        elif plane.name.startswith("/host:"):
            host += [[e.name, int(e.start_ns), int(e.end_ns)]
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted intervals of an (n, 2) array of [start, end)."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    idx = np.flatnonzero(new)
    last = np.r_[idx[1:] - 1, len(iv) - 1]
    return np.stack([iv[idx, 0], ends[last]], axis=1)


def reduce(rec: dict, top: int = 10) -> dict:
    windows = [(s, e) for n, s, e in rec["host"] if n == WINDOW]
    if not windows or not rec["devices"]:
        return {}
    w0, w1 = windows[0]
    busy, op_time, gaps = [], {}, []
    for k, dev in enumerate(rec["devices"]):
        names = [o[0] for o in dev["ops"]]
        iv = np.asarray([o[1:] for o in dev["ops"]], np.int64).reshape(-1, 2)
        iv = np.clip(iv, w0, w1)
        keep = iv[:, 1] > iv[:, 0]
        for name, (s, e) in zip(np.asarray(names, object)[keep], iv[keep]):
            if name.split(":")[-1].split(".")[0] not in CONTROL:
                op_time[name] = op_time.get(name, 0) + int(e - s)
        u = _union(iv[keep])
        busy.append(int(np.sum(u[:, 1] - u[:, 0])))
        if k == 0:
            edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
            gaps = [(int(s), int(e)) for s, e in edges if e - s >= MIN_GAP_NS]
    n_dev = len(rec["devices"])
    spans = [(n, s, e) for n, s, e in rec["host"] if n != WINDOW]

    def label(s, e):
        # the most overlap names the gap; of spans that overlap it as
        # much, the innermost (shortest) one
        best, name = (0, 0), "host"
        for n, hs, he in spans:
            key = (min(e, he) - max(s, hs), hs - he)
            if key[0] > 0 and key > best:
                best, name = key, n
        return name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    ops = sorted(op_time.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in ops[:top]],
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    }
