"""The program's own names in a profiler trace.

``harness.trace`` names a device operation by its program and
instruction and a gap by the benchmark's ``bench.*`` spans.  This
module adds the names the program gives itself through
``repro.obs.scope``:

- ``extract`` reads a ``.xplane.pb`` into ``trace.extract``'s record,
  where each device operation also carries the program scopes on its
  HLO ``op_name`` path (outermost first, ``/``-joined; ``""`` for
  none), each device keeps its ``XLA Modules`` events (one per program
  launch), and the host list keeps the program's eager scopes beside
  the ``bench.*`` spans.  On a TPU the ``op_name`` is the ``tf_op``
  stat of the operation's event metadata, which ``ProfileData`` does
  not expose, so ``op_names`` reads it from the file's protobuf.
- ``reduce`` gives ``trace.reduce``'s numbers for that record, with an
  operation named ``<program>/<scopes>:<instruction>``
  (``jit_level_step/boost.stats/boost.sketch:fusion.738``), and each idle
  gap that a program span overlaps named by the program spans, by
  ``trace.reduce``'s rule (any other keeps its ``bench.*`` name); and besides:
  ``scope_s``, the device seconds under each program scope (an
  operation counts toward every scope on its path), and ``spans``, per
  host span name, how many start in the window, their seconds, and
  the program launches that start inside them.

A record without program scopes reduces to what ``trace.reduce`` gives.
"""
from __future__ import annotations

import re
from pathlib import Path

import numpy as np

from harness import trace

PROGRAM = ("boost.", "serve.", "sumprod.")
HOST = ("bench.",) + PROGRAM
_MODULE_ID = re.compile(r"\((\d+)\)$")


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of a protobuf message in ``buf[i:end]``;
    a length-delimited value is its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = i, i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = i, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(path: str) -> dict:
    """{(device plane, program id, operation event name): op_name} from
    the ``tf_op`` stat of each device plane's event metadata.

    xplane.proto: XSpace.planes = 1; XPlane.name = 2, event_metadata =
    4 and stat_metadata = 5 (map entries: key = 1, value = 2);
    XEventMetadata.name = 2, stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, uint64 = 3, int64 = 4, str = 5, ref = 7."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(buf, *v))
                sm = dict(_fields(buf, *entry[2])) if 2 in entry else {}
                stat_names[entry.get(1, 0)] = _text(buf, sm[2]) if 2 in sm else ""
        if not name.startswith("/device:"):
            continue
        for m in metas:
            entry = dict(_fields(buf, *m))
            if 2 not in entry:
                continue
            ev_name, stats = "", {}
            for f, v in _fields(buf, *entry[2]):
                if f == 2:
                    ev_name = _text(buf, v)
                elif f == 5:
                    st = dict(_fields(buf, *v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = _text(buf, st[5])
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    else:
                        stats[key] = st.get(3, st.get(4))
            if "tf_op" in stats and stats.get("program_id") is not None:
                out[(name, int(stats["program_id"]), ev_name)] = stats["tf_op"]
    return out


def scope_path(op_name: str) -> str:
    """The program scopes on an ``op_name`` path, outermost first
    (``jit(level_step)/boost.stats/boost.sketch/while/body/mul:`` gives
    ``boost.stats/boost.sketch``)."""
    parts = [p.rstrip(":") for p in op_name.split("/")]
    return "/".join(p for p in parts if p.startswith(PROGRAM))


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    names = op_names(path)
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.end_ns), e.name)
                          for e in lines.get("XLA Modules", []))
            starts = np.asarray([m[0] for m in mods], np.int64)
            ops = []
            for e in lines.get("XLA Ops", []):
                s, t = int(e.start_ns), int(e.end_ns)
                i = int(np.searchsorted(starts, s, side="right")) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= t else "?"
                pid = _MODULE_ID.search(mod)
                op_name = names.get((plane.name, int(pid.group(1)), e.name), "") if pid else ""
                ops.append([f"{mod.split('(')[0]}:{e.name.split(' = ')[0].lstrip('%')}",
                            s, t, scope_path(op_name)])
            devices.append({"name": plane.name, "ops": ops,
                            "modules": [[n.split("(")[0], s, t] for s, t, n in mods]})
        elif plane.name.startswith("/host:"):
            host += [[e.name, int(e.start_ns), int(e.end_ns)]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST)]
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host}


def _scoped(name: str, path: str) -> str:
    if not path:
        return name
    program, instr = name.rsplit(":", 1)
    return f"{program}/{path}:{instr}"


def reduce(rec: dict, top: int = 10) -> dict:
    devs = [{"name": d["name"],
             "ops": [[_scoped(o[0], o[3] if len(o) > 3 else ""), o[1], o[2]]
                     for o in d["ops"]]}
            for d in rec["devices"]]
    bench = [h for h in rec["host"] if h[0].startswith("bench.")]
    program = [h for h in rec["host"] if h[0].startswith(PROGRAM)]
    window = [h for h in bench if h[0] == trace.WINDOW]
    out = trace.reduce({"devices": devs, "host": bench}, top)
    if not out:
        return out
    # a gap that a program span overlaps is named by the program spans
    # (by trace.reduce's rule); any other keeps its bench.* name
    by_program = trace.reduce({"devices": devs, "host": window + program}, top)
    out["idle_gaps"] = [p if p[0] != "host" else b for p, b in
                        zip(by_program["idle_gaps"], out["idle_gaps"])]
    w0, w1 = next((s, e) for n, s, e in rec["host"] if n == trace.WINDOW)
    n_dev = len(rec["devices"])
    scope_ns: dict = {}
    for dev in rec["devices"]:
        for o in dev["ops"]:
            path = o[3] if len(o) > 3 else ""
            if not path or o[0].split(":")[-1].split(".")[0] in trace.CONTROL:
                continue
            d = min(o[2], w1) - max(o[1], w0)
            if d > 0:
                for sc in set(path.split("/")):
                    scope_ns[sc] = scope_ns.get(sc, 0) + d
    spans: dict = {}
    launches = [np.sort(np.asarray([m[1] for m in d.get("modules", [])], np.int64))
                for d in rec["devices"]]
    for n, s, e in rec["host"]:
        if n == trace.WINDOW or not (w0 <= s < w1):
            continue
        r = spans.setdefault(n, {"count": 0, "s": 0.0, "launches": 0.0})
        r["count"] += 1
        r["s"] += (e - s) / 1e9
        r["launches"] += sum(int(np.searchsorted(st, e) - np.searchsorted(st, s))
                             for st in launches) / n_dev
    out["scope_s"] = {k: v / n_dev / 1e9 for k, v in
                      sorted(scope_ns.items(), key=lambda kv: kv[1], reverse=True)}
    out["spans"] = spans
    return out
