"""From the profiler's trace to the numbers the per-layer readers take.

A ``--trace 1`` run profiles part of its window (``bench.traced_window``,
an annotation on the host thread that drives the engine). The reduction:

1. ``events()`` flattens the ``.xplane.pb`` into compact events: every
   op of each TPU's ``XLA Ops`` line and every program of its
   ``XLA Modules`` line, and the host's annotated spans, each with its
   start and duration in ns on the trace's one clock. A device op event
   is named by its HLO instruction (``%fusion.53 = ...``) and carries no
   scope; its scope comes from the ``op_name`` metadata of that
   instruction in the HLO of the program it ran in (the ``Hlo Proto``
   that the trace's ``/host:metadata`` plane keeps for each program,
   keyed by the program's name as its ``XLA Modules`` event gives it).
   An instruction without a scope of its own (a fusion, say) takes the
   scope of the root of the computation it calls. Control-flow ops
   (``while``, ``conditional``, ``call``) span the ops of their bodies
   and are left out.
2. ``reduce()`` cuts them to the traced window and, per device, takes:
   the busy time (the union of the op intervals); the device time of
   each program (by its ``XLA Modules`` events); the device time of each super-tick
   span (an op belongs to the outermost ``obs.<scope>`` of its
   instruction's ``op_name``; a fusion is counted whole in the scope of
   its own ``op_name``, which XLA takes from the fusion's root); the top
   ops; and the longest idle gaps, each labelled by the host spans under
   it.

Numbers are seconds. Devices are averaged where a reader asks for one
number; the per-device values stay in the result.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import shutil

_SCOPE = re.compile(r"obs\.([a-z_]+)")
_INSTR = re.compile(r"%?([^\s=]+)\s*=")
_CONTAINERS = ("while", "conditional", "call")


# -- a minimal protobuf reader: the XSpace's program metadata -----------------


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized message: an
    int for a varint, a memoryview for bytes, None for fixed widths."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} not read")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list[int]:
    """A repeated int64: packed (bytes) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def hlo_protos(xplane: bytes) -> dict[str, memoryview]:
    """The serialized ``HloProto`` of each program the trace names, by the
    program's name (XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, stats=5; XStat.metadata_id=1,
    bytes_value=6)."""
    out = {}
    for f, plane in _fields(xplane):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                entry = dict(_fields(v))
                if 2 in entry:
                    sm = dict(_fields(entry[2]))
                    stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        if name != "/host:metadata":
            continue
        for entry in metas:
            md = dict(_fields(entry)).get(2)
            if md is None:
                continue
            ev_name, blob = "", None
            for g, v in _fields(md):
                if g == 2:
                    ev_name = _text(v)
                elif g == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                        blob = st[6]
            if blob is not None:
                out[ev_name] = blob
    return out


def instruction_scopes(hlo_proto) -> dict[str, tuple[str, str]]:
    """``{instruction name: (opcode, scope)}`` of one program
    (HloProto.hlo_module=1; HloModuleProto.computations=3;
    HloComputationProto.instructions=2, id=5, root_id=6;
    HloInstructionProto.name=1, opcode=2, metadata=7, id=35,
    called_computation_ids=38; OpMetadata.op_name=2)."""
    module = dict(_fields(hlo_proto)).get(1, b"")
    instrs, roots = {}, {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cid = root = None
        for g, v in _fields(comp):
            if g == 2:
                name = opcode = op_name = ""
                iid, called = None, []
                for h, w in _fields(v):
                    if h == 1:
                        name = _text(w)
                    elif h == 2:
                        opcode = _text(w)
                    elif h == 7:
                        op_name = _text(dict(_fields(w)).get(2, b""))
                    elif h == 35:
                        iid = w
                    elif h == 38:
                        called += _ints(w)
                m = _SCOPE.search(op_name)
                instrs[iid] = (name, opcode, m.group(1) if m else "", called)
            elif g == 5:
                cid = v
            elif g == 6:
                root = v
        roots[cid] = root

    def scope(iid, depth=0):
        name, opcode, own, called = instrs[iid]
        if own or depth > 8:
            return own
        for c in called:
            r = roots.get(c)
            if r in instrs:
                s = scope(r, depth + 1)
                if s:
                    return s
        return ""

    return {rec[0]: (rec[1], scope(iid)) for iid, rec in instrs.items()}


def events(xplane_path: str) -> list[dict]:
    """Compact events of one trace file (see the module doc)."""
    from jax.profiler import ProfileData

    with open(xplane_path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    protos = hlo_protos(raw)
    del raw
    programs: dict[str, dict] = {}

    def program(name: str) -> dict:
        if name not in programs:
            blob = protos.get(name)
            programs[name] = {} if blob is None else instruction_scopes(blob)
        return programs[name]

    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            lines = {line.name: line for line in plane.lines}
            mods = []
            if "XLA Modules" in lines:
                for ev in lines["XLA Modules"].events:
                    t, u = int(ev.start_ns), int(ev.duration_ns)
                    mods.append((t, t + u, ev.name))
                    out.append({"d": dev, "k": "module", "n": ev.name, "m": ev.name, "s": "",
                                "t": t, "u": u})
            mods.sort()
            starts = [a for a, _, _ in mods]
            for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
                t, u = int(ev.start_ns), int(ev.duration_ns)
                i = bisect.bisect_right(starts, t) - 1
                module = mods[i][2] if i >= 0 and t < mods[i][1] else ""
                m = _INSTR.match(ev.name)
                instr = m.group(1) if m else ev.name
                opcode, scope = program(module).get(instr, ("", ""))
                if opcode in _CONTAINERS:
                    continue
                out.append({"d": dev, "k": "op", "n": instr, "m": module, "s": scope,
                            "t": t, "u": u})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("$"):  # python tracer frames
                        continue
                    out.append({
                        "d": -1,
                        "k": "host",
                        "n": ev.name,
                        "m": line.name,
                        "s": "",
                        "t": int(ev.start_ns),
                        "u": int(ev.duration_ns),
                    })
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class Cover:
    """Merged, sorted intervals with the covered length of any range."""

    def __init__(self, intervals):
        self.iv = _union(intervals)
        self.starts = [a for a, _ in self.iv]
        self.prefix = [0]
        for a, b in self.iv:
            self.prefix.append(self.prefix[-1] + b - a)

    @property
    def total(self) -> int:
        return self.prefix[-1]

    def before(self, x) -> int:
        """Length of (-inf, x) covered."""
        i = bisect.bisect_right(self.starts, x) - 1
        if i < 0:
            return 0
        a, b = self.iv[i]
        return self.prefix[i] + min(x, b) - a

    def between(self, a, b) -> int:
        """Length of [a, b) covered."""
        return self.before(b) - self.before(a)


# Host spans that say what the host was doing: the benchmark's own
# (``bench.train_call``, ``bench.predict``), a program's dispatch
# (``PjitFunction(<program>)``), a wait for a result (``np.asarray``) and
# compilation. The runtime's own spans under them say nothing more.
_LABELS = ("bench.", "PjitFunction", "np.asarray", "backend_compile", "Compile")


def _label(gap, host) -> str:
    """What the host was doing in an idle gap: the labelled host spans
    that overlap it, the longest overlap first."""
    a, b = gap
    over = {}
    for ev in host:
        if ev["n"] == "bench.traced_window" or not ev["n"].startswith(_LABELS):
            continue
        ov = min(b, ev["t"] + ev["u"]) - max(a, ev["t"])
        if ov > 0:
            over[ev["n"]] = max(over.get(ev["n"], 0), ov)
    names = sorted(over, key=lambda k: (-over[k], k))[:4]
    return " | ".join(names) if names else "no host span"


def reduce(evs: list[dict], top: int = 10) -> dict:
    """Per-device busy, program and span times of the traced window, with
    the breakdown (see the module doc)."""
    host = [e for e in evs if e["k"] == "host"]
    win = [e for e in host if e["n"] == "bench.traced_window"]
    if not win:
        raise ValueError("the trace holds no bench.traced_window span")
    w0, w1 = win[0]["t"], win[0]["t"] + win[0]["u"]
    devices = sorted({e["d"] for e in evs if e["d"] >= 0})
    per = {}
    op_time: dict[str, float] = {}
    gaps = []
    for d in devices:
        ops = [e for e in evs if e["d"] == d and e["k"] == "op"]
        ops = [e for e in ops if e["t"] < w1 and e["t"] + e["u"] > w0]
        spans = [(max(e["t"], w0), min(e["t"] + e["u"], w1)) for e in ops]
        busy = Cover(spans)
        scope_s: dict[str, float] = {}
        for e, (a, b) in zip(ops, spans):
            dur = (b - a) * 1e-9
            if e["s"]:
                scope_s[e["s"]] = scope_s.get(e["s"], 0.0) + dur
            key = f"{e['m']}:{e['s'] or e['n']}"
            op_time[key] = op_time.get(key, 0.0) + dur / len(devices)
        module_s: dict[str, float] = {}
        modules = []
        for e in evs:
            if e["d"] == d and e["k"] == "module" and e["t"] < w1 and e["t"] + e["u"] > w0:
                a, b = max(e["t"], w0), min(e["t"] + e["u"], w1)
                module_s[e["n"]] = module_s.get(e["n"], 0.0) + (b - a) * 1e-9
                modules.append((e["n"], a, b))
        edges = [w0] + [x for iv in busy.iv for x in iv] + [w1]
        for i in range(0, len(edges), 2):
            a, b = edges[i], edges[i + 1]
            if b > a:
                gaps.append((b - a, d, (a, b)))
        per[d] = {
            "busy_s": busy.total * 1e-9,
            "cover": busy,
            "scope_s": scope_s,
            "module_s": module_s,
            "modules": modules,
        }
    gaps.sort(reverse=True)
    idle = [[f"dev{d}: {_label(iv, host)}", g * 1e-9] for g, d, iv in gaps[:top]]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    spans = [(e["n"], e["m"], e["t"], e["t"] + e["u"]) for e in host
             if e["n"].startswith("bench.") and w0 <= e["t"] < w1]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(p["busy_s"] for p in per.values()) / max(len(per), 1),
        "devices": per,
        "host_spans": spans,
        "breakdown": {"device_ops": [[k, v] for k, v in ops_top], "idle_gaps": idle},
    }


def mean_over_devices(reduced: dict, fn) -> float | None:
    """The mean over devices of ``fn(device_record)``; None where any is None."""
    vals = [fn(p) for p in reduced["devices"].values()]
    if not vals or any(v is None for v in vals):
        return None
    return sum(vals) / len(vals)


def save(evs: list[dict], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(evs, f)


def load(path: str) -> list[dict]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def reduce_dir(trace_dir: str, keep: str | None = None) -> dict:
    """Reduce the one trace under ``trace_dir`` and delete the directory.
    ``keep``: also write the compact events there (gzip JSON)."""
    try:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
        evs = events(files[0])
        if keep:
            save(evs, keep)
        return reduce(evs)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
