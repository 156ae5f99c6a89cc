"""Reduction of a profiler trace to the benchmark's device numbers.

``events(path)`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
three kinds of interval, in seconds on one clock:

  ops      per device: every operation that ran (the "XLA Ops" line)
  modules  per device: every compiled program that ran ("XLA Modules")
  host     every host event of every thread (name, start, end), including
           the ``bench/chunk`` annotation the harness puts round each chunk

and, per device, the name path of each op (``paths``, in the order of
``ops``): the ``tf_op`` stat of the op's event metadata, which
``jax.profiler.ProfileData`` does not show, so :func:`op_paths` reads the
xplane's protobuf itself.

``summarize(...)`` turns them into a :class:`Summary`: the traced window
(first chunk annotation's start to the last one's end), the device's busy
time (union of op intervals in the window, averaged over devices), the
idle gaps between one chunk program and the next, per-op totals, the calls
and seconds of each Pallas kernel by its name path, and the longest idle
gaps named by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import os
import shutil

CHUNK_ANNOTATION = "bench/chunk"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
# the event-metadata stat that holds the op's name path (op_name)
PATH_STAT = "tf_op"
# a Pallas kernel's op on the "XLA Ops" line: its name path ends in
# ``pallas_call``, and its HLO text is the kernel's custom call, or the
# custom fusion XLA wraps round one that writes its output in place (a
# kernel vmapped by an explicit loop)
PALLAS_PATH = "pallas_call"
PALLAS_OPS = ('custom_call_target="tpu_custom_call"', "kind=kCustom")
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


def component(part: str) -> str:
    """A name path's component without its transformations:
    ``transpose(jvp(aux_ce))`` -> ``aux_ce``."""
    m = _WRAPPED.match(part)
    while m:
        part = m.group(1)
        m = _WRAPPED.match(part)
    return part


def is_kernel(name: str, path: str) -> bool:
    """Whether the op ``name`` with name path ``path`` is a Pallas
    kernel's call (a path ends in ``:`` and the op's type, empty here)."""
    last = path.rstrip(":").rsplit("/", 1)[-1]
    return last == PALLAS_PATH and any(k in name for k in PALLAS_OPS)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """``(field number, value)`` of the protobuf message ``buf[lo:hi]``;
    a length-delimited value is its ``(start, end)`` in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 1:
            v, i = buf[i:i + 8], i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _entry_value(buf, span):
    """The value (field 2) of a protobuf map entry."""
    for num, v in _fields(buf, *span):
        if num == 2:
            return v
    return None


def op_paths(path: str) -> dict:
    """``{device plane: {op event name: name path}}`` from the ``tf_op``
    stat of each device plane's event metadata (XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        buf = f.read()
    text = lambda span: buf[span[0]:span[1]].decode("utf-8", "replace")
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for n, v in _fields(buf, *plane):
            if n == 2:
                name = text(v)
            elif n == 4:
                events.append(v)
            elif n == 5:
                meta = _entry_value(buf, v)
                fields = dict(_fields(buf, *meta))
                stat_names[fields.get(1)] = text(fields[2]) \
                    if 2 in fields else ""
        if not name or not DEVICE_PLANE.match(name):
            continue
        want = {i for i, s in stat_names.items() if s == PATH_STAT}
        paths = {}
        for entry in events:
            op, scope = None, ""
            for n, v in _fields(buf, *_entry_value(buf, entry)):
                if n == 2:
                    op = text(v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) not in want:
                        continue
                    if 5 in stat:
                        scope = text(stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if op is not None:
                paths[op] = scope
        out[name] = paths
    return out


def events(path: str) -> dict:
    """``{"ops": {device: [(name, t0, t1)]}, "modules": {...},
    "host": [(name, t0, t1)], "paths": {device: [name path of each op]}}``
    from one xplane file.  A device plane without an "XLA Ops" line gives
    its busiest line as its ops."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events]
        if not device:
            for evs in lines.values():
                out["host"].extend(evs)
            continue
        rest = {k: v for k, v in lines.items() if k != MODULES_LINE}
        ops = lines.get(OPS_LINE) or max(rest.values(), key=len,
                                         default=[])
        if ops:
            out["ops"][plane.name] = ops
            out["modules"][plane.name] = lines.get(MODULES_LINE, [])
    names = op_paths(path) if out["ops"] else {}
    out["paths"] = {d: [names.get(d, {}).get(n, "") for n, _, _ in ops]
                    for d, ops in out["ops"].items()}
    return out


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    chunk_gaps_s: list
    op_seconds: dict            # op name -> seconds, averaged over devices
    idle: list                  # [(what the host did, seconds)], longest
                                # first, the TOP longest gaps only
    # name path of a Pallas kernel's op -> [calls, seconds], averaged over
    # devices
    kernels: dict = dataclasses.field(default_factory=dict)

    def kernel(self, name: str):
        """``(calls, seconds)`` of the Pallas kernel ``name`` in the
        window: its ops whose name path has ``name`` as a component."""
        calls = seconds = 0.0
        for path, (c, s) in self.kernels.items():
            if name in map(component, path.split("/")):
                calls, seconds = calls + c, seconds + s
        return calls, seconds

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle[:TOP]]}


def _host_name(host, a, b):
    """The host event (other than the chunk annotation) that covers most
    of the gap ``[a, b]``; among equals, the shortest, i.e. innermost."""
    best, key = "host idle", (0.0, 0.0)
    for name, h0, h1 in host:
        if name == CHUNK_ANNOTATION:
            continue
        cover = min(b, h1) - max(a, h0)
        if cover <= 0:
            continue
        k = (cover, -(h1 - h0))
        if k > key:
            best, key = name, k
    return best


def summarize_events(ev: dict) -> Summary:
    host = ev["host"]
    chunks = [(a, b) for n, a, b in host if n == CHUNK_ANNOTATION]
    devices = sorted(ev["ops"])
    if chunks:
        lo, hi = min(a for a, _ in chunks), max(b for _, b in chunks)
    else:
        spans = [(a, b) for d in devices for _, a, b in ev["ops"][d]]
        lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    window = hi - lo
    busy, gaps, idle = [], [], []
    op_seconds, kernels = {}, {}
    for d in devices:
        paths = ev.get("paths", {}).get(d) or [""] * len(ev["ops"][d])
        ops = [(n, max(a, lo), min(b, hi), p)
               for (n, a, b), p in zip(ev["ops"][d], paths)
               if b > lo and a < hi]
        merged = union([(a, b) for _, a, b, _ in ops])
        busy.append(sum(b - a for a, b in merged))
        for n, a, b, p in ops:
            op_seconds[n] = op_seconds.get(n, 0.0) + (b - a) / len(devices)
            if is_kernel(n, p):
                k = kernels.setdefault(p, [0.0, 0.0])
                k[0] += 1 / len(devices)
                k[1] += (b - a) / len(devices)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        mods = ev["modules"].get(d, [])
        if mods:
            total = {}
            for n, a, b in mods:
                total[n] = total.get(n, 0.0) + b - a
            chunk = max(total, key=total.get)
            runs = sorted((a, b) for n, a, b in mods
                          if n == chunk and a >= lo and b <= hi)
            gaps += [n0 - b0 for (_, b0), (n0, _) in zip(runs, runs[1:])]
    idle.sort(key=lambda ab: ab[0] - ab[1])
    named = [(_host_name(host, a, b), b - a) for a, b in idle[:TOP]]
    return Summary(window_s=window, busy_s=sum(busy) / max(len(busy), 1),
                   chunk_gaps_s=gaps, op_seconds=op_seconds, idle=named,
                   kernels=kernels)


def summarize(trace_dir: str, devices: int) -> Summary:
    ev = events(find_xplane(trace_dir))
    if len(ev["ops"]) != devices:
        raise RuntimeError(f"trace holds {sorted(ev['ops'])}, the cell "
                           f"ran on {devices} devices")
    return summarize_events(ev)


def remove(trace_dir: str):
    shutil.rmtree(trace_dir, ignore_errors=True)
