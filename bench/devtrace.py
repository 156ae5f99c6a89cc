"""Reduction of a profiler trace to the benchmark's device numbers.

``events(path)`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
three kinds of interval, in seconds on one clock:

  ops      per device: every operation that ran (the "XLA Ops" line)
  modules  per device: every compiled program that ran ("XLA Modules")
  host     every host event of every thread (name, start, end), including
           the ``bench/chunk`` annotation the harness puts round each chunk

``summarize(...)`` turns them into a :class:`Summary`: the traced window
(first chunk annotation's start to the last one's end), the device's busy
time (union of op intervals in the window, averaged over devices), the
idle gaps between one chunk program and the next, per-op totals, and the
longest idle gaps named by what the host was doing.
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


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def events(path: str) -> dict:
    """``{"ops": {device: [(name, t0, t1)]}, "modules": {...},
    "host": [(name, t0, t1)]}`` from one xplane file.  A device plane
    without an "XLA Ops" line gives its busiest line as its ops."""
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
    op_seconds = {}
    for d in devices:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in ev["ops"][d]
               if b > lo and a < hi]
        merged = union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        for n, a, b in ops:
            op_seconds[n] = op_seconds.get(n, 0.0) + (b - a) / len(devices)
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
                   chunk_gaps_s=gaps, op_seconds=op_seconds, idle=named)


def summarize(trace_dir: str, devices: int) -> Summary:
    ev = events(find_xplane(trace_dir))
    if len(ev["ops"]) != devices:
        raise RuntimeError(f"trace holds {sorted(ev['ops'])}, the cell "
                           f"ran on {devices} devices")
    return summarize_events(ev)


def remove(trace_dir: str):
    shutil.rmtree(trace_dir, ignore_errors=True)
