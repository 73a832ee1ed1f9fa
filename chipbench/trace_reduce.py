"""From the profiler's ``.xplane.pb`` to the few numbers the per-layer
metrics read.  The yardstick: every PR computes these the same way.

Read with nothing but ``jax.profiler.ProfileData``.  A device is a plane
named ``/device:TPU:<n>``; on it the line ``XLA Modules`` holds one
event per execution of a compiled program (``jit__decode(<id>)``), the
line ``XLA Ops`` one event per HLO operation on the core, and the line
``Async XLA Ops`` one event per asynchronous operation, from its
``-start`` to its ``-done``.  From them:

* busy: the union of the intervals in which an operation ran on the
  core.  A ``while``, ``conditional`` or ``call`` event spans its whole
  body and the body's operations have events of their own, so control
  flow is left out: a gap inside a scan is idle time;
* the traced window: first start to last end of any device event, one
  window for all devices of the trace;
* per program: executions and device time (module events);
* per operation name: device time (digits of the instance stripped);
* collectives: time in all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute operations, on the core's line or in
  flight on the asynchronous line, and the part of it in which no
  other operation ran on that device (exposed);
* the longest idle gaps, each labelled with the host event (any host
  thread of the trace) that overlaps it most, or ``unattributed``.

Only the process that holds the chip can take the trace, so
``reduce_dir`` runs there, after the measured window, and what it
returns is plain data.
"""

from __future__ import annotations

import glob
import os
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULES, _OPS, _ASYNC = "XLA Modules", "XLA Ops", "Async XLA Ops"
_INSTANCE = re.compile(r"\(\d+\)$")
_OP_DIGITS = re.compile(r"[.\-_]?\d+$")
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
# Control flow: a `while` or `conditional` event spans its whole body, so
# it says nothing about whether an operation ran.
_CONTROL = ("while", "conditional", "call")
MAX_PROGRAM_EVENTS = 40_000
N_GAPS = 10
MAX_LABELLED_GAPS = 3000
MIN_LABELLED_GAP_NS = 50_000
N_OPS = 30


def start_trace(jax, directory: str) -> None:
    """The profiler as the benchmark starts it: no Python tracer (it
    slows the host loop that is being measured), host TraceMe events
    kept, so that idle gaps can be labelled."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory, profiler_options=options)


def newest_xplane(directory: str) -> str | None:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce_dir(directory: str) -> dict:
    path = newest_xplane(directory)
    if path is None:
        return {"devices": [], "window_s": 0.0, "planes": [],
                "error": f"no .xplane.pb under {directory}"}
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


# ------------------------------------------------------------ intervals

def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def total(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: list, b: list) -> list:
    """The part of the merged intervals ``a`` not covered by the merged
    intervals ``b``."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def gaps(merged: list, lo: float, hi: float) -> list:
    """Idle intervals of [lo, hi) given the merged busy intervals."""
    return subtract([[lo, hi]], merged)


# ------------------------------------------------------------ reduction

def program_name(event_name: str) -> str:
    return _INSTANCE.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.170 = bf16[16,4096]... fusion(...)`` -> ``fusion``: the
    trace names an operation by its whole HLO line."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return _OP_DIGITS.sub("", name)


def _events(line) -> list:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce_profile(profile) -> dict:
    planes, device_planes, host_events = [], [], []
    for plane in profile.planes:
        lines = list(plane.lines)
        planes.append({"name": plane.name, "lines": [
            [line.name, sum(1 for _ in line.events)] for line in lines]})
        match = _DEVICE.match(plane.name)
        if match:
            device_planes.append((int(match.group(1)), plane.name, lines))
        elif plane.name.startswith("/host:"):
            for line in lines:
                host_events.extend(
                    (n, s, e, line.name) for n, s, e in _events(line))
    per_device = []
    for index, name, lines in sorted(device_planes):
        by_name = {line.name: _events(line) for line in lines}
        ops = by_name.get(_OPS) or []
        modules = by_name.get(_MODULES) or []
        if ops or modules:
            per_device.append((index, name, ops, modules,
                               by_name.get(_ASYNC) or []))
    if not per_device:
        return {"devices": [], "window_s": 0.0, "planes": planes}
    host = _HostEvents(host_events)
    lo = min(s for _, _, ops, mods, _ in per_device
             for _, s, _ in ops + mods)
    hi = max(e for _, _, ops, mods, _ in per_device
             for _, _, e in ops + mods)
    devices = []
    for index, name, ops, modules, in_flight in per_device:
        ops = [(op_name(n), s, e) for n, s, e in ops]
        busy = union([[s, e] for n, s, e in ops if n not in _CONTROL]
                     or [[s, e] for _, s, e in modules])
        collective = union([[s, e] for n, s, e in ops
                            if _COLLECTIVE.search(n)]
                           + [[max(s, lo), min(e, hi)]
                              for n, s, e in in_flight
                              if _COLLECTIVE.search(op_name(n))
                              and e > lo and s < hi])
        compute = union([[s, e] for n, s, e in ops
                         if not _COLLECTIVE.search(n) and n not in _CONTROL])
        programs: dict = {}
        for n, s, e in modules:
            entry = programs.setdefault(program_name(n),
                                        {"count": 0, "total_s": 0.0})
            entry["count"] += 1
            entry["total_s"] += (e - s) / 1e9
        op_time: dict = {}
        for n, s, e in ops:
            entry = op_time.setdefault(n, [0.0, 0])
            entry[0] += (e - s) / 1e9
            entry[1] += 1
        idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
        labelled = [[(s - lo) / 1e9, (e - s) / 1e9, host.label(s, e)]
                    for s, e in idle[:MAX_LABELLED_GAPS]
                    if e - s >= MIN_LABELLED_GAP_NS]
        by_label: dict = {}
        for _, dur, label in labelled:
            entry = by_label.setdefault(label, [0.0, 0])
            entry[0] += dur
            entry[1] += 1
        devices.append({
            "index": index, "name": name,
            "busy_s": total(busy) / 1e9,
            "programs": programs,
            "program_events": [
                [program_name(n), (s - lo) / 1e9, (e - s) / 1e9]
                for n, s, e in sorted(modules, key=lambda m: m[1])
            ][:MAX_PROGRAM_EVENTS],
            "ops": sorted(([n, t, c] for n, (t, c) in op_time.items()),
                          key=lambda o: -o[1])[:N_OPS],
            "collective_s": total(collective) / 1e9,
            "collective_exposed_s": total(subtract(collective, compute))
            / 1e9,
            "idle_gaps": labelled[:N_GAPS],
            "idle_by_host": sorted(
                ([k, v[0], v[1]] for k, v in by_label.items()),
                key=lambda g: -g[1])[:N_OPS],
        })
    return {"devices": devices, "window_s": (hi - lo) / 1e9,
            "planes": planes}


class _HostEvents:
    """Labels an idle gap with the most specific host event that covers
    at least half of it: the shortest such event, as ``thread: name``
    (digits of instance ids stripped), or ``unattributed``."""

    def __init__(self, events: list):
        import numpy as np

        self._np = np
        self._names = [f"{thread}: {_INSTANCE.sub('', name)}"[:120]
                       for name, _, _, thread in events]
        self._start = np.array([s for _, s, _, _ in events], np.int64)
        self._end = np.array([e for _, _, e, _ in events], np.int64)

    def label(self, start: int, end: int) -> str:
        np = self._np
        if not len(self._start):
            return "unattributed"
        overlap = np.minimum(self._end, end) - np.maximum(self._start, start)
        covering = np.nonzero(overlap * 2 >= end - start)[0]
        if not len(covering):
            return "unattributed"
        length = self._end[covering] - self._start[covering]
        return self._names[int(covering[int(np.argmin(length))])]


# ------------------------------------------------------- for the readers

def worst_device(trace: dict | None, key: str):
    """The device with the largest ``key`` (None without devices)."""
    if not trace or not trace.get("devices"):
        return None
    return max(trace["devices"], key=lambda d: d[key])


def program_time(trace: dict | None, pattern: str):
    """(executions, device seconds) of the programs whose name matches
    ``pattern``, on device 0 of the trace; None where there is none."""
    if not trace or not trace.get("devices"):
        return None
    rx = re.compile(pattern)
    count, seconds = 0, 0.0
    for name, entry in trace["devices"][0]["programs"].items():
        if rx.search(name):
            count += entry["count"]
            seconds += entry["total_s"]
    return (count, seconds) if count else None


def breakdown(trace: dict | None) -> dict | None:
    """The contract's ``breakdown``: the ten device operations (and
    programs) that took most time, and the longest idle gaps by what
    the host was doing.  Worst (least busy) device."""
    if not trace or not trace.get("devices"):
        return None
    device = min(trace["devices"], key=lambda d: d["busy_s"])
    programs = [[f"program {n}", e["total_s"]]
                for n, e in device["programs"].items()]
    ops = [[f"op {n}", t] for n, t, _ in device["ops"]]
    top = sorted(programs, key=lambda p: -p[1])[:4] + ops[:6]
    return {"device_ops": top[:10],
            "idle_gaps": [[k, v] for k, v, _ in device["idle_by_host"][:10]]}
