"""From a profiler trace to numbers.

``events(path)`` flattens an ``.xplane.pb`` into plain tuples; the rest
works on those tuples alone, so it is tested on a small recorded trace.

* Device busy time: the union of the intervals in which an operation
  ran on a device (its ``XLA Ops`` line), averaged over the devices.
* Kernel time: the summed durations of the device operations whose
  name, or HLO op name, matches a pattern.
* Breakdown: the device operations that took most time, and the longest
  idle gaps, each named by the host activity that overlaps it most.
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    op: str          # the HLO op name / long name, when the trace has it


def find_xplane(log_dir) -> pathlib.Path:
    paths = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def events(path) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                op = ""
                if device:
                    st = dict(e.stats)
                    op = str(st.get("long_name") or st.get("hlo_op")
                             or st.get("tf_op") or "")
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 op))
    return out


def device_ops(evs) -> list[Event]:
    return [e for e in evs if DEVICE_PLANE.match(e.plane)
            and e.line == OPS_LINE]


def _union(intervals):
    """Merged, sorted [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def busy(evs) -> tuple[float, list]:
    """(busy seconds averaged over devices, merged intervals of the
    first device)."""
    per = collections.defaultdict(list)
    for e in device_ops(evs):
        per[e.plane].append((e.start_ns, e.start_ns + e.dur_ns))
    if not per:
        return 0.0, []
    unions = {p: _union(iv) for p, iv in sorted(per.items())}
    total = sum(sum(e - s for s, e in u) for u in unions.values())
    return total / len(unions) * 1e-9, next(iter(unions.values()))


def kernel_seconds(evs, pattern: str) -> float:
    """Summed device time of the ops matching ``pattern``, averaged
    over the devices that ran any op."""
    rx = re.compile(pattern)
    ops = device_ops(evs)
    n_dev = len({e.plane for e in ops}) or 1
    return sum(e.dur_ns for e in ops
               if rx.search(e.name) or rx.search(e.op)) / n_dev * 1e-9


def short_name(name: str) -> str:
    """An op's HLO name (``%fusion.58``) from a TPU trace's op text,
    marked when it is a Pallas kernel."""
    head = name.split(" = ", 1)[0]
    return head + (" [pallas]" if "tpu_custom_call" in name else "")


def top_ops(evs, n: int = 10) -> list:
    acc = collections.Counter()
    for e in device_ops(evs):
        acc[short_name(e.name)] += e.dur_ns
    return [[name, ns * 1e-9] for name, ns in acc.most_common(n)]


def idle_gaps(evs, n: int = 10, max_host_s: float = 1.0) -> list:
    """The ``n`` longest gaps between busy intervals of the first
    device, named by the host event overlapping each most (events
    longer than ``max_host_s`` span the whole run and say nothing)."""
    _, merged = busy(evs)
    gaps = [(merged[i + 1][0] - merged[i][1], merged[i][1],
             merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(reverse=True)
    host = [e for e in evs if e.plane.startswith("/host")
            and e.dur_ns < max_host_s * 1e9]
    host.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    out = []
    for length, a, b in gaps[:n]:
        best, label = 0.0, "no host event"
        for e in host[:bisect.bisect_right(starts, b)]:
            over = min(b, e.start_ns + e.dur_ns) - max(a, e.start_ns)
            if over > best:
                best, label = over, f"host: {e.name}"
        out.append([label, length * 1e-9])
    return out
