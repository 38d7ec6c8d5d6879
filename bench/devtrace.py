"""Reduction of a profiler trace to device busy time, kernel time and gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
only what the metrics need: the start of the alignment marker on the host,
and each device plane's op events as (name, start_ns, dur_ns).
``reduce`` turns that into busy and idle time over a window, time per op
and per kernel, and the idle gaps. Host spans (``time.perf_counter``
seconds) are put on the trace's clock by the marker: a
``jax.profiler.TraceAnnotation`` opened at a known ``perf_counter`` time.
"""
from __future__ import annotations

import dataclasses
import glob
import os

MARKER = "bench_window_marker"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def extract(logdir: str) -> dict:
    """The marker and the device op events of the trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {paths}")
    pd = ProfileData.from_file(paths[0])
    marker_ns, devices = None, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    # an op event is named by its HLO text: keep the
                    # instruction's name ("%chunk_checksum_many.1 = ...")
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    events.append([name, float(ev.start_ns), float(ev.duration_ns)])
            devices.append({"plane": plane.name, "events": events})
        elif marker_ns is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker_ns = float(ev.start_ns)
                        break
    if marker_ns is None:
        raise RuntimeError(f"marker {MARKER!r} not found in {paths[0]}")
    return {"marker_ns": marker_ns, "devices": devices}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def matches(kernel: dict, name: str) -> bool:
    return any(p in name for p in kernel["match"])


@dataclasses.dataclass
class DeviceReduction:
    """Device time over one window, in seconds; gaps in host seconds."""

    window_s: float
    busy_s: float                           # union of op intervals, mean over devices
    op_s: dict[str, float]                  # per op name, summed over devices
    kernel_s: dict[str, float]              # per kernel file name
    roles: dict[str, str]                   # kernel file name -> its role
    gaps: list[tuple[float, float]]         # idle intervals of the first device

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace: dict, marker_perf_s: float, t0: float, t1: float,
           kernels: list[dict]) -> DeviceReduction:
    """Reduce an ``extract``-ed trace over the host window [t0, t1]."""
    def to_ns(t: float) -> float:
        return trace["marker_ns"] + (t - marker_perf_s) * 1e9

    def to_s(ns: float) -> float:
        return marker_perf_s + (ns - trace["marker_ns"]) / 1e9

    w0, w1 = to_ns(t0), to_ns(t1)
    busy, op_s, gaps = [], {}, []
    kernel_s = {k["name"]: 0.0 for k in kernels}
    for i, dev in enumerate(trace["devices"]):
        spans = []
        for name, start, dur in dev["events"]:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            spans.append((a, b))
            op_s[name] = op_s.get(name, 0.0) + (b - a) / 1e9
            for k in kernels:
                if matches(k, name):
                    kernel_s[k["name"]] += (b - a) / 1e9
        merged = union(spans)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        if i == 0:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(to_s(edges[j]), to_s(edges[j + 1]))
                    for j in range(0, len(edges), 2) if edges[j + 1] > edges[j]]
    return DeviceReduction(
        window_s=t1 - t0,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        op_s=op_s, kernel_s=kernel_s,
        roles={k["name"]: k["role"] for k in kernels}, gaps=gaps)


def breakdown(red: DeviceReduction, label_gap, top: int = 10) -> dict:
    """The ops that took most device time, and the longest idle gaps, each
    gap named by ``label_gap(start_s, end_s)`` (what the host was doing)."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[label_gap(a, b), b - a] for a, b in gaps]}
