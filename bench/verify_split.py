#!/usr/bin/env python3
"""Where the verify path's time goes in one run of a cell.

    python3 bench/verify_split.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell exactly as ``bench/run.py`` does and prints the same lines,
then one more JSON line, ``{"verify_split": {...}}``: the service's named
spans read as numbers of the window. A span is a record
``(cat, name, t0, t1, args)``, ``args`` holding its ``task`` and ``lane``
besides its own details; spans are clipped to the window, and GB is 1e9 of
the bytes the integrity engine handed to the device in it.

  mover_digest_s_per_GB        summed ``cksum_inline`` (the movers' digest)
  verify_readback_s_per_GB     summed ``verify_readback`` (lease + read-back)
  dispatch_<phase>_s_per_GB    summed ``digest_<phase>`` of the device
                               dispatches: stage, put, wait, unpad
  verify_lag_p50_s             median over chunks verified in the window of
                               ``verify`` end - the enqueue (the start of the
                               chunk's ``verify_wait`` before it)
  verify_covered_share         readback + dispatch seconds over ``verify``
                               seconds: how much of the engine's work the
                               spans inside it account for

Each is ``None`` where its spans or the device bytes are missing. The
verify path's per-layer readers (``bench/metrics/<name>.dtn.py``) return
the same numbers from a run's ``Observations`` (``read``). With
``--trace 1`` it adds how far each ``digest_wait`` span starts from its
``jax.profiler.TraceAnnotation`` twin in the profiler's host plane, placed
on the host clock by the window marker: near the window's open and its
close.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import devtrace, harness  # noqa: E402

PHASES = ("stage", "put", "wait", "unpad")
PER_GB = {"mover_digest_s_per_GB": ("cksum_inline",),
          "verify_readback_s_per_GB": ("verify_readback",)}
PER_GB.update({f"dispatch_{p}_s_per_GB": (f"digest_{p}",) for p in PHASES})
INSIDE_VERIFY = ("verify_readback",) + tuple(f"digest_{p}" for p in PHASES)
PAIRED = 10              # digest_wait spans compared near the open and the close


def span_seconds(records, names, t0: float, t1: float) -> float | None:
    """Summed seconds of the spans named ``names``, clipped to [t0, t1];
    ``None`` when no such span exists at all."""
    found, total = False, 0.0
    for _cat, name, a, b, _args in records:
        if name in names:
            found = True
            total += max(0.0, min(b, t1) - max(a, t0))
    return total if found else None


def per_gb(records, names, t0: float, t1: float, device_bytes: int) -> float | None:
    s = span_seconds(records, names, t0, t1)
    if s is None or device_bytes <= 0:
        return None
    return s / (device_bytes / 1e9)


def verify_lags(records, t0: float, t1: float) -> list[float]:
    """Per chunk verified in [t0, t1]: ``verify`` end - the start of the last
    ``verify_wait`` of its (task, offset) that began before it."""
    waits: dict[tuple, list[float]] = {}
    for _cat, name, a, _b, args in records:
        if name == "verify_wait":
            waits.setdefault((args.get("task"), args.get("offset")), []).append(a)
    lags = []
    for _cat, name, a, b, args in records:
        if name != "verify" or not t0 <= b <= t1:
            continue
        began = [w for w in waits.get((args.get("task"), args.get("offset")), ())
                 if w <= a]
        if began:
            lags.append(b - max(began))
    return lags


def split(records, t0: float, t1: float, device_bytes: int) -> dict:
    """The numbers of the module docstring for one window."""
    out = {name: per_gb(records, names, t0, t1, device_bytes)
           for name, names in PER_GB.items()}
    lags = verify_lags(records, t0, t1)
    out["verify_lag_p50_s"] = statistics.median(lags) if lags else None
    out["verify_lag_samples"] = len(lags)
    verify = span_seconds(records, ("verify",), t0, t1)
    inside = span_seconds(records, INSIDE_VERIFY, t0, t1)
    out["verify_s_per_GB"] = per_gb(records, ("verify",), t0, t1, device_bytes)
    out["verify_covered_share"] = (inside / verify if verify and inside is not None
                                   else None)
    out["window_s"] = t1 - t0
    out["device_bytes"] = device_bytes
    return out


def annotations(logdir: str, name: str) -> list[float]:
    """Starts (trace ns) of the host-plane events called ``name``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    starts = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            starts.extend(float(ev.start_ns) for ev in line.events if ev.name == name)
    return sorted(starts)


def offsets(records, starts_ns: list[float], marker_ns: float, marker_s: float,
            t0: float, t1: float, name: str = "digest_wait") -> dict | None:
    """Annotation start - span start (us), for the first and the last
    ``PAIRED`` spans called ``name`` within [t0, t1], each against the nearest
    annotation once both are on the host clock."""
    import bisect

    ann = [marker_s + (ns - marker_ns) / 1e9 for ns in starts_ns]
    # an annotation is written when it closes: pair only spans closed in the
    # window, which the profiler was still recording
    spans = sorted(a for _c, n, a, b, _args in records if n == name and t0 <= a <= b <= t1)
    if not ann or not spans:
        return None

    def nearest(a: float) -> float:
        i = bisect.bisect_left(ann, a)
        near = [ann[j] for j in (i - 1, i) if 0 <= j < len(ann)]
        return min(near, key=lambda x: abs(x - a)) - a

    out = {"spans": len(spans), "annotations": len(ann)}
    for side, part in (("open", spans[:PAIRED]), ("close", spans[-PAIRED:])):
        d = [nearest(a) * 1e6 for a in part]
        out[f"{side}_max_abs_us"] = max(abs(x) for x in d)
        out[f"{side}_median_us"] = statistics.median(d)
    return out


def read(obs, name: str) -> float | None:
    """The number ``name`` of ``split`` for a window's ``Observations``: what
    each verify-path reader of ``bench/metrics`` returns."""
    return split(obs.records, obs.t0, obs.t1, obs.device_bytes)[name]


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, **kw) -> tuple[dict, dict]:
    """``harness.run_cell`` with its observations and (traced) the annotation
    starts kept; returns its result and the split."""
    cap: dict = {}
    real = (devtrace.extract, devtrace.reduce)

    def extract(logdir: str) -> dict:
        out = real[0](logdir)
        cap["annotations"] = annotations(logdir, "digest_wait")
        return out

    def reduce(tr: dict, marker_s: float, *a, **k):
        cap["marker"] = (tr["marker_ns"], marker_s)
        return real[1](tr, marker_s, *a, **k)

    devtrace.extract, devtrace.reduce = extract, reduce
    try:
        out = harness.run_cell(cell, seed, seconds, trace,
                               observe=lambda obs: cap.update(obs=obs), **kw)
    finally:
        devtrace.extract, devtrace.reduce = real
    obs = cap["obs"]
    res = split(obs.records, obs.t0, obs.t1, obs.device_bytes)
    if "annotations" in cap:
        res["digest_wait_vs_annotation"] = offsets(
            obs.records, cap["annotations"], *cap["marker"], obs.t0, obs.t1)
    return out, res


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one cell and split its verify time.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = harness.resolve_cell(args.workload)
    try:
        device, peaks = harness.open_chip(cell.chips)
    except RuntimeError as e:
        print(f"bench: {cell.name}: {e}", file=sys.stderr)
        return 2
    print(f"cell {cell.name}; seed {args.seed}; seconds {args.seconds}; trace "
          f"{args.trace}", flush=True)
    out, res = run(cell, args.seed, args.seconds, bool(args.trace), device=device,
                   peaks=peaks, work=harness.work_dir(cell.name),
                   log=lambda m: print(m, flush=True), t_start=t_start)
    harness.print_checks(out)
    print(json.dumps(out), flush=True)
    print(json.dumps({"verify_split": res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
