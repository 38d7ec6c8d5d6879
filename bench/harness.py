"""The benchmark's run: one cell, one seed, one measured window.

Everything that belongs to one configuration, traffic mix, per-layer metric
or kernel is a file found by its name in ``BENCHMARK.json``:

  bench/configs/<config>.json     the deployment: its adapter, the system's
                                  settings, dataset, warm-up, guarantees, cuts
  bench/adapters/<adapter>.py     runs that kind of deployment: makes its
                                  data, sends one request, checks the answers
                                  against the plain reference (its
                                  ``Deployment``, below)
  bench/cases/<adapter>.py        the runs a test drives through that
                                  adapter's cells: sound ones, its control
                                  and its faults, each failing a named check
  bench/traffic/<traffic>.json    the request mix the generator reads
                                  (``bench.dataset.RequestStream``)
  bench/metrics/<metric>.py       a reader: ``read(obs) -> float | None``
  bench/kernels/<kernel>.json     names of a kernel's device events
  bench/peaks.json                the chip's peaks, by ``device_kind``

A run has the adapter generate its data from the seed, start the system and
warm every shape up with a request of its own; then the traffic's clients
send requests for the window, in a closed or an open loop. After the close
it waits for the requests in flight, reads the device memory peak, stops
the system, and has the adapter check every finished request.

A ``Deployment(config, seed, work, log)`` has ``pool_size`` and:
``start()`` (the warm-up; returns its failed tasks), ``send(request,
timeout)``, ``counters()`` (the program's metrics now, in the form of
``repro.obs.metrics.Registry.snapshot``; taken at the window's open and its
close),
``at_close()``, ``window(at_close, t0, t_close, log)`` (``bytes``,
``t_last``, ``device_bytes``), ``stop()`` (the final status of each task and
the program's spans as ``(cat, name, t0, t1, args)``) and ``check(requests,
final, log)`` (the reference's numbers, limit 0 each).

From the adapter's start to the end of the reference check a thread reads
this process's resident memory every ``RSS_EVERY_S``. A run whose memory
passes ``HOST_MEMORY_SHARE`` of the host's RAM stops its traffic, skips the
reference check, prints its result with ``host_memory_over_limit`` 1, and
ends at once with ``EXIT_OVER_LIMIT``, before the host's out-of-memory
killer ends it with nothing printed.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

from bench import dataset, devtrace, stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
GRACE_S = 120.0          # how long past the close a request may still finish
# The one-chip host has 40 GiB and its out-of-memory kill came at 40 GiB: 60 %
# leaves 16 GiB for the page cache of the files the run moves, the other
# processes of the host, and what a leaking run adds between two samples and
# while it prints (~0.2 GB at the ~0.8 GB/s such a run grew by).
HOST_MEMORY_SHARE = 0.6
RSS_EVERY_S = 0.25
EXIT_OVER_LIMIT = 4


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: str = ROOT            # the checkout whose bench/ files the run loads


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, bench: dict | None = None, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``bench`` (by default ``root``'s
    ``BENCHMARK.json``) with its files loaded from ``root``."""
    bench = bench or load_json(os.path.join(root, "BENCHMARK.json"))
    (wl,) = [w for w in bench["workloads"] if w["name"] == name] or [None]
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    (cfg,) = [c for c in bench["configs"] if c["name"] == wl["config"]]
    return Cell(
        name=name, chips=int(wl["chips"]),
        config=load_json(os.path.join(root, cfg["file"])),
        traffic=load_json(os.path.join(root, "bench", "traffic", wl["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _for_cell(m, name)],
        root=root)


def _load_module(kind: str, name: str, root: str):
    """``bench/<kind>/<name>.py`` of ``root`` as a module."""
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT):
    """``read`` of ``bench/metrics/<metric>.py``."""
    return _load_module("metrics", metric, root).read


def load_adapter(name: str, root: str = ROOT):
    """The deployment adapter ``bench/adapters/<name>.py``: its ``Deployment``
    generates the data, runs the system and checks what it produced."""
    return _load_module("adapters", name, root)


def load_cases(adapter: str, root: str = ROOT):
    """``bench/cases/<adapter>.py``: ``tiny(cell)`` and ``CASES``, each
    ``name: (kind, change, fault, check that must trip)`` with ``kind``
    ``sound``, ``control`` or ``fault``."""
    return _load_module("cases", adapter, root)


def load_kernels(root: str = ROOT) -> list[dict]:
    return [load_json(p) for p in
            sorted(glob.glob(os.path.join(root, "bench", "kernels", "*.json")))]


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise LookupError(f"device_kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


class CompileCounter:
    """Counts programs built in this process (monitoring events): ``n``
    backend compiles or persistent-cache loads, ``hits`` of them loads."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, *_args, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1

    def _on_event(self, event, *_args, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1


@dataclasses.dataclass
class Observations:
    """What the per-layer readers read, all of one window."""

    t0: float                       # window, perf_counter seconds
    t1: float
    device_bytes: int               # bytes the engine handed to the device
    device: devtrace.DeviceReduction | None
    peaks: dict | None
    # the program's spans as (cat, name, start, end, args)
    records: list[tuple] = dataclasses.field(default_factory=list)
    # what each program counter added in the window, by (name, its label
    # values joined with ",")
    counters: dict[tuple[str, str], float] = dataclasses.field(default_factory=dict)

    @functools.cached_property
    def spans(self) -> list[tuple[str, float, float]]:
        """The spans as ``(cat, start, end)``."""
        return [(cat, a, b) for cat, _name, a, b, _args in self.records]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def window_counters(before: dict, after: dict) -> dict:
    """What each counter added between two snapshots of the program's
    metrics, keyed as ``Observations.counters`` is."""
    from repro.obs.metrics import delta

    return {(name, key): value for name, fam in delta(before, after).items()
            if fam["kind"] == "counter" for key, value in fam["series"].items()}


def work_dir(cell: str) -> str:
    """Where a run of ``cell`` keeps its data: a fixed directory inside the
    checkout, emptied before and after the run."""
    return os.path.join(ROOT, "bench_work", cell)


def mem_total_bytes() -> int:
    with open("/proc/meminfo") as fh:
        return next(int(l.split()[1]) * 1024 for l in fh if l.startswith("MemTotal:"))


def host_memory_limit() -> int:
    """The resident memory a run may reach: ``HOST_MEMORY_SHARE`` of RAM."""
    return int(HOST_MEMORY_SHARE * mem_total_bytes())


def rss_bytes() -> int:
    """This process's resident memory."""
    with open("/proc/self/statm") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class MemoryGuard:
    """Reads this process's resident memory every ``RSS_EVERY_S`` on a thread
    of its own, keeps the peak, and calls ``on_trip(rss)`` once, from that
    thread, when a reading passes ``limit``."""

    def __init__(self, limit: int, on_trip):
        self.limit, self._on_trip = limit, on_trip
        self.peak = 0
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._sample, name="rss-guard", daemon=True)

    def start(self) -> MemoryGuard:
        self._thread.start()
        return self

    def _sample(self) -> None:
        while True:
            rss = rss_bytes()
            self.peak = max(self.peak, rss)
            if rss > self.limit:
                self._on_trip(rss)
                return
            if self._done.wait(RSS_EVERY_S):
                return

    def stop(self) -> None:
        self._done.set()
        self._thread.join()


def _host_line(work: str) -> str:
    return (f"host: cores {os.cpu_count()}, ram_bytes {mem_total_bytes()}, "
            f"disk_free_bytes {shutil.disk_usage(work).free}")


def drive(stream: dataset.RequestStream, send, seconds: float,
          stop: threading.Event) -> list[threading.Thread]:
    """Start the traffic's clients. ``send(request, t_submit)`` sends one
    request and returns when it is done. A closed loop runs ``clients``
    threads that each send their next request when the last one is done; an
    open loop sends each request at its arrival, timed from now, whatever
    is in flight, and its latency counts from the arrival."""
    t0 = time.perf_counter()
    if stream.traffic["loop"] == "closed":
        def client() -> None:
            while not stop.is_set():
                send(stream.next(), time.perf_counter())

        threads = [threading.Thread(target=client, name=f"client{i}", daemon=True)
                   for i in range(int(stream.traffic["clients"]))]
    else:
        def arrivals() -> None:
            for req in stream.schedule(seconds):
                if stop.wait(max(0.0, t0 + req.at_s - time.perf_counter())):
                    return
                th = threading.Thread(target=send, args=(req, t0 + req.at_s), daemon=True)
                th.start()
                threads.append(th)

        threads = [threading.Thread(target=arrivals, name="arrivals", daemon=True)]
    for th in list(threads):
        th.start()
    return threads


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             device: dict, peaks: dict | None, work: str, log=print,
             t_start: float | None = None, observe=None) -> dict:
    """One run of ``cell``; returns the result line's object. Set-up counts
    from ``t_start`` (``perf_counter``), by default from this call.
    ``observe(obs)``, where given, gets the window's ``Observations``."""
    import jax

    t_main = time.perf_counter() if t_start is None else t_start
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    cfg, traffic = cell.config, cell.traffic
    log(_host_line(work))
    counter = CompileCounter()
    lock = threading.Lock()
    requests: list[dataset.Request] = []
    stop = threading.Event()

    def device_out() -> dict:
        mem = jax.devices()[0].memory_stats() or {}
        return {"platform": device["platform"], "kind": device["kind"],
                "count": device["count"],
                "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    limit = host_memory_limit()

    def over_limit(rss: int) -> None:
        stop.set()
        with lock:
            sent = list(requests)
        out = {"correct": False, "attempted": len(sent),
               "failed": sum(1 for r in sent if r.t_done is None or r.error),
               "metrics": {}, "device": dict(device, memory_peak_bytes=0),
               "checks": {"host_memory_over_limit": {"value": 1, "limit": 0}}}
        try:
            log(f"host memory: rss {rss} bytes passed the limit {limit} bytes; "
                f"traffic stopped, reference check skipped")
            out["device"] = device_out()
        finally:
            end_over_limit(out)

    guard = MemoryGuard(limit, over_limit).start()
    try:
        dep = load_adapter(cfg["adapter"], cell.root).Deployment(cfg, seed, work, log)
        stopped = False
        try:
            t = time.perf_counter()
            warm_failed = dep.start()
            log(f"warm-up: {time.perf_counter() - t:.6f} s, programs built {counter.n} "
                f"(compile-cache hits {counter.hits}), tasks failed {warm_failed}")

            stream = dataset.RequestStream(traffic, dep.pool_size, seed)

            def send(req: dataset.Request, t_submit: float) -> None:
                with lock:
                    requests.append(req)
                req.t_submit = t_submit
                try:
                    dep.send(req, timeout=max(1.0, t1 + GRACE_S - time.perf_counter()))
                    req.t_done = time.perf_counter()
                except Exception as e:  # noqa: BLE001 — recorded, fails the run
                    req.error = repr(e)
                    stop.set()

            logdir = os.path.join(work, "trace")
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(logdir, profiler_options=opts)
            marker_s = time.perf_counter()
            with jax.profiler.TraceAnnotation(devtrace.MARKER):
                pass
            counters_open = dep.counters()
            setup_s = time.perf_counter() - t_main
            t0 = time.perf_counter()
            t1 = t0 + seconds
            c0 = counter.n
            threads = drive(stream, send, seconds, stop)
            stop.wait(max(0.0, t1 - time.perf_counter()))
            t_close = time.perf_counter()
            at_close = dep.at_close()
            counters = window_counters(counters_open, dep.counters())
            stop.set()
            compiles = counter.n - c0
            if trace:
                jax.profiler.stop_trace()
            i = 0
            while i < len(threads):     # an open loop's arrivals thread comes first
                threads[i].join(timeout=max(1.0, t1 + GRACE_S + 30 - time.perf_counter()))
                i += 1
            dev_out = device_out()
            final, records = dep.stop()
            stopped = True
        finally:
            if not stopped:
                dep.stop()

        finished = [r for r in requests if r.t_done is not None and r.t_done <= t_close]
        log(f"window: {t_close - t0:.6f} s; requests submitted {len(requests)}, finished "
            f"in window {len(finished)} (latencies "
            f"{', '.join(f'{r.t_done - r.t_submit:.6f}' for r in finished[:20])} s); "
            f"programs built in window {compiles}")
        win = dep.window(at_close, t0, t_close, log)
        e2e = {"setup_s": setup_s,
               "verified_GBps": stats.rate(win["bytes"], t0, win["t_last"])}

        result: dict = {}
        red = None
        if trace:
            red = devtrace.reduce(devtrace.extract(logdir), marker_s, t0, t_close,
                                  load_kernels(cell.root))
            dev_out["busy_s"] = red.busy_s
            dev_out["window_s"] = red.window_s
        obs = Observations(t0, t_close, win["device_bytes"], red, peaks, records, counters)
        if trace:
            metrics = {}
            for m in cell.per_layer:
                v = load_reader(m["name"], cell.root)(obs)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}

            def label(a: float, b: float) -> str:
                sec = stats.phase_seconds(obs.spans, a, b)
                return max(sec, key=sec.get)

            result["breakdown"] = devtrace.breakdown(red, label)
        else:
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] in e2e}

        checks = {k: {"value": v, "limit": 0}
                  for k, v in dep.check(requests, final, log).items()}
    finally:
        guard.stop()
    log(f"host_rss_peak_bytes {guard.peak} (limit {guard.limit} bytes, "
        f"{HOST_MEMORY_SHARE} of ram_bytes)")
    checks["warmup_tasks_failed"] = {"value": warm_failed, "limit": 0}
    checks["no_request_finished"] = {"value": int(not finished), "limit": 0}
    checks["host_memory_over_limit"] = {"value": 0, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = sum(1 for r in requests if r.t_done is None or r.error or any(
        final[tid].state != "SUCCEEDED" for tid in r.task_ids))
    shutil.rmtree(work, ignore_errors=True)
    if observe is not None:
        observe(obs)
    out = {"correct": correct, "attempted": len(requests), "failed": failed,
           "metrics": metrics, "device": dev_out}
    out.update(result)
    out["checks"] = checks
    return out


def end_over_limit(out: dict) -> None:
    """Print a run's check lines and result line, then end the process at
    once, without waiting for the system's threads. Other threads' output
    goes nowhere from here on, so the result stays the last line."""
    real_out, real_err = sys.stdout, sys.stderr
    sys.stdout = sys.stderr = open(os.devnull, "w")
    try:
        print_checks(out, real_err)
        real_out.write(json.dumps(out) + "\n")
        real_out.flush()
    finally:
        os._exit(EXIT_OVER_LIMIT)


def open_chip(chips: int) -> tuple[dict, dict]:
    """The device this process holds and its peaks. Raises ``RuntimeError``
    without a TPU, with fewer than ``chips`` chips, or for a ``device_kind``
    missing from ``bench/peaks.json``: a run never falls back to the CPU."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise RuntimeError(f"needs {chips} chips; JAX found {len(devs)}")
    try:
        peaks = load_peaks(devs[0].device_kind)
    except LookupError as e:
        raise RuntimeError(str(e)) from None
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; jax "
          f"{jax.__version__}; compile cache {cache}", flush=True)
    return ({"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}, peaks)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = resolve_cell(args.workload)
    try:
        device, peaks = open_chip(cell.chips)
    except RuntimeError as e:
        print(f"bench: {cell.name}: {e}", file=sys.stderr)
        return 2
    print(f"cell {cell.name}; seed {args.seed}; seconds {args.seconds}; trace "
          f"{args.trace}", flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device=device,
                   peaks=peaks, work=work_dir(cell.name),
                   log=lambda m: print(m, flush=True), t_start=t_start)
    print_checks(out)
    print(json.dumps(out), flush=True)
    return 0


def print_checks(out: dict, err=None) -> None:
    """The numbers compared, each beside its limit: the last lines of stderr."""
    err = err or sys.stderr
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=err)
    print(f"correct = {out['correct']}", file=err, flush=True)
