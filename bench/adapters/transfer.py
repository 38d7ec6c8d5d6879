"""Deployment adapter: files moved through ``TransferService``.

A configuration whose ``adapter`` is ``transfer`` names a pool of seeded
source files (``dataset``), the service's settings (``service``) and a
warm-up request (``warmup``). Each request of the traffic is one
``TransferService.submit`` of its files, each to a fresh destination, and
one ``wait_all`` on the tasks it was split into. After the run every
finished request is checked against the plain reference
(``bench.reference``): every landed byte, every journaled digest, and where
the engine digested.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import shutil
import threading
import time

from bench import dataset, reference


class Deployment:
    """One transfer service over the configuration's pool, for one run."""

    def __init__(self, config: dict, seed: int, work: str, log):
        self.config, self.seed, self.work, self.log = config, seed, work, log
        t = time.perf_counter()
        self.pool = dataset.Pool(config["dataset"], seed, os.path.join(work, "src"))
        self.pool.write()
        sizes = [n for _, n in self.pool.files]
        log(f"dataset: {len(sizes)} files, {sum(sizes)} bytes, sizes {min(sizes)}.."
            f"{max(sizes)}, {sum(n % 4 != 0 for n in sizes)} not word-aligned, "
            f"{sum(n > 8 << 20 for n in sizes)} over 8 MiB; generated in "
            f"{time.perf_counter() - t:.6f} s")
        self.pool_size = len(sizes)
        self.svc = None
        self._lock = threading.Lock()
        self._task_ids: list[str] = []
        self._commit_s: dict[tuple[str, int], float] = {}

    def _on_event(self, ev) -> None:
        # delivered while the committing thread waits in emit(), just after
        # bytes_done grew: the time a chunk became verified and journaled
        if ev.kind == "PROGRESS":
            self._commit_s[(ev.task_id, ev.payload["chunks_done"])] = time.perf_counter()

    def start(self) -> int:
        """Start the service and send the warm-up request; the number of its
        tasks that did not succeed."""
        from repro.service import ServiceConfig, TransferService

        self.svc = TransferService(os.path.join(self.work, "svc"),
                                   ServiceConfig(**self.config["service"]))
        warm = os.path.join(self.work, "warm")
        os.makedirs(warm)
        files = [(os.path.join(warm, f"w{i}.bin"), n)
                 for i, n in enumerate(self.config["warmup"]["file_bytes"])]
        dataset.ContentStream(self.seed, int(self.config["dataset"]["block_bytes"]),
                              stream=0xE0).write_files(files)
        ids = self.svc.submit([(p, p + ".dst") for p, _ in files])
        failed = sum(st.state != "SUCCEEDED" for st in self.svc.wait_all(ids, timeout=900))
        self.svc.subscribe(self._on_event)
        return failed

    def send(self, req: dataset.Request, timeout: float) -> None:
        """One request: submit its files, wait for every task."""
        dst = os.path.join(self.work, "dst", f"r{req.number:06d}")
        req.items = [(self.pool.files[i][0], os.path.join(dst, f"{j}_{i:05d}.bin"))
                     for j, i in enumerate(req.picks)]
        req.task_ids = self.svc.submit(req.items, tenant=req.tenant)
        with self._lock:
            self._task_ids.extend(req.task_ids)
        self.svc.wait_all(req.task_ids, timeout=timeout)

    def counters(self) -> dict:
        """The program's metrics now."""
        from repro.obs.metrics import REGISTRY

        return REGISTRY.snapshot()

    def at_close(self) -> list:
        """Status of every task submitted so far, read at the window's close."""
        with self._lock:
            ids = list(self._task_ids)
        return self.svc.status_many(ids)

    def window(self, at_close: list, t0: float, t_close: float, log) -> dict:
        """What the window verified: bytes, the host time of the last chunk
        commit counted in them, and the engine's device and host bytes."""
        last = [self._commit_s.get((st.task_id, st.chunks_done), t_close)
                for st in at_close if st.chunks_done]
        out = {"bytes": sum(st.bytes_done for st in at_close),
               "t_last": max(last, default=t_close),
               "device_bytes": sum(st.verify_device_bytes for st in at_close),
               "host_bytes": sum(st.verify_host_bytes for st in at_close)}
        log(f"window: tasks {len(at_close)}, chunks verified "
            f"{sum(st.chunks_done for st in at_close)}, bytes verified {out['bytes']}, "
            f"last commit {out['t_last'] - t0:.6f} s after the open")
        log(f"engine in window: verify_device_bytes {out['device_bytes']} "
            f"verify_host_bytes {out['host_bytes']}")
        return out

    def stop(self) -> tuple[dict, list]:
        """Close the service; the final status of every task and its spans as
        ``(cat, name, t0, t1, args)``, ``args`` holding the span's ``task`` and
        ``lane`` besides its own."""
        try:
            with self._lock:
                ids = list(self._task_ids)
            final = {st.task_id: st for st in self.svc.status_many(ids)}
            records = [(s.cat, s.name, s.t0, s.t1, dict(s.args, task=s.task, lane=s.lane))
                       for s in self.svc.tracer.spans()]
        finally:
            self.svc.close()
        return final, records

    def check(self, requests: list, final: dict, log) -> dict:
        """The reference's numbers for every request (limit 0 each)."""
        threads = max(1, min(12, (os.cpu_count() or 2) - 1))
        t = time.perf_counter()

        def drop(req) -> None:
            shutil.rmtree(os.path.join(self.work, "dst", f"r{req.number:06d}"),
                          ignore_errors=True)

        with _one_blas_thread(), cf.ThreadPoolExecutor(threads) as ex:
            out = reference.check_landed(requests, final, self.pool.expected, ex, drop)
        log(f"reference: {out['files_checked']} files, {out['bytes_compared']} bytes "
            f"compared byte for byte and their journaled digests checked in "
            f"{time.perf_counter() - t:.6f} s (digests {out['digest_s']:.6f} s, "
            f"byte compare {out['compare_s']:.6f} s, {threads} threads)")
        return {k: out[k] for k in (
            "requests_unfinished", "tasks_not_succeeded", "files_unreported",
            "files_differing", "chunk_digests_wrong", "file_digests_wrong",
            "engine_host_bytes", "device_bytes_short")}


def _one_blas_thread():
    """The reference's threads each run one BLAS thread (they would
    otherwise contend for the cores); a no-op without threadpoolctl."""
    try:
        from threadpoolctl import threadpool_limits
    except ImportError:
        import contextlib

        return contextlib.nullcontext()
    return threadpool_limits(1)
