#!/usr/bin/env python3
"""On-chip smoke test of the served integrity path.

Drives the transfer service with the device digest backend
(``ServiceConfig(pipeline="pipelined", integrity_backend="pallas")``) through
its client entry points, in this one process, and checks every result
against the plain host reference (landed bytes equal the source; every
journaled digest equals host ``fingerprint_bytes`` of those bytes):

  A  facility large file: one 4 GiB file, 256 MiB chunks, 8 movers
  B  many-file dataset: ~2000 heavy-tailed files (4 KiB..64 MiB, ragged
     lengths) through ``submit_many``
  C  training checkpoint: a few mamba2-370m train steps at published widths,
     saved with ``submit_checkpoint``, restored and compared bit for bit
  D  (``--four-chips`` only, and then alone) chunked all-gather,
     reduce-scatter and all-reduce on a 4-device mesh at 256 MiB per device
     against ``jax.lax``, each device's shard digested on that device

Needs a TPU: without one it exits non-zero and prints no result. Run from
the root of a checkout:

    python3 chip_smoke.py [--seed N] [--four-chips]

Each phase prints its sizes and cuts, seconds, compile count and the engine's
device/host digest bytes. The last stdout line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Set JAX_COMPILATION_CACHE_DIR to place the compile cache; otherwise it goes
to ``.jax_cache/`` in the checkout. Scratch data lives in
``chip_smoke_work/`` and is removed at exit.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

KiB, MiB, GiB = 1 << 10, 1 << 20, 1 << 30
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileCounter:
    """Counts XLA backend compiles in this process (monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, *_args, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def write_random(path: str, nbytes: int, rng: np.random.Generator) -> None:
    """``nbytes`` seeded random bytes to ``path``, in 256 MiB blocks."""
    with open(path, "wb") as fh:
        left = nbytes
        while left:
            take = min(left, 256 * MiB)
            raw = rng.bit_generator.random_raw(-(-take // 8))
            fh.write(raw.view(np.uint8)[:take].tobytes())
            left -= take


def service_config(movers: int, chunk_bytes: int):
    from repro.service import ServiceConfig

    return ServiceConfig(
        pipeline="pipelined", integrity_backend="pallas",
        chunk_bytes=chunk_bytes, mover_budget=movers,
        max_concurrent_tasks=min(4, movers))


def check_landed(phase: str, statuses, pool: cf.Executor) -> tuple[int, int]:
    """Every task SUCCEEDED; every landed file equals its source; every
    journaled chunk digest, and each file digest they combine to, equals host
    ``fingerprint_bytes`` of the landed bytes. Returns the engine's
    (device_bytes, host_bytes) summed over the tasks."""
    from repro.core.integrity import Digest, combine_at_offsets, fingerprint_bytes

    for st in statuses:
        check(st.state == "SUCCEEDED", f"{phase}: task {st.task_id} ended "
              f"{st.state}: {st.error}")

    def one_chunk(job):
        rep, c = job
        off, n = c["offset"], c["length"]
        src = np.fromfile(rep.src, dtype=np.uint8, count=n, offset=off)
        dst = np.fromfile(rep.dst, dtype=np.uint8, count=n, offset=off)
        check(src.size == n == dst.size and np.array_equal(src, dst),
              f"{phase}: {rep.dst}@{off} differs from the source")
        got = fingerprint_bytes(dst)
        check(got.hexdigest() == c["digest"],
              f"{phase}: journaled digest of {rep.dst}@{off} differs from "
              "the host reference")
        return off, got

    reps = [r for st in statuses for r in st.item_reports]
    for rep in reps:
        check(os.path.getsize(rep.src) == rep.nbytes == os.path.getsize(rep.dst),
              f"{phase}: {rep.dst} is not {rep.nbytes} bytes like its source")
    parts = list(pool.map(one_chunk, [(r, c) for r in reps for c in r.chunks]))
    pos = 0
    for rep in reps:
        mine, pos = parts[pos:pos + len(rep.chunks)], pos + len(rep.chunks)
        whole = combine_at_offsets(mine, rep.nbytes) if mine else \
            fingerprint_bytes(b"")
        check(whole == Digest.from_bytes(bytes.fromhex(rep.digest_hex)),
              f"{phase}: file digest of {rep.dst} differs from the host reference")
    dev = sum(st.verify_device_bytes for st in statuses)
    host = sum(st.verify_host_bytes for st in statuses)
    return dev, host


def report_engine(phase: str, nbytes: int, dev: int, host: int) -> None:
    say(phase, f"engine device_bytes={dev} host_bytes={host}; movers' "
               f"source digests on the host (by design)={nbytes} bytes")
    check(host == 0, f"{phase}: engine digested {host} bytes on the host")
    check(dev >= nbytes, f"{phase}: engine digested {dev} < {nbytes} bytes on device")


def phase_a(work: str, seed: int, counter: CompileCounter, pool: cf.Executor,
            *, file_bytes: int = 4 * GiB, chunk_bytes: int = 256 * MiB,
            movers: int = 8) -> None:
    from repro.service import TransferService

    say("A", f"facility large file: {file_bytes} bytes, {chunk_bytes} byte chunks, "
             f"{movers} movers (cut from the paper's TB-scale files to "
             f"{file_bytes / GiB:g} GiB for one run's time; paper 64 movers x 4 "
             f"streams cut to {movers})")
    src = os.path.join(work, "a_src.bin")
    t0 = time.perf_counter()
    write_random(src, file_bytes, np.random.default_rng([seed, 0xA]))
    say("A", f"generated source in {time.perf_counter() - t0:.3f} s")
    c0, t0 = counter.n, time.perf_counter()
    svc = TransferService(os.path.join(work, "a_svc"),
                          service_config(movers, chunk_bytes))
    try:
        (tid,) = svc.submit([(src, os.path.join(work, "a_dst.bin"))])
        st = svc.wait(tid, timeout=900)
    finally:
        svc.close()
    dt = time.perf_counter() - t0
    say("A", f"task {st.state} in {dt:.3f} s ({file_bytes / dt / 1e9:.3f} GB/s "
             f"verified, host clock); compiles={counter.n - c0}; "
             f"chunks={st.chunks_done}")
    dev, host = check_landed("A", [st], pool)
    report_engine("A", file_bytes, dev, host)
    say("A", "byte-equal and digest-equal to the host reference")


def phase_b(work: str, seed: int, counter: CompileCounter, pool: cf.Executor,
            *, n_files: int = 2000, lo: int = 4 * KiB, hi: int = 64 * MiB,
            alpha: float = 0.4, chunk_bytes: int = 256 * MiB,
            movers: int = 8) -> None:
    from repro.service import TransferService

    rng = np.random.default_rng([seed, 0xB])
    u = rng.random(n_files)
    # truncated Pareto(alpha) on [lo, hi]: most files small, a heavy tail
    sizes = (lo * (1 - u * (1 - (lo / hi) ** alpha)) ** (-1 / alpha)).astype(np.int64)
    sizes = np.clip(sizes, lo, hi)
    total = int(sizes.sum())
    say("B", f"many-file dataset: {n_files} files, {total} bytes, sizes "
             f"{int(sizes.min())}..{int(sizes.max())} (median {int(np.median(sizes))}), "
             f"{int((sizes % 4 != 0).sum())} not word-aligned, "
             f"{int((sizes > 8 * MiB).sum())} over 8 MiB; truncated Pareto "
             f"alpha={alpha} (the source's 10^4 files cut to {n_files})")
    t0 = time.perf_counter()
    os.makedirs(os.path.join(work, "b_src"))
    os.makedirs(os.path.join(work, "b_dst"))
    items = []
    for i, n in enumerate(sizes):
        src = os.path.join(work, "b_src", f"f{i:05d}.bin")
        write_random(src, int(n), rng)
        items.append((src, os.path.join(work, "b_dst", f"f{i:05d}.bin")))
    say("B", f"generated sources in {time.perf_counter() - t0:.3f} s")
    c0, t0 = counter.n, time.perf_counter()
    svc = TransferService(os.path.join(work, "b_svc"),
                          service_config(movers, chunk_bytes))
    try:
        (ids,) = svc.submit_many([items])
        sts = svc.wait_all(ids, timeout=900)
    finally:
        svc.close()
    dt = time.perf_counter() - t0
    say("B", f"{len(ids)} tasks {sorted({s.state for s in sts})} in {dt:.3f} s "
             f"({total / dt / 1e9:.3f} GB/s verified, host clock); "
             f"compiles={counter.n - c0}")
    dev, host = check_landed("B", sts, pool)
    report_engine("B", total, dev, host)
    say("B", "byte-equal and digest-equal to the host reference")


def phase_c(work: str, seed: int, counter: CompileCounter, *,
            arch: str = "mamba2-370m", smoke: bool = False, steps: int = 3,
            seq_len: int = 512, global_batch: int = 8,
            chunk_bytes: int = 256 * MiB, movers: int = 8) -> None:
    import jax

    from repro.ckpt import restore_checkpoint
    from repro.core.integrity import Digest
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.kernels import fingerprint_array
    from repro.launch.train import build_training, init_state, parse_mesh
    from repro.service import TransferService
    from repro.service.ckpt_bridge import submit_checkpoint

    mesh = parse_mesh("1x1")
    model, ocfg, step_fn = build_training(
        arch, mesh, smoke=smoke, seq_len=seq_len, global_batch=global_batch,
        lr=3e-3)
    cfg = model.cfg
    say("C", f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
             f"vocab {cfg.vocab}, ssm_state {cfg.ssm_state}, expand "
             f"{cfg.ssm_expand}, head_dim {cfg.ssm_head_dim} (published widths, "
             f"depth not cut); batch {global_batch} x seq {seq_len} (cut from "
             f"train_4k's 256 x 4096), {steps} steps, mesh 1x1")
    c0, t0 = counter.n, time.perf_counter()
    params, opt = init_state(mesh, model, ocfg, seed)
    data = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                                    global_batch=global_batch, seed=seed), mesh)
    losses = []
    try:
        with mesh:
            for _ in range(steps):
                params, opt, stats = step_fn(params, opt, next(data))
                losses.append(float(stats["loss"]))
    finally:
        data.close()
    check(all(np.isfinite(losses)), f"C: non-finite loss {losses}")
    tree = {"params": params, "opt": {"step": opt.step, "m": opt.m, "v": opt.v}}
    leaves = jax.tree.leaves(tree)
    state_bytes = sum(x.nbytes for x in leaves)
    mem = jax.devices()[0].memory_stats() or {}
    say("C", f"{steps} steps in {time.perf_counter() - t0:.3f} s (compile "
             f"included), losses {losses}; state on HBM {state_bytes} bytes "
             f"({len(leaves)} leaves), bytes_in_use {mem.get('bytes_in_use')}, "
             f"peak_bytes_in_use {mem.get('peak_bytes_in_use')}; "
             f"compiles={counter.n - c0}")

    c0, t0 = counter.n, time.perf_counter()
    svc = TransferService(os.path.join(work, "c_svc"),
                          service_config(movers, chunk_bytes))
    try:
        sub = submit_checkpoint(svc, os.path.join(work, "c_ckpt"), steps, tree)
        rep = sub.wait(timeout=900)
        st = svc.status(sub.task_id)
    finally:
        svc.close()
    say("C", f"checkpoint task {st.state}: {rep.total_bytes} bytes, "
             f"{rep.n_leaves} leaves in {rep.seconds:.3f} s; "
             f"compiles={counter.n - c0}")
    report_engine("C", rep.total_bytes, st.verify_device_bytes,
                  st.verify_host_bytes)

    c0, t0 = counter.n, time.perf_counter()
    restored, rstep = restore_checkpoint(rep.path)
    check(rstep == steps, f"C: restored step {rstep}, saved {steps}")
    with open(os.path.join(rep.path, "MANIFEST.json")) as fh:
        manifest = json.load(fh)
    for key, entry in manifest["leaves"].items():
        dev_leaf, host_leaf = tree, restored
        for part in key.split("/"):
            dev_leaf, host_leaf = dev_leaf[part], host_leaf[part]
        want = np.asarray(jax.device_get(dev_leaf))
        check(host_leaf.dtype == want.dtype and host_leaf.shape == want.shape,
              f"C: {key} restored as {host_leaf.dtype}{host_leaf.shape}")
        check(np.array_equal(np.ascontiguousarray(host_leaf).view(np.uint8),
                             np.ascontiguousarray(want).view(np.uint8)),
              f"C: {key} restored bytes differ from the device array")
        res = fingerprint_array(dev_leaf)
        dig = Digest(tuple(int(v) for v in np.asarray(res)), int(dev_leaf.nbytes))
        check(dig.hexdigest() == entry["digest"],
              f"C: manifest digest of {key} differs from fingerprint_array "
              "of the device array")
    say("C", f"restore bit-equal on {len(manifest['leaves'])} leaves, every "
             f"manifest digest equals fingerprint_array on device, "
             f"{time.perf_counter() - t0:.3f} s; compiles={counter.n - c0}")


def phase_d(counter: CompileCounter, seed: int, *,
            per_device_bytes: int = 256 * MiB, cols: int = 1024) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.integrity import fingerprint_bytes
    from repro.distributed import chunked as C
    from repro.kernels import fingerprint_array

    devs = jax.devices()
    check(len(devs) == 4, f"D: needs 4 devices, JAX found {len(devs)}")
    mesh = jax.make_mesh((4,), ("x",), (AxisType.Auto,))
    rows = per_device_bytes // (4 * cols)
    sharded = NamedSharding(mesh, P("x"))
    # integer-valued f32: every summation order is exact, so chunked and
    # monolithic reductions must agree bit for bit
    x = jax.jit(lambda k: jax.random.randint(k, (4 * rows, cols), -1000, 1000)
                .astype(jnp.float32), out_shardings=sharded)(jax.random.PRNGKey(seed))
    say("D", f"mesh {dict(mesh.shape)} over {[d.id for d in devs]}; "
             f"{per_device_bytes} bytes f32 per device ({rows} x {cols})")

    def smap(fn, out_spec):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("x"),
                                     out_specs=out_spec, check_vma=False))

    cases = [
        ("all_gather",
         smap(functools.partial(C.chunked_all_gather, axis_name="x", axis_size=4), P()),
         smap(lambda v: jax.lax.all_gather(v, "x", tiled=True), P())),
        ("reduce_scatter",
         smap(functools.partial(C.chunked_reduce_scatter, axis_name="x", axis_size=4),
              P("x")),
         smap(lambda v: jax.lax.psum_scatter(v, "x", tiled=True), P("x"))),
        ("all_reduce",
         smap(functools.partial(C.chunked_all_reduce, axis_name="x", axis_size=4),
              P("x")),
         smap(lambda v: jax.lax.psum(v, "x"), P("x"))),
    ]
    for name, chunked, mono in cases:
        c0 = counter.n
        got = chunked(x).block_until_ready()
        want = mono(x).block_until_ready()
        t0 = time.perf_counter()
        chunked(x).block_until_ready()
        t_chunk = time.perf_counter() - t0
        t0 = time.perf_counter()
        mono(x).block_until_ready()
        t_mono = time.perf_counter() - t0
        check(got.shape == want.shape, f"D {name}: shape {got.shape} vs {want.shape}")
        on = {s.device for s in got.addressable_shards}
        check(on == set(devs), f"D {name}: result shards on {on}")
        for g, w in zip(sorted(got.addressable_shards, key=lambda s: s.device.id),
                        sorted(want.addressable_shards, key=lambda s: s.device.id)):
            dg, dw = fingerprint_array(g.data), fingerprint_array(w.data)
            check(dg.devices() == {g.device} and dw.devices() == {w.device},
                  f"D {name}: shard digest left device {g.device}")
            check(np.array_equal(np.asarray(dg), np.asarray(dw)),
                  f"D {name}: shard on device {g.device.id} differs from jax.lax")
        first = got.addressable_shards[0]
        host = np.asarray(first.data)
        check(fingerprint_bytes(host.view(np.uint8)).h
              == tuple(int(v) for v in np.asarray(fingerprint_array(first.data))),
              f"D {name}: device digest differs from the host reference")
        check(np.array_equal(host, np.asarray(
            [s for s in want.addressable_shards if s.device == first.device][0].data)),
            f"D {name}: differs from jax.lax on the host")
        say("D", f"{name}: bit-equal to jax.lax on all 4 devices (per-device "
                 f"digests); chunked {t_chunk:.6f} s, jax.lax {t_mono:.6f} s "
                 f"(host clock, warm); compiles={counter.n - c0}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device chunked-collective phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    counter = CompileCounter()
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache}; seed {args.seed}",
          flush=True)
    work = os.path.join(HERE, "chip_smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_d(counter, args.seed)
        else:
            with cf.ThreadPoolExecutor(8) as pool:
                phase_a(work, args.seed, counter, pool)
                shutil.rmtree(work)
                os.makedirs(work)
                phase_b(work, args.seed, counter, pool)
                shutil.rmtree(work)
                os.makedirs(work)
            phase_c(work, args.seed, counter)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"all phases passed in {time.perf_counter() - t0:.3f} s; "
          f"compiles={counter.n}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
