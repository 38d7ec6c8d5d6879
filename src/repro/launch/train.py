"""Training driver: data pipeline -> train_step -> chunked checkpoints.

Fault tolerance story (exercised by tests/test_train_loop.py and
examples/train_e2e.py):

  * checkpoints are chunked + integrity-checked + journaled (repro.ckpt);
    a crash mid-save leaves a resumable journal, a crash between saves
    restarts from the latest verified step;
  * the data pipeline is (seed, step)-keyed, so restore(step) resumes the
    exact sample order;
  * **elastic restart**: checkpoints are mesh-agnostic (host-side arrays +
    PartitionSpecs re-derived per mesh), so a job that lost nodes restarts on
    a smaller --mesh from the same checkpoint — the paper's partial-restart
    behaviour lifted to whole-job scale;
  * stragglers: the checkpoint writer's movers pull chunks from a shared
    queue (work stealing), and slow chunk writes can be speculatively
    duplicated (core.transfer.speculative_factor).

Usage (CPU example — reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --mesh 2x2 --steps 40 --ckpt-dir /tmp/ck --ckpt-every 10
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager
from repro.configs.registry import ShapeCell, build_model
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step
from repro.optim import adamw


def parse_mesh(spec: str):
    dims = [int(x) for x in spec.split("x")]
    if len(dims) == 2:
        names = ("data", "model")
    elif len(dims) == 3:
        names = ("pod", "data", "model")
    else:
        raise ValueError(spec)
    devices = jax.devices()[: int(np.prod(dims))]
    if len(devices) < int(np.prod(dims)):
        raise RuntimeError(f"mesh {spec} needs {np.prod(dims)} devices, have {len(devices)}")
    return jax.make_mesh(tuple(dims), names, (AxisType.Auto,) * len(names),
                         devices=devices)


def build_training(arch: str, mesh, *, smoke: bool, seq_len: int,
                   global_batch: int, lr: float, microbatches: int = 1,
                   sync_mode: str = "auto"):
    """(model, optimizer config, jitted train step) for ``arch`` on ``mesh``."""
    model = build_model(arch, mesh, smoke=smoke)
    cell = ShapeCell("custom", seq_len, global_batch, "train")
    ocfg = adamw.AdamWConfig(lr=lr, warmup_steps=10)
    bundle = build_train_step(model, mesh, ocfg, cell=cell,
                              microbatches=microbatches, sync_mode=sync_mode)
    step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings)
    return model, ocfg, step_fn


def init_state(mesh, model, ocfg, seed: int):
    """Fresh (params, AdamW state), created sharded on ``mesh``."""
    pspecs = model.param_specs(mesh)
    params = jax.jit(
        lambda: model.init_params(seed),
        out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
    )()
    return params, adamw.init(params, ocfg)


def restore_into(mesh, model, ocfg, mgr: CheckpointManager):
    """Mesh-agnostic restore: host arrays -> shardings of THIS mesh."""
    tree, step = mgr.restore()
    pspecs = model.param_specs(mesh)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree["params"], pspecs)
    m = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree["opt"]["m"], pspecs)
    v = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree["opt"]["v"], pspecs)
    opt = adamw.OptState(step=jnp.asarray(tree["opt"]["step"]), m=m, v=v)
    return params, opt, step


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--sync-mode", default="auto", choices=["auto", "chunked"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    mesh = parse_mesh(args.mesh)
    model, ocfg, step_fn = build_training(
        args.arch, mesh, smoke=args.smoke, seq_len=args.seq_len,
        global_batch=args.global_batch, lr=args.lr,
        microbatches=args.microbatches, sync_mode=args.sync_mode)
    cfg = model.cfg
    with mesh:
        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if mgr is not None and mgr.latest_step() is not None:
            params, opt, start = restore_into(mesh, model, ocfg, mgr)
            print(f"[restore] resumed from step {start} ({mgr.root})")
        else:
            params, opt = init_state(mesh, model, ocfg, args.seed)

        data = TokenPipeline(
            DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                       global_batch=args.global_batch, seed=args.seed),
            mesh, start_step=start)

        losses = []
        t0 = time.perf_counter()
        for step in range(start, args.steps):
            batch = next(data)
            if cfg.family == "encdec":
                batch["audio_embed"] = jnp.zeros(
                    (args.global_batch, cfg.enc_positions, cfg.d_model), cfg.dtype)
            if cfg.family == "vlm":
                batch["vis_embed"] = jnp.zeros(
                    (args.global_batch, cfg.n_vis_tokens, cfg.d_model), cfg.dtype)
            params, opt, stats = step_fn(params, opt, batch)
            loss = float(stats["loss"])
            losses.append(loss)
            if args.log_every and (step + 1) % args.log_every == 0:
                dt = (time.perf_counter() - t0) / max(1, len(losses))
                print(f"step {step+1:5d}  loss {loss:8.4f}  "
                      f"gnorm {float(stats['grad_norm']):8.3f}  {dt*1e3:6.0f} ms/step",
                      flush=True)
            if mgr is not None and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                rep = mgr.save(step + 1, {"params": params,
                                          "opt": {"step": opt.step, "m": opt.m, "v": opt.v}})
                print(f"[ckpt] step {step+1}: {rep.total_bytes/1e6:.1f} MB "
                      f"in {rep.seconds:.2f}s (resumed_chunks={rep.resumed_chunks})",
                      flush=True)
        data.close()
    return {"losses": losses, "final_loss": losses[-1] if losses else None}


if __name__ == "__main__":
    enable_compile_cache()
    out = main()
    print(f"final loss: {out['final_loss']:.4f}")
