"""Step builders: train / prefill / decode, with shardings and input specs.

This is the seam where the paper's feature plugs into training:

  * sync_mode="auto"    — baseline: one pjit; XLA emits monolithic cross-pod
                          all-reduces (the un-chunked Globus of the paper).
  * sync_mode="chunked" — the whole step runs per-pod (shard_map manual over
                          POD; data/model stay GSPMD) and gradients cross pods
                          through ``distributed.chunked`` rings in planner-
                          sized chunks.

Microbatching (grad accumulation over a scan) bounds activation memory the
same way the paper's chunking bounds mover buffer footprints; it is the knob
that fits yi-34b's 1M-token steps on 16 GB chips.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import SHAPES, ShapeCell, build_model
from repro.distributed.fsdp import cross_pod_mean
from repro.distributed.mesh import DATA, MODEL, POD, axis_size
from repro.models import common as cm
from repro.optim import adamw


@dataclasses.dataclass
class StepBundle:
    """Everything needed to lower one (arch x shape) cell."""

    fn: Callable
    in_shardings: Any
    out_shardings: Any
    in_shapes: Any            # ShapeDtypeStructs matching fn's positional args
    model: Any
    kind: str


def _sharded(mesh: Mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_specs(model, cell: ShapeCell, mesh: Mesh) -> tuple[dict, dict]:
    """(ShapeDtypeStructs, PartitionSpecs) for the train/prefill batch."""
    cfg = model.cfg
    B = cell.global_batch
    S = cell.seq_len
    b = cm.batch_axes(mesh)
    shapes: dict[str, jax.ShapeDtypeStruct] = {}
    specs: dict[str, P] = {}
    tok_len = S + 1 if cell.kind == "train" else S
    if cfg.family == "vlm":
        tok_len = max(2, tok_len - cfg.n_vis_tokens)
        shapes["vis_embed"] = jax.ShapeDtypeStruct((B, cfg.n_vis_tokens, cfg.d_model), cfg.dtype)
        specs["vis_embed"] = P(b, None, None)
    if cfg.family == "encdec":
        shapes["audio_embed"] = jax.ShapeDtypeStruct((B, cfg.enc_positions, cfg.d_model), cfg.dtype)
        specs["audio_embed"] = P(b, None, None)
    shapes["tokens"] = jax.ShapeDtypeStruct((B, tok_len), jnp.int32)
    specs["tokens"] = P(b, None)
    return shapes, specs


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def build_train_step(
    model,
    mesh: Mesh,
    ocfg: adamw.AdamWConfig | None = None,
    *,
    cell: ShapeCell | None = None,
    microbatches: int = 1,
    sync_mode: str = "auto",
    n_chunks: int = 4,
) -> StepBundle:
    ocfg = ocfg or adamw.AdamWConfig(
        state_dtype=jnp.bfloat16 if model.cfg.param_count() > 1e11 else jnp.float32
    )
    cell = cell or SHAPES["train_4k"]
    n_pods = axis_size(mesh, POD)
    chunked = sync_mode in ("chunked", "chunked_bf16") and n_pods > 1
    compress = sync_mode == "chunked_bf16"
    model.pod_manual = chunked

    p_shapes = jax.eval_shape(lambda: model.init_params(0))
    o_shapes = jax.eval_shape(lambda: adamw.init(p_shapes, ocfg))
    pspecs = model.param_specs(mesh)
    ospecs = adamw.state_specs(pspecs)
    b_shapes, b_specs = _batch_specs(model, cell, mesh)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def grads_of(params, batch):
        if microbatches == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        mb = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]),
            batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, microbatch):
            acc_l, acc_g = carry
            l, g = jax.value_and_grad(loss_fn)(params, microbatch)
            acc_g = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc_g, g)
            return (acc_l + l, acc_g), None

        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), g0), mb)
        inv = 1.0 / microbatches
        return loss * inv, jax.tree.map(lambda g: (g * inv).astype(model.cfg.dtype), grads)

    def step_core(params, opt, batch):
        loss, grads = grads_of(params, batch)
        if chunked:
            if compress:
                # beyond-paper: 'gradient compression' for the DCN hop —
                # cast to bf16 for the wire, accumulate mean back in f32
                dt0 = jax.tree.map(lambda g: g.dtype, grads)
                grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
                grads = cross_pod_mean(grads, n_pods, n_chunks=n_chunks)
                grads = jax.tree.map(lambda g, d: g.astype(d), grads, dt0)
            else:
                grads = cross_pod_mean(grads, n_pods, n_chunks=n_chunks)
            loss = jax.lax.pmean(loss, POD)
        params, opt, stats = adamw.apply(params, grads, opt, ocfg)
        return params, opt, {"loss": loss, **stats}

    if chunked:
        # shard_map specs may reference only the manual axis (pod): params and
        # optimizer state are pod-replicated (P()); batches split on dim 0;
        # data/model sharding rides through as GSPMD-auto from jit shardings.
        rep = lambda tree: jax.tree.map(lambda _: P(), tree,               # noqa: E731
                                        is_leaf=lambda x: isinstance(x, P))
        pod_batch = {k: P(POD, *([None] * (len(v.shape) - 1)))
                     for k, v in b_shapes.items()}
        scalar = P()
        step = jax.shard_map(
            step_core, mesh=mesh,
            in_specs=(rep(pspecs), rep(ospecs), pod_batch),
            out_specs=(rep(pspecs), rep(ospecs),
                       {"loss": scalar, "grad_norm": scalar, "lr": scalar}),
            axis_names={POD}, check_vma=False,
        )
    else:
        step = step_core

    scalar_sh = NamedSharding(mesh, P())
    in_sh = (_sharded(mesh, pspecs), _sharded(mesh, ospecs), _sharded(mesh, b_specs))
    out_sh = (_sharded(mesh, pspecs), _sharded(mesh, ospecs),
              {"loss": scalar_sh, "grad_norm": scalar_sh, "lr": scalar_sh})
    return StepBundle(step, in_sh, out_sh, (p_shapes, o_shapes, b_shapes), model, "train")


# ---------------------------------------------------------------------------
# prefill (forward producing logits — the compute profile of ingest)
# ---------------------------------------------------------------------------
def build_prefill_step(model, mesh: Mesh, *, cell: ShapeCell) -> StepBundle:
    cfg = model.cfg
    p_shapes = jax.eval_shape(lambda: model.init_params(0))
    pspecs = model.param_specs(mesh)
    b_shapes, b_specs = _batch_specs(model, cell, mesh)
    b = cm.batch_axes(mesh)

    if cfg.family == "encdec":
        def prefill(params, batch):
            enc = model.encode(params, batch["audio_embed"])
            h = model.dec_hidden(params, batch["tokens"], enc)
            return jnp.einsum("bsd,vd->bsv", h[:, -1:], params["embed"].astype(cfg.dtype))
    elif cfg.family == "vlm":
        def prefill(params, batch):
            h = model.hidden_mm(params, batch["tokens"], batch["vis_embed"])
            return jnp.einsum("bsd,dv->bsv", h[:, -1:], model._out_w(params))
    else:
        def prefill(params, batch):
            h = model.hidden(params, batch["tokens"])
            w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
            return jnp.einsum("bsd,dv->bsv", h[:, -1:], w.astype(cfg.dtype))

    in_sh = (_sharded(mesh, pspecs), _sharded(mesh, b_specs))
    out_sh = NamedSharding(mesh, P(b, None, None))
    return StepBundle(prefill, in_sh, out_sh, (p_shapes, b_shapes), model, "prefill")


# ---------------------------------------------------------------------------
# decode (one serve step: next-token + cache update)
# ---------------------------------------------------------------------------
def build_serve_step(model, mesh: Mesh, *, cell: ShapeCell,
                     weight_stationary: bool = False) -> StepBundle:
    cfg = model.cfg
    B, T = cell.global_batch, cell.seq_len
    p_shapes = jax.eval_shape(lambda: model.init_params(0))
    try:
        pspecs = model.param_specs(mesh, serve=weight_stationary)
    except TypeError:
        pspecs = model.param_specs(mesh)
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, T))
    cache_specs = model.cache_specs(mesh, B, T)
    b = cm.batch_axes(mesh) if B % _bdiv(mesh) == 0 else None

    def serve_step(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache, pos + 1

    tok_sh = NamedSharding(mesh, P(b, None))
    pos_sh = NamedSharding(mesh, P(b))
    in_sh = (_sharded(mesh, pspecs), _sharded(mesh, cache_specs), tok_sh, pos_sh)
    out_sh = (tok_sh, _sharded(mesh, cache_specs), pos_sh)
    shapes = (p_shapes, cache_shapes,
              jax.ShapeDtypeStruct((B, 1), jnp.int32), jax.ShapeDtypeStruct((B,), jnp.int32))
    return StepBundle(serve_step, in_sh, out_sh, shapes, model, "decode")


def _bdiv(mesh: Mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in (POD, DATA) if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# cell entry point
# ---------------------------------------------------------------------------
# Grad-accumulation defaults that fit each arch's train_4k step in 16 GB/chip
# (determined from dry-run memory_analysis; see EXPERIMENTS.md §Dry-run).
DEFAULT_MICROBATCHES = {
    "yi-34b": 4, "grok-1-314b": 8, "mistral-nemo-12b": 2, "whisper-large-v3": 2,
    "mamba2-370m": 2, "recurrentgemma-2b": 4,
}


def build_cell(arch: str, shape: str, mesh: Mesh, *, sync_mode: str = "auto",
               microbatches: int = 0, layers_override: int | None = None,
               cfg_overrides: dict | None = None,
               weight_stationary: bool = False) -> StepBundle:
    cell = SHAPES[shape]
    model = build_model(arch, mesh, shape=shape)
    if cfg_overrides:
        model = _rebuild(model, mesh,
                         dataclasses.replace(model.cfg, **cfg_overrides), shape)
    if layers_override is not None:
        model = _with_layers(arch, model, mesh, layers_override, shape)
    if cell.kind == "train":
        if microbatches == 0:
            microbatches = DEFAULT_MICROBATCHES.get(arch, 1)
        return build_train_step(model, mesh, cell=cell, sync_mode=sync_mode,
                                microbatches=microbatches)
    if cell.kind == "prefill":
        return build_prefill_step(model, mesh, cell=cell)
    return build_serve_step(model, mesh, cell=cell,
                            weight_stationary=weight_stationary)


def _rebuild(model, mesh, cfg, shape):
    kw = {}
    if cfg.family == "encdec":
        kw["max_target"] = model.max_target
    if cfg.family == "moe":
        kw["cf"] = model.cf
    return type(model)(cfg, mesh, **kw)


def _with_layers(arch: str, model, mesh: Mesh, n_layers: int, shape: str):
    """Same arch with a reduced layer count (scan-body FLOPs extrapolation)."""
    cfg = dataclasses.replace(model.cfg, n_layers=n_layers)
    if cfg.family == "encdec":
        cfg = dataclasses.replace(cfg, n_enc_layers=n_layers)
    return _rebuild(model, mesh, cfg, shape)
