"""Mixture-of-Experts LM with static-capacity all-to-all expert parallelism.

Token-choice top-k routing; dispatch/combine are the GShard/Switch-style
static-shape all-to-alls, executed inside a shard_map that is manual over the
whole mesh for the MoE block only (attention stays GSPMD-auto):

  1. each model column takes a 1/tp slice of the data-shard's tokens,
  2. routes them into a (tp, E_loc, C, D) send buffer (capacity-dropped,
     rank-in-bucket via one-hot cumsum),
  3. all-to-all over the model axis delivers each column its experts' tokens,
  4. batched expert FFN (E_loc experts per column),
  5. reverse all-to-all + weighted combine, then all-gather restores the
     model-replicated activation layout.

Expert placement generalizes over the fixed 16-column model axis:
  * E >= tp (qwen3: 128/16): E_loc = E/tp experts per column, full FFN width.
  * E <  tp (grok-1: 8/16):  SPLIT = tp/E columns per expert, each holding an
    F/SPLIT slice; tokens fan out to all SPLIT slices and the slices' partial
    outputs are summed in combine — tensor parallelism *inside* expert
    parallelism, so the 16-wide axis is always fully used.

Weights are stored pre-sliced as (tp, E_loc, D, F/SPLIT) so a per-column slice
is a plain PartitionSpec('model', ...) — total element count = E*D*F exactly.

The all-to-alls are the model-axis analogue of the paper's chunked transfers:
they are the single largest routed data movement in the framework, and the
hillclimb chunks them (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import common as cm
from repro.models.common import ModelConfig
from repro.models.transformer import DenseLM
from repro.distributed.mesh import MODEL, POD, DATA


def expert_layout(cfg: ModelConfig, tp: int) -> tuple[int, int, int]:
    """(E_loc, SPLIT, C_factor-less layout) for a model axis of size tp."""
    E = cfg.n_experts
    if E >= tp:
        assert E % tp == 0, (E, tp)
        return E // tp, 1, tp
    assert tp % E == 0, (E, tp)
    return 1, tp // E, E


def capacity(t_sub: int, cfg: ModelConfig, tp: int, cf: float = 2.0) -> int:
    """Per-(dest-column, local-expert) receive capacity from one sender."""
    e_loc, split, _ = expert_layout(cfg, tp)
    per_bucket = t_sub * cfg.top_k * split / (tp * e_loc)
    return max(4, int(math.ceil(per_bucket * cf)))


def _moe_local(x_my, wr, wg, wi, wo, *, cfg: ModelConfig, tp: int,
               axis_name: str | None, cf: float):
    """MoE over this column's token slice. x_my: (T_sub, D).

    wg/wi/wo: (E_loc, D, Fs) / (E_loc, D, Fs) / (E_loc, Fs, D) local slices.
    Returns (T_sub, D).
    """
    T_sub, D = x_my.shape
    E = cfg.n_experts
    k = cfg.top_k
    e_loc, split, _ = expert_layout(cfg, tp)
    C = capacity(T_sub, cfg, tp, cf)

    # ---- routing (f32 for stability)
    logits = (x_my.astype(jnp.float32) @ wr.astype(jnp.float32))      # (T_sub, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                            # (T_sub, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- bucket ranks: bucket = (expert-group g, local expert e_loc)
    flat_e = top_e.reshape(-1)                                        # (T_sub*k,)
    g = flat_e // e_loc                                               # column group
    el = flat_e % e_loc
    bucket = g * e_loc + el                                           # (T_sub*k,) in [0, E)
    onehot = jax.nn.one_hot(bucket, E, dtype=jnp.int32)               # (T*k, E)
    rank = jnp.cumsum(onehot, axis=0) * onehot                        # 1-indexed
    slot = jnp.sum(rank, axis=1) - 1                                  # (T*k,)
    keep = slot < C
    slot_c = jnp.where(keep, slot, C)                                 # C => dropped

    tok_idx = jnp.repeat(jnp.arange(T_sub), k)

    # ---- scatter into send buffer (tp, E_loc, C, D); h-splits duplicate rows
    send = jnp.zeros((tp, e_loc, C, D), cfg.dtype)
    vals = x_my[tok_idx].astype(cfg.dtype)
    for h in range(split):
        dest = g * split + h
        send = send.at[dest, el, slot_c].add(vals, mode="drop")

    # ---- a2a to expert owners
    if axis_name is not None and tp > 1:
        recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
    else:
        recv = send                                                    # tp == 1

    # ---- expert FFN (E_loc experts, rows = tp*C each)
    xe = recv.transpose(1, 0, 2, 3).reshape(e_loc, tp * C, D)
    hg = cm.act_fn(cfg.act)(jnp.einsum("etd,edf->etf", xe, wg))
    hi = jnp.einsum("etd,edf->etf", xe, wi)
    out = jnp.einsum("etf,efd->etd", hg * hi, wo)                      # (E_loc, tp*C, D)
    out = out.reshape(e_loc, tp, C, D).transpose(1, 0, 2, 3)           # (tp, E_loc, C, D)

    # ---- return trip + combine
    if axis_name is not None and tp > 1:
        back = jax.lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0)
    else:
        back = out
    # gather per (token, choice): sum F-splits, weight by router prob
    y = jnp.zeros((T_sub, D), jnp.float32)
    flat_back = back.reshape(tp * e_loc * C, D)
    for h in range(split):
        dest = g * split + h
        lin = (dest * e_loc + el) * C + jnp.where(keep, slot, tp * e_loc * C)
        picked = jnp.take(flat_back, jnp.clip(lin, 0, flat_back.shape[0] - 1), axis=0)
        picked = jnp.where(keep[:, None], picked.astype(jnp.float32), 0.0)
        y = y.at[tok_idx].add(picked * top_p.reshape(-1)[:, None])
    return y.astype(cfg.dtype)


class MoELM(DenseLM):
    """DenseLM attention + EP MoE FFN."""

    def __init__(self, cfg: ModelConfig, mesh: Mesh | None = None, *, cf: float = 2.0):
        super().__init__(cfg, mesh)
        self.cf = cf
        self.tp = mesh.shape[MODEL] if (mesh is not None and MODEL in mesh.axis_names) else 1

    # -- params --------------------------------------------------------------
    def init_params(self, seed: int = 0) -> Any:
        params = super().init_params(seed)
        cfg = self.cfg
        ini = cm.Initializer(seed + 1, cfg.dtype)
        nb, D, F, E = self.n_blocks, cfg.d_model, cfg.d_ff, cfg.n_experts
        e_loc, split, _ = expert_layout(cfg, self.tp)
        fs = F // split
        for i in range(len(self.pattern)):
            lp = params["blocks"][str(i)]
            for key in ("wi", "wg", "wmo"):
                del lp[key]
            lp["router"] = ini(f"b{i}.router", (nb, D, E), scale=1.0 / math.sqrt(D))
            lp["we_g"] = ini(f"b{i}.we_g", (nb, self.tp, e_loc, D, fs))
            lp["we_i"] = ini(f"b{i}.we_i", (nb, self.tp, e_loc, D, fs))
            lp["we_o"] = ini(f"b{i}.we_o", (nb, self.tp, e_loc, fs, D),
                             scale=1.0 / math.sqrt(F))
        return params

    def param_specs(self, mesh: Mesh) -> Any:
        specs = super().param_specs(mesh)
        d_dat = cm.shardable(self.cfg.d_model, DATA, mesh)
        for i in range(len(self.pattern)):
            lp = specs["blocks"][str(i)]
            for key in ("wi", "wg", "wmo"):
                del lp[key]
            lp["router"] = P(None, d_dat, None)
            lp["we_g"] = P(None, MODEL, None, d_dat, None)
            lp["we_i"] = P(None, MODEL, None, d_dat, None)
            lp["we_o"] = P(None, MODEL, None, None, d_dat)
        return specs

    # -- the MoE FFN replaces the dense MLP ----------------------------------
    def _mlp(self, x, lp):
        cfg = self.cfg
        B, S, D = x.shape
        h = cm.rms_norm(x, lp["ln2"])
        tp = self.tp
        # Fast path: with the residual already sequence-sharded over MODEL
        # (Megatron-SP), each column's seq shard IS its token slice — no
        # slice/all-gather bracket around the dispatch.
        seq_sharded = self.mesh is not None and self._seq(S) is not None

        def block(h_loc, wr, wg, wi, wo):
            Bl, Sl, _ = h_loc.shape
            t_loc = Bl * Sl
            flat = h_loc.reshape(t_loc, D)
            if tp > 1 and seq_sharded:
                y = _moe_local(flat, wr, wg[0], wi[0], wo[0], cfg=cfg, tp=tp,
                               axis_name=MODEL, cf=self.cf)
            elif tp > 1:
                col = jax.lax.axis_index(MODEL)
                pad = (-t_loc) % tp          # decode batches can be < tp
                if pad:
                    flat = jnp.pad(flat, ((0, pad), (0, 0)))
                sliced = flat.reshape(-1, tp, D)
                x_my = jax.lax.dynamic_slice_in_dim(sliced, col, 1, axis=1)[:, 0]
                y_my = _moe_local(x_my, wr, wg[0], wi[0], wo[0], cfg=cfg, tp=tp,
                                  axis_name=MODEL, cf=self.cf)
                g = jax.lax.all_gather(y_my, MODEL, axis=0)           # (tp, T_sub, D)
                y = g.transpose(1, 0, 2).reshape(-1, D)[:t_loc]
            else:
                y = _moe_local(flat, wr, wg[0], wi[0], wo[0], cfg=cfg, tp=1,
                               axis_name=None, cf=self.cf)
            return y.reshape(Bl, Sl, D)

        if self.mesh is not None and self.mesh.size > 1:
            b_axes = self._batch()
            manual = {a for a in (POD, DATA, MODEL) if a in self.mesh.axis_names}
            if self.pod_manual:
                manual.discard(POD)   # already manual in the enclosing region
            seq_ax = MODEL if seq_sharded else None
            y = jax.shard_map(
                block, mesh=self.mesh,
                in_specs=(P(b_axes, seq_ax, None), P(None, None),
                          P(MODEL, None, None, None), P(MODEL, None, None, None),
                          P(MODEL, None, None, None)),
                out_specs=P(b_axes, seq_ax, None),
                axis_names=manual, check_vma=False,
            )(h, lp["router"], lp["we_g"], lp["we_i"], lp["we_o"])
        else:
            y = block(h, lp["router"], lp["we_g"], lp["we_i"], lp["we_o"])
        return self._res(x + y)
