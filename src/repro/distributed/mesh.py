"""Mesh axis conventions and sharding-rule helpers.

Axis names (fixed across the framework):
  pod    — cross-pod data parallelism over DCN (the slow, WAN-like hop where
           the paper's chunking matters most)
  data   — intra-pod FSDP/DP (+ sequence/context sharding of activations)
  model  — tensor parallelism (heads / ffn / vocab / experts)

Logical dimension names used by model definitions are mapped here to mesh
axes; a model never hardcodes a mesh axis.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

POD, DATA, MODEL = "pod", "data", "model"


# logical dim -> mesh axis (None = replicate)
_RULES: dict[str, str | None] = {
    "batch": DATA,         # + pod, applied by batch_spec()
    "seq": None,           # sequence sharding is opt-in (context parallelism)
    "embed": None,         # activations' feature dim stays unsharded
    "vocab": MODEL,
    "heads": MODEL,
    "kv_heads": MODEL,
    "head_dim": None,
    "ffn": MODEL,
    "experts": MODEL,
    "expert_ffn": None,
    "fsdp": DATA,          # parameter dim chosen for ZeRO-3 sharding
    "state": None,         # SSM / RG-LRU recurrent state dim
    "conv": None,
}


def spec(*logical: str | None) -> P:
    """PartitionSpec from logical dim names, e.g. spec('fsdp','ffn')."""
    axes = []
    for name in logical:
        if name is None:
            axes.append(None)
        else:
            axes.append(_RULES.get(name, None) if isinstance(name, str) else name)
    return P(*axes)


def batch_spec(mesh: Mesh, *, seq_sharded: bool = False) -> P:
    """(batch, seq, ...) activation spec: batch over pod+data when present."""
    batch_axes = tuple(a for a in (POD, DATA) if a in mesh.axis_names)
    b = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    return P(b, MODEL if seq_sharded else None)


def shard(mesh: Mesh, x, pspec: P):
    return jax.device_put(x, NamedSharding(mesh, pspec))


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Resolved parallelism plan for a given mesh."""

    mesh: Mesh

    @property
    def n_pods(self) -> int:
        return axis_size(self.mesh, POD)

    @property
    def dp(self) -> int:
        return axis_size(self.mesh, DATA)

    @property
    def tp(self) -> int:
        return axis_size(self.mesh, MODEL)

    @property
    def n_devices(self) -> int:
        return self.mesh.size

    def describe(self) -> str:
        return (
            f"mesh{tuple(self.mesh.shape.values())} axes={self.mesh.axis_names} "
            f"pods={self.n_pods} dp={self.dp} tp={self.tp} devices={self.n_devices}"
        )
