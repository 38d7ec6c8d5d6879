"""Compile-only checks of the served device shapes for a TPU v5e.

Nothing runs here: each test lowers a kernel (or collective) for a v5e:2x2
topology that is described, not attached, and compiles it with the chip's
compiler, which refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, VMEM overuse). The topology is described inside a fixture,
never at import time, so every test worker collects the same tests and only
the worker that runs this file loads the TPU compiler.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.distributed import chunked as C
from repro.kernels import checksum as ck
from repro.kernels import ops

MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # entries compiled for a described chip cannot be read back without one
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_text(fn, *args, **kw) -> str:
    txt = jax.jit(fn, **kw).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt       # a compiled kernel, not the interpreter
    return txt


SERVED = [(ops.batch_rows(b), b) for b in ops.BUCKETS]


@pytest.mark.parametrize("k,bucket", SERVED + [(32, 8 * MiB)],
                         ids=[f"{k}x{b // 1024}KiB" for k, b in SERVED] + ["32x8MiB"])
def test_checksum_many_words_compiles(one_chip, k, bucket):
    words = jax.ShapeDtypeStruct((k, bucket // 4), jnp.int32, sharding=one_chip)
    _kernel_text(functools.partial(ck.checksum_many_words, interpret=False), words)


def test_checksum_words_compiles_at_256mib(one_chip):
    words = jax.ShapeDtypeStruct((256 * MiB // 4,), jnp.int32, sharding=one_chip)
    _kernel_text(functools.partial(ck.checksum_words, interpret=False), words)


def test_checksum_copy_words_compiles(one_chip):
    words = jax.ShapeDtypeStruct((8 * MiB // 4,), jnp.int32, sharding=one_chip)
    _kernel_text(functools.partial(ck.checksum_copy_words, interpret=False), words)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_fingerprint_array_compiles_on_leaves(one_chip, dtype):
    # mamba2-370m's stacked input projection (layers, d_model, in_proj),
    # four of its 48 layers: compile time grows with the leaf
    leaf = jax.ShapeDtypeStruct((4, 1024, 4384), dtype, sharding=one_chip)
    txt = ops.fingerprint_array.lower(leaf, interpret=False).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("fn,out_spec", [
    (C.chunked_all_gather, P()),
    (C.chunked_reduce_scatter, P("x")),
    (C.chunked_all_reduce, P("x")),
], ids=["all_gather", "reduce_scatter", "all_reduce"])
def test_chunked_collectives_compile_on_four_chips(topo, fn, out_spec):
    mesh = jax.sharding.Mesh(topo.devices[:4], ("x",))
    x = jax.ShapeDtypeStruct((4 * 64, 1024), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    body = functools.partial(fn, axis_name="x", axis_size=4)
    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                 out_specs=out_spec, check_vma=False))
    assert "collective-permute" in step.lower(x).compile().as_text()
