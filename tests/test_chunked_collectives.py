"""Chunked collectives == monolithic jax.lax collectives (8-device subprocess)."""
import pytest

from conftest import run_multidevice

EQUIV = """
import numpy as np, jax, jax.numpy as jnp, functools
from jax.sharding import PartitionSpec as P
from repro.distributed import chunked as C
from jax.sharding import AxisType

mesh = jax.make_mesh((8,), ("x",), (AxisType.Auto,))
A = 8
sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
rng = np.random.default_rng(3)

x = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32))
for nc in (1, 2, 4):
    f = jax.jit(sm(functools.partial(C.chunked_all_gather, axis_name="x", axis_size=A, n_chunks=nc),
                   in_specs=P("x"), out_specs=P()))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))

y = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32))
want = jax.jit(sm(lambda v: jax.lax.psum_scatter(v, "x", tiled=True), in_specs=P(), out_specs=P("x")))(y)
for nc in (1, 2, 4):
    f = jax.jit(sm(functools.partial(C.chunked_reduce_scatter, axis_name="x", axis_size=A, n_chunks=nc),
                   in_specs=P(), out_specs=P("x")))
    np.testing.assert_allclose(np.asarray(f(y)), np.asarray(want), rtol=1e-6)

z = jnp.asarray(rng.standard_normal((8, 33)).astype(np.float32))
want = jax.jit(sm(lambda v: jax.lax.psum(v, "x"), in_specs=P("x"), out_specs=P("x")))(z)
for nc in (1, 2, 4):
    f = jax.jit(sm(functools.partial(C.chunked_all_reduce, axis_name="x", axis_size=A, n_chunks=nc),
                   in_specs=P("x"), out_specs=P("x")))
    np.testing.assert_allclose(np.asarray(f(z)), np.asarray(want), rtol=1e-5)

xx = jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32))
ww = jnp.asarray(rng.standard_normal((64, 24)).astype(np.float32))
want = xx @ ww
f = jax.jit(sm(functools.partial(C.ag_matmul, axis_name="x", axis_size=A),
               in_specs=(P(), P("x")), out_specs=P()))
np.testing.assert_allclose(np.asarray(f(xx, ww)), np.asarray(want), rtol=1e-4, atol=1e-4)

f = jax.jit(sm(functools.partial(C.matmul_rs, axis_name="x", axis_size=A, n_chunks=2),
               in_specs=(P(None, "x"), P("x")), out_specs=P("x")))
np.testing.assert_allclose(np.asarray(f(xx, ww)), np.asarray(want), rtol=1e-4, atol=1e-4)
print("ALL_EQUIV_OK")
"""


def test_chunked_collectives_equivalence():
    out = run_multidevice(EQUIV, n_devices=8)
    assert "ALL_EQUIV_OK" in out


CROSS_POD = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed.fsdp import cross_pod_mean, manual_pod
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), (AxisType.Auto,) * 2)

def step(g):
    return cross_pod_mean(g, 2, n_chunks=2)

f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                      axis_names={"pod"}, check_vma=False))
x = jnp.arange(32.0).reshape(8, 4)
got = np.asarray(f(x))
want = np.tile(np.asarray(x).reshape(2, 4, 4).mean(0), (2, 1))
np.testing.assert_allclose(got, want, rtol=1e-6)
print("CROSS_POD_OK")
"""


def test_cross_pod_mean():
    out = run_multidevice(CROSS_POD, n_devices=8)
    assert "CROSS_POD_OK" in out


HLO_CHUNKS = """
import jax, jax.numpy as jnp, functools, re
from jax.sharding import PartitionSpec as P
from repro.distributed import chunked as C
from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("x",), (AxisType.Auto,))
x = jnp.zeros((64, 256), jnp.float32)

def count_cp(nc):
    f = jax.jit(jax.shard_map(
        functools.partial(C.chunked_all_gather, axis_name="x", axis_size=8, n_chunks=nc),
        mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False))
    txt = f.lower(x).compile().as_text()
    return len(re.findall(r"collective-permute(?:-start)?\\(", txt))

c1, c4 = count_cp(1), count_cp(4)
assert c4 > c1, (c1, c4)   # chunking must yield finer, more numerous messages
print("HLO_CHUNKING_OK", c1, c4)
"""


def test_chunking_visible_in_hlo():
    out = run_multidevice(HLO_CHUNKS, n_devices=8)
    assert "HLO_CHUNKING_OK" in out


CHUNKED_STEP = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import build_model, ShapeCell
from repro.launch.steps import build_train_step
from jax.sharding import AxisType
from repro.optim import adamw

mesh = jax.make_mesh((2,2,2), ("pod","data","model"), (AxisType.Auto,) * 3)
cell = ShapeCell("t", 32, 8, "train")
ocfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=1)

def run(sync_mode):
    model = build_model("gemma-2b", mesh, smoke=True)
    b = build_train_step(model, mesh, ocfg, cell=cell, sync_mode=sync_mode, microbatches=2)
    with mesh:
        step = jax.jit(b.fn, in_shardings=b.in_shardings, out_shardings=b.out_shardings)
        pspecs = model.param_specs(mesh)
        params = jax.jit(lambda: model.init_params(0),
                         out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs))()
        opt = adamw.init(params, ocfg)
        tok = jax.random.randint(jax.random.PRNGKey(7), (8, 33), 0, model.cfg.vocab)
        tok = jax.device_put(tok, NamedSharding(mesh, P(("pod","data"), None)))
        p2, o2, stats = step(params, opt, {"tokens": tok})
        return float(stats["loss"]), jax.tree.leaves(p2)[0]

l_auto, p_auto = run("auto")
l_chunk, p_chunk = run("chunked")
assert abs(l_auto - l_chunk) < 1e-4, (l_auto, l_chunk)
np.testing.assert_allclose(np.asarray(p_auto, np.float32),
                           np.asarray(p_chunk, np.float32), rtol=2e-3, atol=2e-5)
print("CHUNKED_STEP_EQUIV_OK", l_auto, l_chunk)
"""


def test_chunked_pod_step_matches_auto():
    """Paper-technique train step == monolithic baseline, numerically."""
    out = run_multidevice(CHUNKED_STEP, n_devices=8, timeout=900)
    assert "CHUNKED_STEP_EQUIV_OK" in out


SERVE_SPECS = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import build_model
from jax.sharding import AxisType

mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
m = build_model("yi-34b", mesh, smoke=True)
params = m.init_params(0)
B, T = 4, 16
tok = jax.random.randint(jax.random.PRNGKey(3), (B, 1), 0, m.cfg.vocab)
pos = jnp.zeros((B,), jnp.int32)

outs = {}
for serve in (False, True):
    specs = m.param_specs(mesh, serve=serve)
    p = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)
    cache = m.init_cache(B, T)
    lg, _ = jax.jit(m.decode_step)(p, cache, tok, pos)
    outs[serve] = np.asarray(lg, np.float32)
np.testing.assert_allclose(outs[False], outs[True], rtol=2e-4, atol=2e-4)
print("SERVE_SPECS_EQUIV_OK")
"""


def test_weight_stationary_serving_matches_default():
    """The §Perf cell-3 optimization changes layout, not math."""
    out = run_multidevice(SERVE_SPECS, n_devices=8, timeout=600)
    assert "SERVE_SPECS_EQUIV_OK" in out
