"""Sharding rules: divisibility safety, priorities, per-arch coverage.
Uses AbstractMesh so the production 16x16 shapes are testable on 1 CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import sharding as sh
from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.models import model as M

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_spec_tree(tree_abs, specs, mesh):
    flat_a = jax.tree_util.tree_leaves(tree_abs)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_a) == len(flat_s)
    for leaf, spec in zip(flat_a, flat_s):
        used = set()
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (leaf.shape, spec)
            for a in axes:
                assert a not in used, f"axis {a} reused in {spec}"
                used.add(a)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["1pod", "2pod"])
def test_param_specs_valid(arch, mesh):
    cfg = get_config(arch)
    params = M.abstract_params(cfg)
    specs = sh.param_specs(params, mesh)
    _check_spec_tree(params, specs, mesh)


@pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-3b", "zamba2-1.2b",
                                  "olmoe-1b-7b", "whisper-tiny"])
def test_cache_specs_valid(arch):
    cfg = get_config(arch)
    for shp in ("decode_32k", "long_500k"):
        s = INPUT_SHAPES[shp]
        cache = M.abstract_cache(cfg, s.global_batch, min(s.seq_len, 32768))
        specs = sh.cache_specs(cache, MESH, global_batch=s.global_batch)
        _check_spec_tree(cache, specs, MESH)


def test_param_specs_use_model_axis():
    """Tensor parallelism must actually engage for the big dims."""
    cfg = get_config("yi-6b")
    specs = sh.param_specs(M.abstract_params(cfg), MESH)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert any("model" in str(s) for s in flat)
    # ffn w_in: (L, d, ff) -> (None, data, model)
    assert specs["blocks"]["ffn"]["w_in"] == P(None, "data", "model")
    assert specs["embed"] == P("model", "data")


def test_kv_heads_priority_fallback():
    """kv-heads too small to split 16-way -> the sequence dim claims
    "model" instead (the cache must still shard)."""
    cfg = get_config("yi-6b")          # 4 kv heads, 16-way model axis
    cache = M.abstract_cache(cfg, 128, 1024)   # k: (L, B, S, Hkv, hd)
    specs = sh.cache_specs(cache, MESH, global_batch=128)
    assert tuple(specs["k"])[:3] == (None, "data", "model")  # S gets model
    cfg2 = get_config("olmoe-1b-7b")   # 16 kv heads divide 16
    cache2 = M.abstract_cache(cfg2, 128, 1024)
    specs2 = sh.cache_specs(cache2, MESH, global_batch=128)
    assert tuple(specs2["k"])[:4] == (None, "data", None, "model")


def test_batch_spec_fallbacks():
    b = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    assert sh.batch_spec(b, MESH_MP, global_batch=256)["tokens"] == \
        P(("pod", "data"), None)
    # batch=1 cannot shard
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    s1 = sh.batch_spec(b1, MESH_MP, global_batch=1)["tokens"]
    assert all(e is None for e in tuple(s1))
    # batch=16 divides data but not pod*data
    b16 = {"tokens": jax.ShapeDtypeStruct((16, 128), jnp.int32)}
    assert sh.batch_spec(b16, MESH_MP, global_batch=16)["tokens"] == \
        P("data", None)


def test_opt_state_mirrors_params():
    cfg = get_config("deepseek-7b")
    params = M.abstract_params(cfg)
    o = sh.opt_state_specs(params, MESH)
    assert o["m"]["blocks"]["ffn"]["w_out"] == \
        sh.param_specs(params, MESH)["blocks"]["ffn"]["w_out"]
    assert o["step"] == P()


def test_host_mesh_lowering_end_to_end():
    """The same train_step + shardings lower on a real 1-device mesh."""
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw
    from repro.train import loop as TL
    cfg = get_smoke_config("yi-6b")
    mesh = make_host_mesh()
    params = M.abstract_params(cfg, jnp.float32)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
    p_spec = sh.param_specs(params, mesh)
    step = TL.make_train_step(cfg, adamw.AdamWConfig())
    NS = jax.sharding.NamedSharding
    opt_abs = {"m": params, "v": params,
               "step": jax.ShapeDtypeStruct((), jnp.int32)}
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=(
            jax.tree.map(lambda s: NS(mesh, s), p_spec),
            {"m": jax.tree.map(lambda s: NS(mesh, s), p_spec),
             "v": jax.tree.map(lambda s: NS(mesh, s), p_spec),
             "step": NS(mesh, P())},
            None)).lower(params, opt_abs, batch)
        lowered.compile()


def test_host_mesh_refuses_more_devices_than_present():
    """A requested mesh is never silently clamped to the devices present."""
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=n + 1, model=1)
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=1, model=2 * n)
    assert dict(make_host_mesh(data=n, model=1).shape) == {
        "data": n, "model": 1}
