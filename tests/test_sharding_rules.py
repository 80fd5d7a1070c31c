"""Sharding-rule unit tests — run against abstract params (no devices needed;
rules must resolve on ShapeDtypeStructs) with a symbolic 16x16 mesh built
from the single real CPU device via AbstractMesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.dist import sharding as SH
from repro.nn import transformer as T

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _abstract(name):
    cfg = ARCHS[name]
    return cfg, jax.eval_shape(lambda k: T.init(k, cfg), jax.random.PRNGKey(0))


def _find(specs, params, substr):
    out = []
    for (p, spec), (_, leaf) in zip(
            jax.tree_util.tree_leaves_with_path(specs,
                                                is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_leaves_with_path(params)):
        ps = SH.path_str(p)
        if substr in ps:
            out.append((ps, spec, leaf.shape))
    return out


def test_dense_tp_and_fsdp_axes():
    cfg, params = _abstract("nemotron-4-340b")
    specs = SH.param_specs(params, MESH)
    wq = _find(specs, params, "wq/kernel")[0]
    # (L, d, H, hd): fsdp on d, heads on model
    assert wq[1] == P(None, "data", "model", None), wq
    wo = _find(specs, params, "mlp/wo/kernel")[0]
    assert wo[1] == P(None, "model", "data"), wo
    emb = _find(specs, params, "embed/table")[0]
    assert emb[1] == P("model", None), emb


def test_non_divisible_heads_fall_back_to_replication():
    cfg, params = _abstract("gemma2-2b")      # 8 q-heads on 16-way model axis
    specs = SH.param_specs(params, MESH)
    wq = _find(specs, params, "wq/kernel")[0]
    assert wq[1][2] is None, "8 heads must not shard on a 16-way axis"
    # ffn still TP
    wi = _find(specs, params, "wi_gate/kernel")[0]
    assert wi[1][-1] == "model"


def test_moe_expert_parallel():
    cfg, params = _abstract("deepseek-v2-236b")
    specs = SH.param_specs(params, MESH)
    e = _find(specs, params, "experts/wi_gate")[0]
    # (L, E, d, de): experts on model (160 % 16 == 0)
    assert e[1] == P(None, "model", "data", None), e
    r = _find(specs, params, "router/kernel")[0]
    assert r[1][-1] is None, "router output dim stays replicated"


def test_mamba_tp_on_inner_dim():
    cfg, params = _abstract("falcon-mamba-7b")
    specs = SH.param_specs(params, MESH)
    a = _find(specs, params, "A_log")[0]
    assert a[1] == P(None, "model", None), a
    o = _find(specs, params, "out_proj/kernel")[0]
    assert o[1] == P(None, "model", "data"), o


def test_norms_replicated():
    cfg, params = _abstract("qwen3-0.6b")
    specs = SH.param_specs(params, MESH)
    for ps, spec, shape in _find(specs, params, "norm"):
        assert spec == P(), (ps, spec)


def test_cache_specs_prefer_kv_then_seq():
    cfg = ARCHS["gemma-7b"]                  # kv=16 -> kv-sharded
    state = jax.eval_shape(
        lambda: T.init_decode_state(cfg, 128, 1024, jnp.bfloat16))
    specs = SH.cache_specs(state, MESH)
    ks = _find(specs, state, "/k")[0]
    # (repeats, B, S, KV, hd): batch on data, KV on model
    assert ks[1] == P(None, ("data",), None, "model", None), ks

    cfg2 = ARCHS["qwen3-0.6b"]               # kv=8 -> seq-sharded
    state2 = jax.eval_shape(
        lambda: T.init_decode_state(cfg2, 128, 1024, jnp.bfloat16))
    specs2 = SH.cache_specs(state2, MESH)
    ks2 = _find(specs2, state2, "/k")[0]
    assert ks2[1] == P(None, ("data",), "model", None, None), ks2


def test_cache_specs_batch_replicated_when_not_divisible():
    cfg = ARCHS["falcon-mamba-7b"]
    state = jax.eval_shape(
        lambda: T.init_decode_state(cfg, 1, 64, jnp.bfloat16))
    specs = SH.cache_specs(state, MESH, shard_batch=False)
    h = _find(specs, state, "/h")[0]
    assert h[1][1] is None                    # batch replicated
    assert "model" in h[1]                    # d_inner sharded


def test_pod_axis_in_batch():
    assert SH.batch_axes(MESH3) == ("pod", "data")
    spec = SH.batch_spec(MESH3, 2)
    assert spec == P(("pod", "data"), None)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_every_param_gets_valid_spec(name):
    """Every leaf resolves; every sharded dim is divisible by its axis."""
    cfg, params = _abstract(name)
    specs = SH.param_specs(params, MESH)
    flat_s = jax.tree_util.tree_leaves(specs,
                                       is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree_util.tree_leaves(params)
    assert len(flat_s) == len(flat_p)
    sizes = dict(MESH.shape)
    for spec, leaf in zip(flat_s, flat_p):
        for d, ax in enumerate(tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = int(np.prod([sizes[a] for a in axes]))
            assert leaf.shape[d] % total == 0, (spec, leaf.shape, d)
