"""Sharding-rules engine against an abstract production mesh."""
import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import get_arch
from repro.distributed.sharding import (ShardingDecisions, param_specs,
                                        spec_for_leaf)
from repro.models.model import build_model

MESH = AbstractMesh((16, 16), ("data", "model"))


def test_attention_weights_2d_sharded():
    spec = spec_for_leaf("blocks/attn/wq/w", (1024, 2048), MESH, False)
    assert spec == P("data", "model")
    spec = spec_for_leaf("blocks/attn/wo/w", (2048, 1024), MESH, False)
    assert spec == P("model", "data")


def test_nondivisible_falls_back_replicated():
    d = ShardingDecisions()
    spec = spec_for_leaf("blocks/attn/wk/w", (1024, 24), MESH, False, d)
    assert spec == P("data", None)     # 24 not divisible by 16
    assert d.fallbacks


def test_embed_vocab_on_model():
    spec = spec_for_leaf("embed/table", (256000, 2048), MESH, False)
    assert spec == P("model", "data")


def test_norm_scales_replicated():
    assert spec_for_leaf("blocks/ln1", (2048,), MESH, False) == P()


def test_moe_experts_on_model():
    spec = spec_for_leaf("blocks/moe/wi", (128, 2048, 768), MESH, False)
    assert spec == P("model", "data", None)


def test_scanned_params_get_leading_none():
    cfg = get_arch("qwen1.5-0.5b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = param_specs(params, MESH)
    wq = specs["blocks"]["attn"]["wq"]["w"]
    assert tuple(wq)[0] is None        # scan group dim unsharded
    assert len(tuple(wq)) == 3
