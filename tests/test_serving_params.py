"""The weights are cast once (``transformer.serving_params``).

Serve holds the leaves its programs would cast to ``cfg.dtype`` in that
dtype from load. The programs are the same code over either tree: a
``.astype`` of a leaf that is already there is nothing, and the same
rounding made once gives the same bf16 operands. So every paged program
over the cast tree must equal, bit for bit, the same program over the
float32 masters; and a leaf a program reads in float32 must stay one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import (
    PagedKVCache, TransformerConfig, init_params, serving_params,
)
from kvedge_tpu.models.serving import PagedGenerationServer

DENSE = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64, dtype="bfloat16",
)
# Dropless by construction (factor x top_k >= experts), so serving and
# training agree and the cache does not warn.
EXPERTS = dataclasses.replace(
    DENSE, n_experts=2, expert_top_k=2, expert_capacity_factor=1.0)
BLOCKS = {"dense": DENSE, "experts": EXPERTS}

CAST = {"embedding", "w_qkv", "w_out", "w_up", "w_down",
        "w_up_experts", "w_down_experts"}


def _same(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and np.array_equal(a, b), (
        f"{what} differs between the float32 masters and the cast tree")


def _programs(cfg, params):
    """Every kind of paged program once over ``params``: a prompt in
    two prefill chunks, a single step and a decode window. Returns
    what each hands back, logits where it has them."""
    cache = PagedKVCache(cfg, slots=2, pages=16, page_size=4)
    prompt = jnp.asarray([5, 9, 2, 7, 1, 3, 8, 4], jnp.int32)
    cache.admit(0, 8)
    out = {"chunk0": cache.prefill_chunk(params, 0, prompt[:4], 0),
           "chunk1": cache.prefill_chunk(params, 0, prompt[4:], 4)}
    active = np.array([True, False])
    tok = jnp.argmax(out["chunk1"]).astype(jnp.int32)
    pending = jnp.stack([tok, jnp.int32(0)])
    out["step"] = cache.step(params, pending, active)
    pending = jnp.argmax(out["step"], axis=-1).astype(jnp.int32)
    out["window"] = cache.harvest_window(
        cache.dispatch_window(params, pending, 4, active))[:4]
    return out


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_cast_tree_is_bit_identical_to_the_masters(block):
    cfg = BLOCKS[block]
    masters = init_params(jax.random.PRNGKey(0), cfg)
    cast = serving_params(masters, cfg)

    # Exactly the leaves every program casts are cast; the router
    # (moe._route reads it in float32) and the norm gains are not.
    assert set(cast) == set(masters)
    for name, leaf in cast.items():
        want = jnp.bfloat16 if name in CAST else jnp.float32
        assert leaf.dtype == want, (name, leaf.dtype)
        assert masters[name].dtype == jnp.float32, name
    if cfg.n_experts:
        assert cast["router"] is masters["router"]

    want, got = _programs(cfg, masters), _programs(cfg, cast)
    assert want["chunk1"].dtype == jnp.float32  # logits, not tokens
    for name in want:
        _same(want[name], got[name], f"{block}: {name}")

    # The same through the server: chunked prefill and windows, by
    # the tokens a client reads.
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]
    served = {}
    for tree_name, tree in (("masters", masters), ("cast", cast)):
        server = PagedGenerationServer(
            tree, cfg, slots=2, pages=32, page_size=4,
            prefill_chunk=4, window=4)
        try:
            served[tree_name] = [
                server.submit(p, n_new=9) for p in prompts]
            stats = server.stats()
        finally:
            server.close()
        assert stats["weights_dtype"] == (
            "float32" if tree_name == "masters" else "bfloat16")
        assert stats["weights_gb"] == pytest.approx(sum(
            a.size * a.dtype.itemsize for a in tree.values()) / 1e9)
    assert served["masters"] == served["cast"]


def test_float32_config_is_served_as_it_is():
    """The CPU's pipeline meshes derive a float32 model
    (workload.derive_model_config): there is nothing to cast."""
    cfg = dataclasses.replace(DENSE, dtype="float32")
    masters = init_params(jax.random.PRNGKey(0), cfg)
    assert serving_params(masters, cfg) is masters


def test_cast_tree_is_cast_once():
    """The tree goes through the function again unchanged (a server
    handed a cast tree, a recovery that re-restores): the leaves
    themselves, not copies."""
    cast = serving_params(init_params(jax.random.PRNGKey(0), DENSE), DENSE)
    again = serving_params(cast, DENSE)
    assert all(again[name] is leaf for name, leaf in cast.items())


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_cast_keeps_each_leaf_where_it_is_sharded(block):
    """On a mesh nothing is gathered: a leaf's copy is sharded as the
    leaf is (heads, feed-forward and experts over their axes)."""
    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.parallel import build_mesh, shard_params

    cfg = BLOCKS[block]
    axes = ((("data", 2), ("expert", 2), ("model", 2)) if cfg.n_experts
            else (("data", 4), ("model", 2)))
    mesh = build_mesh(MeshSpec(axes=axes))
    masters = shard_params(mesh, init_params(jax.random.PRNGKey(0), cfg))
    cast = serving_params(masters, cfg)
    split = 0
    for name, leaf in masters.items():
        assert cast[name].sharding.is_equivalent_to(
            leaf.sharding, leaf.ndim), name
        split += not leaf.sharding.is_fully_replicated
    assert split >= 4, "the mesh shards nothing: the test shows nothing"
