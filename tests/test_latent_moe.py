"""The latent-attention, routed-and-shared-experts block
(``ops.transformer.LatentMoEBlock``) at toy widths, float32, seeded: the
contract of every described block (``block_contract.py``: the program
against the benchmark family's plain reference,
``benchmark/families/mla_moe/reference.py``, at logit level; the paged
decode; the engine; the width profiles of the latent pool's absorbed read;
every refusal), then its own: the two attention reads as one identity,
dropless routing against a per-token loop, the non-uniform stack and its
pool."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from block_contract import (BlockContract, Toy, params,  # noqa: F401
                            ref_logits, sequences, served)
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV

TOY = Toy("mla_moe", "kanana-2-30b-a3b", 3, "latent_moe",
          overrides=dict(text_seq_len=10, image_grid=4, num_image_tokens=24,
                         num_text_tokens=50, vocab_size=75),
          seed=2 ** 31 + 11, t0s=(7,),         # inside the text window
          chunked=(("latent", 1, 1e-6, 1e-7),), profiles=(0, 1, 3))
DIMS, TCFG, BLK, PS = TOY.dims, TOY.tcfg, TOY.blk, TOY.page_size


class TestContract(BlockContract):
    toy = TOY

    def step_loads(self, loads, b, t0):
        for load in loads:
            assert int(load[0]) == b * BLK.experts_per_token * DIMS.moe_layers

    def chunk_loads(self, loads, b):
        assert sum(int(load[0]) for load in loads) \
            == 16 * b * BLK.experts_per_token * DIMS.moe_layers

    def engine_counters(self, engine, st, placement):
        """The routed load comes out with the ring."""
        assert 0 < st["moe_experts_touched"] <= \
            st["decode_steps"] * DIMS.moe_layers * BLK.num_experts
        assert st["moe_load_max"] * BLK.num_experts >= st["moe_picks"]


# -- (i) the absorbed read is the materialised read --------------------------

def test_absorbed_read_equals_materialised_read():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = attn_ops.latent_init(k[0], 32, 2, BLK)
    b, m = 3, 9
    h = jax.random.normal(k[1], (b, m + 1, 32))
    q_nope, q_rope, entry = attn_ops.latent_project(
        p, h, jnp.arange(m + 1), 2, BLK)
    allowed = jax.random.bernoulli(k[2], 0.7, (b, m))
    # the last token's query over the rows before it and itself
    full = jnp.concatenate([allowed, jnp.ones((b, 1), bool)], axis=1)
    want = attn_ops.latent_attend_materialised(
        p, q_nope[:, -1:], q_rope[:, -1:], entry, full[:, None, None, :],
        BLK, TCFG.scale)[:, 0]
    got = attn_ops.latent_attend_absorbed(
        p, q_nope[:, -1], q_rope[:, -1], entry[:, :m], allowed,
        entry[:, m], BLK, TCFG.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


# -- (ii) dropless routing ----------------------------------------------------

def test_dropless_routing_equals_a_per_token_loop_and_drops_nothing():
    blk = dataclasses.replace(BLK, num_experts=8, experts_per_token=2)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    p = moe_ops.dropless_init(k[0], 32, blk)
    # a selection bias that sends every token's first pick to expert 5:
    # n picks land on it where n * k / E = n / 4 is a capacity-1 queue
    p["router"]["bias"] = jnp.zeros((8,)).at[5].set(4.0)
    n = 24
    x = jax.random.normal(k[1], (2, n // 2, 32))
    out, load = moe_ops.dropless_apply(p, x, blk)
    xt = np.asarray(x).reshape(n, 32)
    picks, weights = moe_ops.route(p["router"], jnp.asarray(xt), 2,
                                   blk.routed_scale)
    picks, weights = np.asarray(picks), np.asarray(weights)
    assert (picks == 5).sum() == n              # all of them, over capacity
    np.testing.assert_allclose(weights.sum(-1), blk.routed_scale, rtol=1e-6)

    def unit(w_in, w_out, v):
        g, u = np.split(v @ w_in, 2)
        return (g / (1 + np.exp(-g)) * u) @ w_out

    want = np.zeros((n, 32), np.float32)
    w_in, w_out = (np.asarray(p["experts"][k_]) for k_ in ("w_in", "w_out"))
    for t in range(n):                          # the per-token loop
        for e, w in zip(picks[t], weights[t]):
            want[t] += w * unit(w_in[e], w_out[e], xt[t])
        want[t] += unit(np.asarray(p["shared"]["w_in"]),
                        np.asarray(p["shared"]["w_out"]), xt[t])
    np.testing.assert_allclose(np.asarray(out).reshape(n, 32), want,
                               atol=1e-5)
    sizes = np.bincount(picks.reshape(-1), minlength=8)
    # (n * 2 pair rows are one row tile: each touched expert read once)
    assert list(np.asarray(load)) == [n * 2, (sizes > 0).sum(), sizes.max(),
                                      (sizes > 0).sum()]
    assert int(load[2]) >= n                    # what a capacity would drop


# -- (iii) the non-uniform stack and its pool ---------------------------------

def test_stack_of_one_dense_and_two_expert_layers_indexes_the_pool(params):
    tp = params["transformer"]
    assert set(tp) == {"dense", "moe"} and T.is_block_params(tp)
    assert tp["dense"]["ff"]["w_in"].shape == (1, 32, 2 * 48)
    assert tp["moe"]["ff"]["experts"]["w_in"].shape == (2, 8, 32, 2 * 12)
    init = T.transformer_init(jax.random.PRNGKey(0), TCFG)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, tp)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 32))
    h, entries, loads = T.block_apply_full(tp, x, TCFG)
    entries = entries["latent"]         # the one pool's one buffer
    assert entries.shape == (3, 1, 6, BLK.row_width)
    assert np.asarray(loads)[0].tolist() == [0, 0, 0, 0]    # the dense layer
    assert (np.asarray(loads)[1:, 0] == 6 * 2).all()
    # layer l of the stack is the pool's layer l: a decode step at position
    # 5 over the first five rows writes each layer's own row
    pool = KV.init_page_pool(TCFG, 4, PS)
    assert pool["latent"].shape == (3, 4, PS, BLK.row_width)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    buf = pool["latent"]
    for j in range(5):
        buf = buf.at[:, tables[0, j // PS], j % PS].set(entries[:, 0, j])
    _, pool, _ = decode_ops.decode_step_block(
        tp, x[:, 5], jnp.asarray([5]), {"latent": buf}, tables, cfg=TCFG,
        key_mask=jnp.ones((1, 8), bool), active=jnp.ones((1,), bool))
    np.testing.assert_allclose(np.asarray(pool["latent"][:, 2, 1]),
                               np.asarray(entries[:, 0, 5]), atol=1e-6)
    snap = KV.snapshot_page(pool, 2)
    assert snap["latent"].shape == (3, PS, BLK.row_width)
    back = KV.restore_page(KV.init_page_pool(TCFG, 4, PS), 3, snap)
    np.testing.assert_array_equal(np.asarray(back["latent"][:, 3]),
                                  np.asarray(snap["latent"]))
