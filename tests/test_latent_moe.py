"""The latent-attention, routed-and-shared-experts block
(``ops.transformer.LatentMoEBlock``) at toy widths, float32, seeded: the
program against the benchmark family's plain reference
(``benchmark/families/mla_moe/reference.py``) at logit level, the two
attention reads as one identity, dropless routing against a per-token
loop, the non-uniform stack and its pool, and every option that cannot
run the block refusing it by the one typed error."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, seeds
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import moe as moe_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine, MigrationError
from dalle_pytorch_tpu.serve.scheduler import (Request, RequestQueue,
                                               SamplingParams)

FAMILY = harness.load_family("mla_moe")
SEED = 2 ** 31 + 11
PS = 4                      # page size: the text window is not a multiple


def _dims(depth=3):
    conf = harness.load_json(harness.ROOT
                             + "/benchmark/configs/kanana-2-30b-a3b.json")
    conf.update(FAMILY.tiny)
    conf.update(text_seq_len=10, image_grid=4, num_image_tokens=24,
                num_text_tokens=50, vocab_size=75)
    return FAMILY.weights.dims_of(conf, depth)


DIMS = _dims()
CFG = FAMILY.build.program_config(DIMS, {})
TCFG = CFG.transformer
BLK = TCFG.block


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda h: FAMILY.weights.tree(h, DIMS, jnp.float32))(
        seeds.split_seed(SEED))


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(3)
    return np.concatenate(
        [rng.integers(1, DIMS.num_text_tokens, (2, DIMS.text_seq_len)),
         rng.integers(0, DIMS.num_image_tokens, (2, DIMS.image_seq_len))], 1)


@pytest.fixture(scope="module")
def ref_logits(sequences):
    return np.asarray(FAMILY.reference.served_logits(
        SEED, DIMS, jnp.float32, sequences.tolist()))


def _close(got, want, atol=2e-5):
    fin = np.isfinite(want)
    assert (np.asarray(got)[~fin] < -1e30).all()      # forbidden either way
    np.testing.assert_allclose(np.asarray(got)[fin], want[fin], atol=atol,
                               rtol=0)


# -- (i) the full forward against the reference -------------------------------

def test_dalle_apply_matches_the_reference_logits(params, sequences,
                                                  ref_logits):
    t = DIMS.text_seq_len
    logits = D.dalle_apply(params, jnp.asarray(sequences[:, :t]),
                           jnp.asarray(sequences[:, t:-1]), cfg=CFG)
    _close(logits, ref_logits)


# -- (ii) prefill, then the paged gather decode --------------------------------

def _prefilled_pool(params, sequences, t0, upto=None):
    """The prompt's rows [0, t0) of the sequences in a pool, slot i's
    pages 1 + i * W .. (page 0 is the trash page); with ``upto`` (b,),
    slot i's rows [0, upto[i]) alone."""
    b = sequences.shape[0]
    width = KV.pages_for(DIMS.seq_len, PS)
    tables = 1 + jnp.arange(b * width, dtype=jnp.int32).reshape(b, width)
    pool = KV.init_page_pool(TCFG, 1 + b * width, PS)
    t = min(t0, DIMS.text_seq_len)
    x = D.embed_prompt(params, CFG, jnp.asarray(sequences[:, :t]),
                       jnp.asarray(sequences[:, t:t0]))
    h, cache = decode_ops.prefill(params["transformer"], x, cfg=TCFG,
                                  total_len=DIMS.seq_len)
    rows = np.asarray(cache["latent"])          # (depth, b, t0, width)
    assert rows.shape == (DIMS.depth, b, t0, BLK.row_width)
    buf = np.array(pool["latent"])
    for i in range(b):
        for j in range(t0 if upto is None else upto[i]):
            buf[:, tables[i, j // PS], j % PS] = rows[:, i, j]
    return h, {"latent": jnp.asarray(buf)}, tables


def _teacher_forced(params, sequences):
    def embed_fn(tok, pos):
        return D.decode_token_embed(params, CFG, tok, pos)

    def sample_fn(_h, pred_pos):
        # the NEXT token of the given sequences, as the loop stores it
        return jnp.take_along_axis(jnp.asarray(sequences),
                                   pred_pos[:, None], axis=1)[:, 0]
    return embed_fn, sample_fn


def test_prefill_then_paged_decode_matches_the_full_forward(
        params, sequences, ref_logits):
    t0 = 7                                      # inside the text window
    h, pool, tables = _prefilled_pool(params, sequences, t0)
    b = sequences.shape[0]
    key_mask = jnp.ones((b, DIMS.seq_len), bool)
    active = jnp.ones((b,), bool)
    forbidden = np.asarray(D.logits_mask(CFG))
    first = np.where(forbidden[t0 - 1], -np.inf,
                     np.asarray(D.to_logits(params, h[:, -1])))
    fin = np.isfinite(ref_logits[:, t0 - 1])
    np.testing.assert_allclose(first[fin], ref_logits[:, t0 - 1][fin],
                               atol=2e-5, rtol=0)     # the prefill's own row
    # position by position, logits against the reference's full forward
    step_pool = pool
    for pos in range(t0, DIMS.seq_len - 1):
        p = jnp.full((b,), pos, jnp.int32)
        x = D.decode_token_embed(params, CFG, jnp.asarray(sequences[:, pos]),
                                 p)
        h_tok, step_pool, load = decode_ops.decode_step_block(
            params["transformer"], x, p, step_pool, tables, cfg=TCFG,
            key_mask=key_mask, active=active)
        logits = np.asarray(D.to_logits(params, h_tok))
        logits = np.where(forbidden[pos], -np.inf, logits)
        fin = np.isfinite(ref_logits[:, pos])
        np.testing.assert_allclose(logits[fin], ref_logits[:, pos][fin],
                                   atol=2e-5, rtol=0)
        assert int(load[0]) == b * BLK.experts_per_token * DIMS.moe_layers
    # the same steps in chunks of 8 write the same pool and count the picks
    embed_fn, sample_fn = _teacher_forced(params, sequences)
    cur = jnp.asarray(sequences[:, t0])
    p = jnp.full((b,), t0, jnp.int32)
    chunk_pool, picks = pool, 0
    for _ in range(2):
        cur, p, act, chunk_pool, ring, load = decode_ops.decode_loop_paged(
            params["transformer"], cur, p, active, chunk_pool, tables,
            cfg=TCFG, key_mask=key_mask, total_len=DIMS.seq_len, steps=8,
            embed_fn=embed_fn, sample_fn=sample_fn)
        picks += int(load[0])
    assert picks == 16 * b * BLK.experts_per_token * DIMS.moe_layers
    np.testing.assert_array_equal(np.asarray(ring)[:, -1],
                                  sequences[:, t0 + 15])
    rows = slice(t0, t0 + 16)
    live = np.asarray(decode_ops.layer_pool_view(
        chunk_pool["latent"], jnp.int32(1), tables)
        ).reshape(b, -1, BLK.row_width)
    want = np.asarray(decode_ops.layer_pool_view(
        step_pool["latent"], jnp.int32(1), tables)
        ).reshape(b, -1, BLK.row_width)
    np.testing.assert_allclose(live[:, rows], want[:, rows], atol=1e-6)


def _step_at(params, seqs, positions):
    """One decode step with slot i at ``positions[i]`` of ``seqs[i]``,
    the rows before it in its pages -> the logits (forbidden ones -inf)."""
    top = int(positions.max())
    _, pool, tables = _prefilled_pool(params, seqs, top, positions)
    p = jnp.asarray(positions)
    b = len(positions)
    x = D.decode_token_embed(
        params, CFG, jnp.asarray(seqs[np.arange(b), positions]), p)
    _step_at.plan = decode_ops.block_view_plan(
        TCFG, params["transformer"], pool, b, DIMS.seq_len)
    h_tok, _, _ = jax.jit(lambda x, p, pool: decode_ops.decode_step_block(
        params["transformer"], x, p, pool, tables, cfg=TCFG,
        key_mask=jnp.ones((b, DIMS.seq_len), bool),
        active=jnp.ones((b,), bool)))(x, p, pool)
    return np.where(np.asarray(D.logits_mask(CFG))[positions], -np.inf,
                    np.asarray(D.to_logits(params, h_tok)))


@pytest.mark.parametrize("at", [0, 1, 3])
def test_slots_up_to_each_width_profile_match_the_full_forward(
        params, sequences, ref_logits, profile_positions, reads_at, at,
        release_programs, four_slots_a_group, switch_placement):
    """ISSUE 38: the reads of the pool whose rows lie in order stop at
    the rows that are written (the absorbed read of the latent pool).
    Sixteen slots in shuffled phase order whose positions need profile
    ``at`` of the table's staircases (in every group a slot AT its
    width's edge, one a row before it, one a row after the edge of the
    group before; a parked slot, one at 1; the last row at the last profile):
    one step by the rule gives the reference's full-forward logits at
    every slot's own position, and the greedy tokens of the same step at
    full width; the profile before (the planted fault) fails the same
    comparison."""
    width = KV.pages_for(DIMS.seq_len, PS)
    assert decode_ops.view_slot_groups(16, width, (PS, BLK.row_width),
                                       jnp.float32) == 4
    profiles = decode_ops.view_profiles(4, width)
    assert len(profiles) == 4
    positions = profile_positions(profiles[at], PS, DIMS.seq_len - 1)
    assert int(decode_ops.view_profile_index(
        np.sort(positions), 4, width, PS, xp=np)) == at
    rows = np.arange(len(positions)) % len(sequences)
    seqs = sequences[rows]
    want = ref_logits[rows, positions]
    got = _step_at(params, seqs, positions)
    _close(got, want)
    # where the switch stands (``block_view_plan``): one around the span
    # of scans that read the ordered pool, every reader at the profile;
    # or one a scanned read, a run of one layer whole
    plan = _step_at.plan
    readers = [r for r in T.layer_runs(BLK, TCFG.depth)
               if r.kind.pool == "full"]
    lone = sum(r.count for r in readers if r.count == 1)
    assert lone and plan.groups == 4
    if switch_placement == "one_switch":
        assert plan.span is not None and plan.whole == 0
    else:
        assert plan.span is None and plan.whole == lone
    assert plan.by_rule + plan.whole == sum(r.count for r in readers)
    with reads_at("full_width"):
        whole = _step_at(params, seqs, positions)
    _close(whole, want)
    np.testing.assert_array_equal(got.argmax(-1), whole.argmax(-1))
    if at:
        with reads_at("too_narrow"):
            cut = _step_at(params, seqs, positions)
        with pytest.raises(AssertionError):
            _close(cut, want)


def test_engine_serves_the_reference_s_tokens_in_chunks_of_8(params):
    """Through the engine: admission's whole-page write, slot reuse, the
    fused chunks. Greedy tokens are the reference's best at every served
    position (gap 0 but for float32 near-ties), and the routed load
    comes out with the ring."""
    queue = RequestQueue(max_depth=8)
    engine = Engine(params, CFG, queue, num_slots=2, chunk_steps=8,
                    kv="paged", page_size=PS)
    greedy = SamplingParams(filter_thres=1.0)
    reqs = [Request(codes=(3, 7, 9), seed=11, sampling=greedy),
            Request(codes=tuple(range(1, 11)), seed=2, sampling=greedy),
            Request(codes=(6, 6, 1, 2, 3, 9, 4), seed=3, sampling=greedy)]
    handles = [queue.submit(r) for r in reqs]
    engine.run_until_idle()
    seqs, lens = [], []
    for r, h in zip(reqs, handles):
        res = h.result(timeout=5)
        assert res.status == "ok"
        seqs.append(list(np.asarray(res.text_tokens))
                    + list(np.asarray(res.tokens)))
        lens.append(len(r.codes))
        assert seqs[-1][:lens[-1]] == list(r.codes)
    gaps, served = FAMILY.reference.served_gaps(SEED, DIMS, jnp.float32,
                                                seqs, lens)
    assert float(np.asarray(gaps)[np.asarray(served)].max()) < 1e-5
    st = engine.stats()
    assert engine.decode_traces == 1 and engine.alloc.in_use == 0
    assert st["moe_picks"] == (st["decode_steps"] * engine.num_slots
                               * BLK.experts_per_token * DIMS.moe_layers)
    assert 0 < st["moe_experts_touched"] <= \
        st["decode_steps"] * DIMS.moe_layers * BLK.num_experts
    assert st["moe_load_max"] * BLK.num_experts >= st["moe_picks"]
    assert st["kv_hbm_bytes"] == KV.modeled_kv_bytes(
        TCFG, kv="paged", num_slots=2, total_len=DIMS.seq_len,
        page_size=PS)


# -- (iii) the absorbed read is the materialised read --------------------------

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


# -- (iv) dropless routing ------------------------------------------------------

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


# -- (v) the non-uniform stack and its pool ------------------------------------

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


# -- (vi) every path that cannot run the block refuses it ----------------------

def _engine(params, **kw):
    kw.setdefault("kv", "paged")
    return Engine(params, CFG, RequestQueue(max_depth=2), num_slots=1, **kw)


def _mesh_engine(params):
    from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
    return MeshEngine(params, CFG, RequestQueue(max_depth=2),
                      devices=jax.devices()[:2], num_slots=1, kv="paged")


REFUSED = {
    "kv_dense": lambda p: _engine(p, kv="dense"),
    "paged_attn_kernel": lambda p: _engine(p, paged_attn="kernel",
                                           page_size=8),
    "speculative": lambda p: _engine(p, speculative=2),
    "sparse_reads": lambda p: _engine(p, sparse_reads=True),
    "quantize_cache": lambda p: _engine(p, quantize_cache=True),
    "prefix_cache": lambda p: _engine(p, prefix_cache=True),
    "mesh_engine": _mesh_engine,
    "quantize_int8": lambda p: D.quantize_for_decode(p),
    "generate_images": lambda p: D.generate_images(
        p, None, jnp.ones((1, 4), jnp.int32), cfg=CFG,
        rng=jax.random.PRNGKey(0)),
    "train": lambda p: D.dalle_apply(
        p, jnp.ones((1, 10), jnp.int32), jnp.ones((1, 16), jnp.int32),
        cfg=CFG, train=True, return_loss=True),
    "reversible": lambda p: dataclasses.replace(CFG, reversible=True)
    .transformer,
    "sparse_attn": lambda p: dataclasses.replace(CFG, sparse_attn=True)
    .transformer,
    "attn_impl_flash": lambda p: dataclasses.replace(CFG, attn_impl="flash")
    .transformer,
    "remat": lambda p: dataclasses.replace(CFG, remat="full").transformer,
    "capacity_moe": lambda p: dataclasses.replace(CFG, moe_experts=4)
    .transformer,
    "dense_cache": lambda p: decode_ops.init_cache(TCFG, 1, 8),
    "dense_decode_step": lambda p: decode_ops.decode_step(
        p["transformer"], jnp.zeros((1, 32)), 3, {}, cfg=TCFG,
        key_mask=jnp.ones((1, 8), bool)),
    "speculative_loop": lambda p: decode_ops.decode_loop_spec_paged(
        p["transformer"], None, None, None, None, {}, None, cfg=TCFG,
        draft_cfg=None, key_mask=None, total_len=8, steps=1, k=2,
        embed_fn=None, sample_fn=None),
    "kernel_loop": lambda p: decode_ops.decode_loop_paged(
        p["transformer"], None, None, None, {}, None, cfg=TCFG,
        key_mask=None, total_len=8, steps=1, embed_fn=None, sample_fn=None,
        attn_impl="kernel"),
    "int8_pool": lambda p: KV.init_page_pool(TCFG, 4, PS, quantized=True),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_every_refused_option_raises_the_one_typed_error(params, option):
    with pytest.raises(T.BlockOptionError) as e:
        REFUSED[option](params)
    assert e.value.block == BLK.name == "latent_moe" and e.value.option
    assert BLK.name in str(e.value) and e.value.option in str(e.value)


@pytest.mark.parametrize("call", ["export", "import"])
def test_migration_refuses_the_block_and_falls_back_to_replay(params, call):
    """A MIGRATE frame's callers catch ``MigrationError`` and replay: the
    refusal is that error, naming the block and the option."""
    engine = _engine(params, page_size=PS)
    with pytest.raises(MigrationError, match="latent_moe.*export/import") \
            as e:
        engine.export_slot(0) if call == "export" \
            else engine.import_slot({"weights_version": "0"})
    assert e.value.reason == "block"
