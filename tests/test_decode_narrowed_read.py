"""ISSUE 38: the paged gather reads stop at the rows that are written
(``ops/decode.py``: the slots in the order of ``pos``, a slot group at the
width its step's profile gives it). The rule as pure functions, where
each described cell's switch stands, the classic pool's read against its
oracle and against the full-width read, the planted fault, the engine's
counters. A file of its own beside tests/test_paged_attention.py, whose
pools, requests and reference it shares (tests/paged_pool.py): the
sixteen-slot programs are the slowest of the suite, and the suite's
workers take a file each."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.serve import RequestQueue
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine
from paged_pool import random_pool
from tiny_model import bundle, CFG, reference_tokens, REQS, VCFG  # noqa: F401


class TestNarrowedRead:
    """ISSUE 38: the paged gather reads stop at the rows that are written.
    The slots are read in the order of ``pos``, a slot group at the width
    that the step's profile gives it: the narrowest of a few staircases of
    widths that holds every group's furthest row, chosen once a step. The
    rule as pure functions, then the classic pool's read held to the
    ``paged_view`` + ``_gather_read`` oracle and to the same read at full
    width, for positions that need each profile in turn, and a planted
    fault (the profile before) that the same comparison must catch; the
    described blocks' cases are with their step tests (test_latent_moe.py,
    test_afmoe_block.py, test_ssm_hybrid_block.py)."""

    @pytest.fixture(autouse=True)
    def _groups(self, four_slots_a_group):
        """(conftest.py: the sixteen slots read in groups of four; the
        programs end with the file, ``programs_end_with_their_file``)"""

    # the tables of the cells (ruDALL-E, 12b, the latent and phi's full
    # pool, trinity's full pool) and some small ones
    COLUMNS = [1, 2, 3, 7, 8, 9, 72, 80, 272, 592]

    @pytest.mark.parametrize("columns", COLUMNS)
    def test_widths_are_whole_pages_in_order_and_end_at_the_table(
            self, columns):
        widths = decode_ops.view_widths(columns)
        assert all(isinstance(w, int) and w >= 1 for w in widths)
        assert list(widths) == sorted(set(widths))   # strictly rising
        assert widths[-1] == columns
        assert len(widths) == min(decode_ops._VIEW_WIDTH_STEPS, columns)
        # equal steps: no step more than a page over an eighth of the table
        steps = np.diff((0,) + widths)
        assert steps.max() <= -(-columns // len(widths))

    @pytest.mark.parametrize("groups", [1, 2, 3, 4, 8])
    @pytest.mark.parametrize("columns", COLUMNS)
    def test_profiles_are_staircases_of_the_ladder_up_to_the_table(
            self, columns, groups):
        """Every profile gives each group a width of the ladder, no
        group less than the one before it (the slots lie in the order of
        ``pos``); each profile holds what the one before it holds and
        more; the last is the whole table in every group; the first is
        the even staircase: group g of n holds the first (g + 1) / n of
        the table."""
        ladder = decode_ops.view_widths(columns)
        profiles = decode_ops.view_profiles(groups, columns)
        assert 1 <= len(profiles) <= decode_ops._VIEW_PROFILES + 1
        assert len(set(profiles)) == len(profiles)
        assert profiles[-1] == (columns,) * groups
        for widths in profiles:
            assert len(widths) == groups
            assert all(w in ladder for w in widths)
            assert list(widths) == sorted(widths)
        for narrow, wide in zip(profiles, profiles[1:]):
            assert all(a <= b for a, b in zip(narrow, wide))
        for g, w in enumerate(profiles[0]):
            assert w * groups >= (g + 1) * columns
            assert (w - -(-columns // len(ladder))) * groups \
                < (g + 1) * columns

    CASES = [(72, 16, 16, 4), (80, 16, 12, 3), (272, 16, 32, 8),
             (592, 16, 16, 4), (3, 8, 8, 2), (9, 4, 16, 4), (72, 16, 16, 1),
             (5, 4, 7, 7)]

    @pytest.mark.parametrize("columns,page_size,slots,groups", CASES)
    def test_chosen_profile_is_the_first_that_holds_every_groups_rows(
            self, columns, page_size, slots, groups):
        """Every row before a slot's ``pos`` lies in the columns its
        group reads (the token's own row is the read's self logit), and
        the profile before the chosen one would cut some group's
        furthest slot; parked slots fit the narrowest profile."""
        profiles = np.asarray(decode_ops.view_profiles(groups, columns))
        rng = np.random.default_rng(columns + slots)
        total_len = columns * page_size
        per = slots // groups
        chosen = set()
        for trial in range(200):
            if trial % 2:       # evenly staggered, some jitter
                pos = (np.arange(slots) + rng.uniform(-1, 1, slots)) \
                    * total_len / slots + rng.integers(0, total_len // 4)
                pos = np.sort(np.clip(pos.astype(np.int64), 0, total_len))
            else:
                pos = np.sort(rng.integers(0, total_len + 1, slots))
            at = int(decode_ops.view_profile_index(pos, groups, columns,
                                                   page_size, xp=np))
            chosen.add(at)
            furthest = pos[per - 1::per]
            assert (profiles[at] * page_size >= furthest).all()
            if at > 0:
                assert (profiles[at - 1] * page_size < furthest).any()
        assert len(chosen) > 1 or len(profiles) == 1
        parked = decode_ops.view_profile_index(
            np.zeros((slots,), np.int64), groups, columns, page_size, xp=np)
        assert parked == 0                      # the narrowest profile

    @pytest.mark.parametrize("columns,page_size,slots,groups", CASES[:6])
    def test_host_and_device_evaluate_the_rule_alike(
            self, columns, page_size, slots, groups):
        """numpy on the host (the engine's counter) and jnp on the device
        (the step) give the same order, the same profile and the same
        columns read, on random positions with ties and parked slots."""
        rng = np.random.default_rng(slots)
        profiles = decode_ops.view_profiles(groups, columns)
        for _ in range(20):
            pos = rng.integers(0, columns * page_size + 1, slots)
            pos[rng.integers(0, slots, 3)] = 0
            order, inverse = decode_ops._slot_order(jnp.asarray(pos))
            np.testing.assert_array_equal(
                np.asarray(order), np.argsort(pos, kind="stable"))
            np.testing.assert_array_equal(
                np.asarray(inverse)[np.asarray(order)], np.arange(slots))
            host = decode_ops.view_profile_index(
                np.sort(pos), groups, columns, page_size, xp=np)
            device = decode_ops.view_profile_index(
                jnp.asarray(pos)[order], groups, columns, page_size)
            assert int(device) == int(host)
            # the host's count over a chunk of three steps: two layers at
            # the profile and one whole; a live slot moves on a row a
            # step, a parked one stays
            plan = decode_ops.ViewPlan(slots, columns, page_size, groups,
                                       by_rule=2, whole=1)
            read, full = plan.columns_read(pos, steps=3)
            assert full == 3 * 3 * slots * columns
            narrowed = 0
            for step in range(3):
                at = np.minimum(pos + step * (pos > 0),
                                columns * page_size)
                narrowed += slots // groups * sum(profiles[int(
                    decode_ops.view_profile_index(
                        np.sort(at), groups, columns, page_size, xp=np))])
            assert read == 2 * narrowed + 3 * slots * columns <= full
            assert plan.columns_read(pos)[0] == 2 * slots // groups * sum(
                profiles[int(host)]) + slots * columns

    @pytest.mark.parametrize("name,slots_a_group,by_rule,whole,span", [
        ("kanana-2-30b-a3b.serve-full", 8, 6, 1, None),
        ("trinity-large-preview.serve-full", 2, 0, 1, None),
        ("phi-4-mini-flash-reasoning.serve-full", 4, 8, 0, (1, 3)),
    ], ids=["kanana", "trinity", "phi"])
    def test_where_each_described_cell_switches(self, monkeypatch, name,
                                                slots_a_group, by_rule,
                                                whole, span):
        """``block_view_plan`` on the benchmark's own shapes (nothing is
        allocated), with the rule's own bytes: kanana's 7.25 GB of expert
        stacks cannot be handed out of a branch, so its six scanned
        expert layers switch read by read and its dense layer, a run of
        one, reads whole; trinity's cut has ONE full layer, a run of one
        under expert stacks: nothing reads by the rule, and the engine's
        counters stay 0 there; phi's full layer and seven cross readers
        stand in one switch with the memory units between them, after the
        eight state-space and window pairs, and what the span hands out
        is the ninth state-space layer's state."""
        from benchmark import harness, seeds
        monkeypatch.undo()              # (the class's four slots a group)
        cell = harness.Cell(name)
        dims = cell.family.weights.dims_of(cell.config, cell.spec["depth"])
        dtype = jnp.dtype(cell.config["param_dtype"])
        cfg = cell.family.build.program_config(dims, cell.spec["flags"])
        tcfg, slots, ps = cfg.transformer, int(cell.spec["num_slots"]), 16
        params = jax.eval_shape(lambda: cell.family.weights.tree(
            seeds.split_seed(0), dims, dtype))["transformer"]
        ring = tcfg.block.ring_pages(ps, cfg.seq_len) \
            if "window" in tcfg.block.pools(tcfg.depth) else 0
        pool = jax.eval_shape(lambda: KV.init_page_pool(
            tcfg, slots * KV.pages_for(cfg.seq_len, ps) + 1, ps, dtype,
            window_pages=slots * ring + 1 if ring else 0, num_slots=slots))
        plan = decode_ops.block_view_plan(tcfg, params, pool, slots,
                                          cfg.seq_len)
        assert plan == decode_ops.ViewPlan(
            slots, KV.pages_for(cfg.seq_len, ps), ps,
            slots // slots_a_group, by_rule, whole, span)
        # no budget at all: a switch a read whatever the block
        monkeypatch.setattr(decode_ops, "_VIEW_SWITCH_BYTES", -1)
        assert decode_ops.block_view_plan(
            tcfg, params, pool, slots, cfg.seq_len).span is None

    # ---- the classic pool's read, positions that need each profile ----

    HEADS, DH, COLS, SLOTS = 4, 128, 12, 16

    def _profile_case(self, kind, at, profile_positions):
        """Sixteen slots (four groups) over a table of 12 columns (ladder
        2, 3, 5, 6, 8, 9, 11, 12) at positions that need exactly profile
        ``at``, shuffled; pages of whole tiles, random everywhere; one
        slot with trash entries past its pages, a padded-off row in
        another's mask."""
        ps = 32 if kind == "int8" else 16
        total_len = self.COLS * ps - 3               # a partial last page
        profiles = decode_ops.view_profiles(4, self.COLS)
        pos = profile_positions(profiles[at], ps, total_len)
        slots = len(pos)
        assert slots == self.SLOTS
        assert int(decode_ops.view_profile_index(
            np.sort(pos), 4, self.COLS, ps, xp=np)) == at
        dtype = jnp.float32 if kind != "bf16" else jnp.bfloat16
        key = jax.random.PRNGKey(38)
        pool = random_pool(key, ps, slots * self.COLS + 1, kind == "int8",
                            dim_head=self.DH, dtype=dtype, heads=self.HEADS)
        assert decode_ops.pool_view_groups(pool, slots, self.COLS) == 4
        bt = np.arange(1, slots * self.COLS + 1, dtype=np.int32).reshape(
            slots, self.COLS)
        mid = int(np.argsort(pos)[slots // 2])
        bt[mid, KV.pages_for(pos[mid] + 1, ps):] = 0     # trash entries
        x = jax.random.normal(jax.random.fold_in(key, 9),
                              (slots, self.WIDE.dim), dtype)
        last = int(np.argmax(pos))
        key_mask = jnp.ones((slots, total_len), bool).at[last, 1].set(False)
        return pool, jnp.asarray(bt), jnp.asarray(pos), x, key_mask, ps

    WIDE = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                         text_seq_len=8, heads=HEADS, dim_head=DH)

    # (kind, per-head form, how it reads) -> (the jitted step, the shapes of
    # the tables that its trace handed ``_paged_gather_read``): the
    # positions are values, so the cases of one key differ in no shape
    # and share a program; it is traced inside the context it is meant
    # for (``reads_at``), which is part of the key
    _TRACED: dict = {}

    def _step(self, kind, case, params, mesh=False, reads_at=None,
              reads="by_rule"):
        """The step math over the pool through the tables, reading by the
        rule or as ``reads_at(reads)`` has it -> (its outputs, the table
        shapes its trace read: a group a read, a branch a profile)."""
        pool, bt, pos, x, key_mask, _ = case
        key = (kind, mesh, reads)
        with reads_at(reads) if reads != "by_rule" \
                else contextlib.nullcontext():
            if key not in self._TRACED:
                shapes, real = [], decode_ops._paged_gather_read

                def spy(pool_, layer, tables, *a, **kw):
                    shapes.append(tables.shape)
                    return real(pool_, layer, tables, *a, **kw)

                def step(params, x, pos, pool, key_mask, bt):
                    return decode_ops._decode_step_math(
                        params, x, pos, pool, cfg=self._tcfg(),
                        key_mask=key_mask, block_tables=bt,
                        out_sync=(lambda out: out) if mesh else None)
                self._TRACED[key] = (jax.jit(step), shapes)
                with pytest.MonkeyPatch.context() as m:
                    m.setattr(decode_ops, "_paged_gather_read", spy)
                    self._TRACED[key][0](params, x, pos, pool, key_mask, bt)
            step, shapes = self._TRACED[key]
            return step(params, x, pos, pool, key_mask, bt), shapes

    def _tcfg(self):
        return self.WIDE.transformer

    _PARAMS: dict = {}

    def _params(self, dtype):
        if dtype not in self._PARAMS:
            params = D.dalle_init(jax.random.PRNGKey(0), self.WIDE,
                                  V.vae_init(jax.random.PRNGKey(1), VCFG))
            self._PARAMS[dtype] = jax.tree.map(
                lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a,
                params["transformer"])
        return self._PARAMS[dtype]

    def _oracle(self, kind, case, params):
        """The same step over the ``paged_view`` of the same pool: the
        dense step's one einsum softmax (``_gather_read``)."""
        pool, bt, pos, x, key_mask, _ = case
        if (kind, "oracle") not in self._TRACED:
            def oracle(params, x, pos, pool, key_mask, bt):
                view = decode_ops.paged_view(pool, bt, key_mask.shape[1],
                                             self.HEADS)
                return decode_ops._decode_step_math(
                    params, x, pos, view, cfg=self._tcfg(),
                    key_mask=key_mask)
            self._TRACED[kind, "oracle"] = jax.jit(oracle)
        return self._TRACED[kind, "oracle"](params, x, pos, pool, key_mask,
                                            bt)

    TOL = {"bf16": dict(rtol=5e-2, atol=5e-2),
           "f32": dict(rtol=1e-4, atol=1e-4),
           # (rows of +-127 x 0.1 summed over 381 of them in float32)
           "int8": dict(rtol=5e-4, atol=5e-4)}

    def _assert_step_close(self, got, want, kind):
        for a, b in zip(got, want):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       **self.TOL[kind])

    @pytest.mark.parametrize("mesh", [False, True],
                             ids=["whole_rows", "per_head"])
    @pytest.mark.parametrize("at", [0, 1, 2, 3])
    @pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
    def test_narrowed_step_matches_the_oracle_and_the_full_width_step(
            self, profile_positions, reads_at, kind, at, mesh):
        """Slots in shuffled phase order that need each profile in turn
        (in every group one AT its width's edge, one a row before it, one
        a row after the edge of the group before; a parked slot, one at
        1, the last row): the step by the rule equals the ``paged_view`` +
        ``_gather_read`` oracle within rounding, over the float32 and
        bf16 pools and the int8 pool with its scale pages, as whole rows
        and in the mesh's per-head form; every profile is traced, a
        branch each, and a group reads its profile's width; the same step
        at full width agrees."""
        case = self._profile_case(kind, at, profile_positions)
        params = self._params(case[3].dtype)
        profiles = decode_ops.view_profiles(4, self.COLS)
        assert len(profiles) == 4
        got, widths_read = self._step(kind, case, params, mesh)
        # every profile traced once (the layer scan's body), a group a read
        assert widths_read == [(4, w) for widths in profiles
                               for w in widths]
        self._assert_step_close(got, self._oracle(kind, case, params), kind)
        whole, _ = self._step(kind, case, params, mesh, reads_at,
                              "full_width")
        self._assert_step_close(got, whole, kind)

    @pytest.mark.parametrize("at", [1, 2, 3])
    @pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
    def test_a_profile_too_narrow_is_caught(self, profile_positions,
                                            reads_at, kind, at):
        """The planted fault: the profile before the one the positions
        need drops rows that are written, and the parity case above
        fails on it (a test that passes with a planted fault is the
        finding: PR 35)."""
        case = self._profile_case(kind, at, profile_positions)
        params = self._params(case[3].dtype)
        got, _ = self._step(kind, case, params, reads_at=reads_at,
                            reads="too_narrow")
        with pytest.raises(AssertionError):
            self._assert_step_close(got, self._oracle(kind, case, params),
                                    kind)

    def test_plain_tables_read_in_slot_order_at_full_width(
            self, profile_positions):
        """``_paged_gather_attend`` handed plain tables (a sparse layer's
        visible columns, which lie in no order of ``pos``) reads every
        group's whole table in slot order, and equals the read through
        the ordered view at the profile's widths."""
        pool, bt, pos, x, key_mask, ps = self._profile_case(
            "f32", 1, profile_positions)
        slots = bt.shape[0]
        qkv = [jax.random.normal(jax.random.PRNGKey(i),
                                 (slots, self.HEADS, 1, self.DH))
               for i in range(3)]
        allowed = (jnp.arange(key_mask.shape[1])[None, :]
                   < pos[:, None]) & key_mask
        plain = decode_ops._paged_gather_attend(
            pool, jnp.asarray(1), bt, *qkv, allowed, scale=128 ** -0.5)
        order, inverse = decode_ops._slot_order(pos)
        view = decode_ops._View(
            bt[order], order, inverse,
            decode_ops.view_profiles(4, self.COLS)[1])
        ordered = decode_ops._paged_gather_attend(
            pool, jnp.asarray(1), view, *qkv, allowed[order],
            scale=128 ** -0.5)
        np.testing.assert_allclose(np.asarray(ordered), np.asarray(plain),
                                   rtol=2e-5, atol=2e-5)

    # ---- the fused loop: greedy tokens, slots that need each profile ----

    EDGE_CFG = D.DALLEConfig(dim=16, depth=2, vae=VCFG, num_text_tokens=50,
                             text_seq_len=8, heads=2, dim_head=8)
    EDGE_SPARSE_CFG = D.DALLEConfig(
        dim=16, depth=2, vae=VCFG, num_text_tokens=50, text_seq_len=8,
        heads=2, dim_head=8, sparse_attn=(True, False), sparse_block=4)

    def _edge_loop(self, cfg, quantized, at, profile_positions,
                   page_size=2):
        """The fused loop's arguments with sixteen slots at positions
        that need profile ``at`` of a 12-column table (24 positions, pages
        of 2 rows), the parked one inactive; greedy sampling through the
        model's own embedding and head."""
        vae_params = V.vae_init(jax.random.PRNGKey(1), VCFG)
        params = D.dalle_init(jax.random.PRNGKey(0), cfg, vae_params)
        tcfg = cfg.transformer
        L = cfg.seq_len
        mp = KV.pages_for(L, page_size)
        profiles = decode_ops.view_profiles(4, mp)
        # six steps on: the last step's positions need profile ``at``
        pos = np.maximum(profile_positions(
            profiles[min(at, len(profiles) - 1)], page_size, L) - 5, 0)
        slots = len(pos)
        pool = random_pool(jax.random.PRNGKey(21), page_size,
                            slots * mp + 1, quantized,
                            dim_head=tcfg.dim_head)
        assert decode_ops.pool_view_groups(pool, slots, mp) == 4
        bt = jnp.asarray(np.arange(1, slots * mp + 1, dtype=np.int32)
                         .reshape(slots, mp))
        active = jnp.asarray(pos > 0)
        cur = jnp.asarray(3 + np.arange(slots) % 7, jnp.int32)

        def embed_fn(tok, p):
            return D.decode_token_embed(params, cfg, tok, p)

        def sample_fn(h, pred_pos):
            return jnp.argmax(D.to_logits(params, h), -1).astype(jnp.int32)

        kw = dict(cfg=tcfg, key_mask=jnp.ones((slots, L), bool), steps=6,
                  embed_fn=embed_fn, sample_fn=sample_fn)
        return (params["transformer"], cur, jnp.asarray(pos), active, pool,
                bt, L, kw)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["f32", "int8"])
    @pytest.mark.parametrize("layers,at", [
        ("dense", 0), ("dense", 1), ("dense", 3), ("sparse_layer", 1),
        ("per_head", 2)])
    def test_loop_tokens_identical_with_slots_up_to_each_profile(
            self, profile_positions, layers, at, quantized):
        """Six fused steps that end at positions needing profile ``at``
        (so the steps cross the widths' edges inside the chunk, and a
        slot ends its sequence and parks): the loop that reads by the
        rule emits the dense loop's greedy tokens byte for byte, for
        dense layers, a sparse layer (its layout ``&``-ed into the same
        mask) and the mesh's per-head form."""
        cfg = self.EDGE_SPARSE_CFG if layers == "sparse_layer" \
            else self.EDGE_CFG
        tp, cur, pos, active, pool, bt, L, kw = self._edge_loop(
            cfg, quantized, at, profile_positions)
        dense = decode_ops.decode_loop(
            tp, cur, pos, active,
            decode_ops.paged_view(pool, bt, L, cfg.heads), **kw)
        paged = decode_ops.decode_loop_paged(
            tp, cur, pos, active, pool, bt, total_len=L,
            out_sync=(lambda out: out) if layers == "per_head" else None,
            **kw)
        assert (np.asarray(paged[4])[np.asarray(active)][:, 0] >= 0).all()
        for i in (0, 1, 2, 4):                    # tok, pos, active, ring
            np.testing.assert_array_equal(np.asarray(paged[i]),
                                          np.asarray(dense[i]))

    def test_loop_with_a_profile_too_narrow_is_caught(
            self, profile_positions, reads_at):
        """The planted fault through the whole loop: other tokens."""
        cfg = self.EDGE_CFG
        tp, cur, pos, active, pool, bt, L, kw = self._edge_loop(
            cfg, False, 2, profile_positions)
        dense = decode_ops.decode_loop(
            tp, cur, pos, active,
            decode_ops.paged_view(pool, bt, L, cfg.heads), **kw)
        with reads_at("too_narrow"):
            paged = decode_ops.decode_loop_paged(
                tp, cur, pos, active, pool, bt, total_len=L, **kw)
        assert (np.asarray(paged[4]) != np.asarray(dense[4])).any()

    def test_engine_counts_the_columns_it_reads(self, bundle):
        """``stats()["kv_view_columns_read"]`` / ``_full`` and the ledger
        row's ``view_read_pct``: the host's evaluation of the rule at the
        positions of every step of each dispatched chunk, a layer at a
        time (``ViewPlan``: both layers read at the profile). Eight slots
        (two groups over a table of 3 columns: profiles (2, 3) and (3,
        3)), one request: the free slots are parked and sort first, so
        every step reads the first profile, 4 x 2 + 4 x 3 of 24 columns a
        layer; the tokens are the reference's."""
        params, vae_params = bundle
        queue = RequestQueue(max_depth=4)
        engine = Engine(params, CFG, queue, num_slots=8, chunk_steps=4,
                        kv="paged", page_size=8)
        s0 = engine.stats()
        assert s0["kv_view_columns_read"] == s0["kv_view_columns_full"] == 0
        h = queue.submit(REQS[0])
        engine.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(h.result(5).tokens),
            reference_tokens(params, vae_params, REQS[0]))
        s1 = engine.stats()
        assert s1["kv_view_groups"] == 2
        rows = [r for r in engine.loop_ring.dump() if "kind" not in r]
        assert rows and len(rows) == s1["decode_steps"] // 4
        columns = KV.pages_for(CFG.seq_len, 8)
        assert decode_ops.view_profiles(2, columns) == ((2, 3), (3, 3))
        assert engine._view_plan == decode_ops.ViewPlan(
            8, columns, 8, groups=2, by_rule=CFG.depth)
        steps = s1["decode_steps"] * CFG.depth       # layers read in all
        assert s1["kv_view_columns_full"] == steps * 8 * columns
        assert s1["kv_view_columns_read"] == steps * (4 * 2 + 4 * 3)
        assert all(r["view_read_pct"] == pytest.approx(100 * 20 / 24)
                   for r in rows)
