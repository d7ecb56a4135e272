"""What every described block (``ops.transformer.DescribedBlock``) is held to
at toy widths, written once: a helper module, not a test file.

A new family's test file writes

- a ``Toy``: the family (``benchmark/families/<family>``), the published
  configuration file, the overrides that cut it to a toy, the depth, the
  block's name, the tolerance, the prompt lengths ``t0s`` of the
  prefill-then-decode case, the requests, and a value or ``None`` for
  each case that not every family has (``bf16_misses``, ``chunked``,
  ``evicted``, ``reused``, ``profiles``);
- ``class TestContract(BlockContract): toy = TOY``, with the hooks that
  say what is the family's own in a common case: ``step_loads`` /
  ``chunk_loads`` (what a step's routed load holds), ``engine_counters``
  (its counters in ``Engine.stats()``), ``watch`` (what holds after every
  engine step);
- ``from block_contract import params, sequences, ref_logits, served``
  (the module's fixtures, which read the module's ``TOY``);
- the cases of its own mechanisms (a ring, a sink, a state, a tail, the
  shares of the experts, the absorbed read), on the toy's helpers
  (``prefilled_pool``, ``step_at``, ``serve``, ``tree``, ``apply``,
  ``close``).

It inherits: the program against the family's plain reference at logit
level (and bfloat16 failing that tolerance), prefill then the paged decode
position by position (and the same steps in fused chunks), the engine
serving the reference's greedy tokens in chunks of 8, a reused slot and an
evicted request's replay giving the same tokens, one step of sixteen slots
at each width profile of the ordered pool, every option that cannot run
the block refusing it by the one typed error, and the migration refusal.

A file stays one worker's unit (``--dist loadfile``): the contract is
subclassed in each family's file, never gathered into one file.

Engines: ``served`` builds an engine of one shape once a file, when a case
first asks for it (two slots, all requests together; one slot, one request
after the other; the undersized pool), and the cases assert on what it
recorded. A case that reads a counter from zero builds its own
(``Toy.serve``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, seeds
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.ops import decode as decode_ops
from dalle_pytorch_tpu.ops import transformer as T
from dalle_pytorch_tpu.serve import kv_pool as KV
from dalle_pytorch_tpu.serve.engine import Engine, MigrationError
from dalle_pytorch_tpu.serve.scheduler import (Request, RequestQueue,
                                               SamplingParams)

GREEDY = SamplingParams(filter_thres=1.0)
# prompts of 3, 10 (the whole text window) and 7 tokens: with two slots the
# third request reuses one
REQS = (Request(codes=(3, 7, 9), seed=11, sampling=GREEDY),
        Request(codes=tuple(range(1, 11)), seed=2, sampling=GREEDY),
        Request(codes=(6, 6, 1, 2, 3, 9, 4), seed=3, sampling=GREEDY))


class Toy:
    """One family's toy and the scaffolding every case stands on.

    ``t0s``: the prompt lengths of the prefill-then-decode case.
    ``gap``: how far a served token's reference logit may lie under the
    reference's best (float32 near-ties). ``bf16_misses``: the factor by
    which bfloat16 misses ``atol``. ``chunked``: the pool rows that two
    fused chunks of 8 are compared on, ``((buffer, layer, atol, rtol),
    ...)``. ``evicted``: the requests (indices) served through the
    undersized pool. ``reused``: one slot serves every request in turn.
    ``profiles``: the width profiles that one step of sixteen slots is
    held to. None or False: the family has no such case. ``tree_name``:
    the block that the parameter tree alone tells
    (``transformer.block_name_of``), where its keys are another block's
    too."""

    def __init__(self, family, config, depth, block_name, *, overrides, t0s,
                 seed=2 ** 31 + 13, page_size=4, atol=2e-5, gap=1e-5,
                 requests=REQS, bf16_misses=None, chunked=None, evicted=None,
                 reused=False, profiles=None, tree_name=None):
        self.family = harness.load_family(family)
        self.published = harness.load_json(
            f"{harness.ROOT}/benchmark/configs/{config}.json")
        self.conf = {**self.published, **self.family.tiny, **overrides}
        self.depth, self.block_name, self.seed = depth, block_name, seed
        self.page_size, self.atol, self.gap = page_size, atol, gap
        self.t0s, self.requests = tuple(t0s), tuple(requests)
        self.bf16_misses, self.chunked, self.evicted = (bf16_misses, chunked,
                                                        evicted)
        self.reused, self.profiles = reused, profiles
        self.tree_name = tree_name or block_name
        self.dims = self.dims_of()
        self.cfg = self.family.build.program_config(self.dims, {})
        self.tcfg = self.cfg.transformer
        self.blk = self.tcfg.block
        self.pools = self.blk.pools(depth)      # {cache: its buffers}
        self.width = KV.pages_for(self.dims.seq_len, page_size)
        self.ring = self.blk.ring_pages(page_size, self.dims.seq_len) \
            if "window" in self.pools else 0
        self.layers = {pool: self.blk.cache_layers(pool, depth)
                       for pool in self.pools}
        self._steps = {}        # (slots, ``traced_as``) -> the jitted step

    # -- the weights, the sequences, the reference ----------------------------

    def dims_of(self, depth=None, **kw):
        return self.family.weights.dims_of(dict(self.conf, **kw),
                                           depth or self.depth)

    def tree(self, dims=None, dtype=jnp.float32):
        dims = dims or self.dims
        return jax.jit(lambda h: self.family.weights.tree(h, dims, dtype))(
            seeds.split_seed(self.seed))

    def sequences(self):
        d, rng = self.dims, np.random.default_rng(3)
        return np.concatenate(
            [rng.integers(1, d.num_text_tokens, (2, d.text_seq_len)),
             rng.integers(0, d.num_image_tokens, (2, d.image_seq_len))], 1)

    def ref_logits(self, sequences, dims=None):
        return np.asarray(self.family.reference.served_logits(
            self.seed, dims or self.dims, jnp.float32, sequences.tolist()))

    def close(self, got, want, atol=None):
        fin = np.isfinite(want)
        assert (np.asarray(got)[~fin] < -1e30).all()   # forbidden either way
        np.testing.assert_allclose(
            np.asarray(got)[fin], want[fin], rtol=0,
            atol=self.atol if atol is None else atol)

    def apply(self, params, sequences, cfg=None):
        t = self.dims.text_seq_len
        return D.dalle_apply(params, jnp.asarray(sequences[:, :t]),
                             jnp.asarray(sequences[:, t:-1]),
                             cfg=cfg or self.cfg)

    def logits(self, params, h, pos):
        """The head's logits of hidden rows at positions ``pos`` (one or
        one a row), the forbidden ones -inf."""
        return np.where(np.asarray(D.logits_mask(self.cfg))[pos], -np.inf,
                        np.asarray(D.to_logits(params, h, self.cfg)))

    # -- pools by hand --------------------------------------------------------

    def tables(self, b):
        """Slot i's pages 1 + i * W .. of each page pool (page 0 is the
        trash page): one table, or a table a pool as the engine holds
        them."""
        def table(columns):
            return 1 + jnp.arange(b * columns, dtype=jnp.int32).reshape(
                b, columns)
        if "window" not in self.pools:
            return table(self.width)
        return {"full": table(self.width), "window": table(self.ring)}

    def full_table(self, tables):
        return tables["full"] if isinstance(tables, dict) else tables

    def prefilled_pool(self, params, sequences, t0, upto=None):
        """-> (the prefill's hidden rows, the pool, the tables): the
        prompt's rows [0, t0) of the sequences in the page pools (a full
        layer's row j in page j // PS of the slot's table, a window
        layer's in column (j // PS) % RING of its ring, later rows over
        earlier ones) and each slot's state after its prompt; with
        ``upto`` (b,), slot i's prompt is its first ``upto[i]`` tokens
        alone (padded on the right to t0)."""
        d, ps, b = self.dims, self.page_size, sequences.shape[0]
        tables = self.tables(b)
        pool = dict(KV.init_page_pool(
            self.tcfg, 1 + b * self.width, ps,
            window_pages=1 + b * self.ring if self.ring else 0,
            num_slots=b if "state" in self.pools else 0))
        t = min(t0, d.text_seq_len)
        x = D.embed_prompt(params, self.cfg, jnp.asarray(sequences[:, :t]),
                           jnp.asarray(sequences[:, t:t0]))
        lens = {} if upto is None or "state" not in self.pools \
            else {"lens": jnp.asarray(upto)}
        h, cache = decode_ops.prefill(params["transformer"], x, cfg=self.tcfg,
                                      total_len=d.seq_len, **lens)
        for name in self.pools.get("state", ()):
            shape = self.blk.state_layout(d.dim)[name][0]
            assert cache[name].shape == (len(self.layers["state"]), b) + shape
            assert pool[name].shape == cache[name].shape
            pool[name] = cache[name]
        for which in ("full", "window"):
            for name in self.pools.get(which, ()):
                # a buffer of the pool each, over the layers that store to it
                buf, rows = np.array(pool[name]), np.asarray(cache[name])
                assert rows.shape[:3] == (len(self.layers[which]), b, t0)
                assert buf.shape[0] == rows.shape[0] and buf.shape[3] \
                    == int(np.prod(rows.shape[3:]))
                table = np.asarray(tables[which] if self.ring else tables)
                for i in range(b):
                    for j in range(t0 if upto is None else upto[i]):
                        column = j // ps if which == "full" \
                            else (j // ps) % self.ring
                        buf[:, table[i, column], j % ps] = rows[:, i, j] \
                            .reshape(rows.shape[0], -1)
                pool[name] = jnp.asarray(buf)
        return h, pool, tables

    def teacher_forced(self, params, sequences):
        def embed_fn(tok, pos):
            return D.decode_token_embed(params, self.cfg, tok, pos)

        def sample_fn(_h, pred_pos):
            # the NEXT token of the given sequences, as the loop stores it
            return jnp.take_along_axis(jnp.asarray(sequences),
                                       pred_pos[:, None], axis=1)[:, 0]
        return embed_fn, sample_fn

    def step(self, params, tables, b, active=None, traced_as=None):
        """The decode step of ``b`` slots over ``tables``, jitted:
        (x, pos, pool) -> (hidden, pool, load). ``traced_as``: the key
        under which the traced program is kept for the file's later
        calls of the same shapes (the positions, the tables and the rows
        are values); it names every patch under which the caller stands
        (``switch_placement``, ``reads_at``), since a step is traced
        inside the context it is meant for. None: a program of its own."""
        def program(tp, x, p, pool, tables, key_mask, active):
            return decode_ops.decode_step_block(
                tp, x, p, pool, tables, cfg=self.tcfg, key_mask=key_mask,
                active=active)
        jitted = self._steps.get((b, traced_as)) or jax.jit(program)
        if traced_as is not None:
            self._steps[b, traced_as] = jitted
        key_mask = jnp.ones((b, self.dims.seq_len), bool)
        active = jnp.ones((b,), bool) if active is None else active
        return lambda x, p, pool: jitted(params["transformer"], x, p, pool,
                                         tables, key_mask, active)

    def step_at(self, params, seqs, positions, active=None, traced_as=None):
        """One decode step with slot i at ``positions[i]`` of ``seqs[i]``,
        the rows before it in its pages (its ring as far as it has
        turned), its state after that many tokens -> (the logits,
        forbidden ones -inf; the step's load; where the step's switch
        stands, ``block_view_plan``)."""
        _, pool, tables = self.prefilled_pool(
            params, seqs, int(positions.max()), positions)
        p, b = jnp.asarray(positions), len(positions)
        x = D.decode_token_embed(
            params, self.cfg, jnp.asarray(seqs[np.arange(b), positions]), p)
        plan = decode_ops.block_view_plan(self.tcfg, params["transformer"],
                                          pool, b, self.dims.seq_len)
        h_tok, _, load = self.step(params, tables, b, active, traced_as)(
            x, p, pool)
        return self.logits(params, h_tok, positions), load, plan

    # -- engines --------------------------------------------------------------

    def serve(self, params, reqs, watch=None, **kw):
        """A new engine (two slots unless told) serves ``reqs`` to the
        end, ``watch(engine)`` after every step -> (the engine, each
        request's whole sequence: its prompt and what was served)."""
        queue = RequestQueue(max_depth=16)
        kw.setdefault("num_slots", 2)
        engine = Engine(params, self.cfg, queue, chunk_steps=8, kv="paged",
                        page_size=self.page_size, **kw)
        handles = [queue.submit(dataclasses.replace(r)) for r in reqs]
        while not engine.idle():
            engine.step_once()
            if watch is not None:
                watch(engine)
        out = []
        for r, h in zip(reqs, handles):
            res = h.result(timeout=5)
            assert res.status == "ok"
            out.append(list(np.asarray(res.text_tokens))
                       + list(np.asarray(res.tokens)))
            assert out[-1][:len(r.codes)] == list(r.codes)
        return engine, out

    def engine(self, params, **kw):
        kw.setdefault("kv", "paged")
        return Engine(params, self.cfg, RequestQueue(max_depth=2),
                      num_slots=1, **kw)

    def mesh_engine(self, params):
        from dalle_pytorch_tpu.serve.mesh_engine import MeshEngine
        return MeshEngine(params, self.cfg, RequestQueue(max_depth=2),
                          devices=jax.devices()[:2], num_slots=1, kv="paged")

    def refused(self):
        """{option: params -> the call that must raise}: every path that
        cannot run a described block."""
        cfg, tcfg, d = self.cfg, self.tcfg, self.dims

        def config(**kw):
            return lambda p: dataclasses.replace(cfg, **kw).transformer
        return {
            "kv_dense": lambda p: self.engine(p, kv="dense"),
            "paged_attn_kernel": lambda p: self.engine(
                p, paged_attn="kernel", page_size=8),
            "speculative": lambda p: self.engine(p, speculative=2),
            "sparse_reads": lambda p: self.engine(p, sparse_reads=True),
            "quantize_cache": lambda p: self.engine(p, quantize_cache=True),
            "prefix_cache": lambda p: self.engine(p, prefix_cache=True),
            "mesh_engine": self.mesh_engine,
            "quantize_int8": lambda p: D.quantize_for_decode(p),
            "generate_images": lambda p: D.generate_images(
                p, None, jnp.ones((1, 4), jnp.int32), cfg=cfg,
                rng=jax.random.PRNGKey(0)),
            "train": lambda p: D.dalle_apply(
                p, jnp.ones((1, d.text_seq_len), jnp.int32),
                jnp.ones((1, d.image_seq_len), jnp.int32), cfg=cfg,
                train=True, return_loss=True),
            "reversible": config(reversible=True),
            "sparse_attn": config(sparse_attn=True),
            "attn_impl_flash": config(attn_impl="flash"),
            "remat": config(remat="full"),
            "capacity_moe": config(moe_experts=4),
            "dense_cache": lambda p: decode_ops.init_cache(tcfg, 1, 8),
            "dense_decode_step": lambda p: decode_ops.decode_step(
                p["transformer"], jnp.zeros((1, 32)), 3, {}, cfg=tcfg,
                key_mask=jnp.ones((1, 8), bool)),
            "speculative_loop": lambda p: decode_ops.decode_loop_spec_paged(
                p["transformer"], None, None, None, None, {}, None, cfg=tcfg,
                draft_cfg=None, key_mask=None, total_len=8, steps=1, k=2,
                embed_fn=None, sample_fn=None),
            "kernel_loop": lambda p: decode_ops.decode_loop_paged(
                p["transformer"], None, None, None, {}, None, cfg=tcfg,
                key_mask=None, total_len=8, steps=1, embed_fn=None,
                sample_fn=None, attn_impl="kernel"),
            "int8_pool": lambda p: KV.init_page_pool(
                tcfg, 4, self.page_size, quantized=True, num_slots=1),
        }


@dataclasses.dataclass
class Run:
    """What one engine left: itself, its counters at the end, and each
    request's whole sequence."""
    engine: Engine
    stats: dict
    seqs: list


class Served:
    """The toy's requests through an engine of each shape, each built and
    run when a case first asks for it and once a file."""

    def __init__(self, toy, params):
        self.toy, self.params, self._runs = toy, params, {}

    def _run(self, key, reqs, watch=None, **kw):
        if key not in self._runs:
            engine, seqs = self.toy.serve(self.params, reqs, watch, **kw)
            self._runs[key] = Run(engine, engine.stats(), seqs)
        return self._runs[key]

    def together(self, placement=None, watch=None):
        """Two slots, every request at once (the last ones reuse a slot).
        ``placement``: the ``switch_placement`` (conftest.py) that the
        asking case runs under; an engine is traced where its switch
        stands, so there is one a placement, and a case that names none
        gets the toy shapes' own."""
        return self._run(("together", placement or "one_switch"),
                         self.toy.requests, watch)

    def one_slot(self):
        """One slot, one request after the other: the first in a new
        engine, each later one in the slot its predecessor left."""
        return self._run("one_slot", self.toy.requests, num_slots=1)

    def tight(self):
        """Two slots over a pool of one sequence and a bit."""
        reqs = [self.toy.requests[i] for i in self.toy.evicted]
        return self._run("tight", reqs, num_pages=self.toy.width + 4)


# -- the fixtures of a family's file (they read its ``TOY``) ------------------

@pytest.fixture(scope="module")
def params(request):
    return request.module.TOY.tree()


@pytest.fixture(scope="module")
def sequences(request):
    return request.module.TOY.sequences()


@pytest.fixture(scope="module")
def ref_logits(request, sequences):
    return request.module.TOY.ref_logits(sequences)


@pytest.fixture(scope="module")
def served(request, params):
    return Served(request.module.TOY, params)


class BlockContract:
    """The cases every described block has; ``toy`` says which of the
    optional ones, and the hooks what is the family's own in them."""
    toy: Toy = None

    def __init_subclass__(cls):
        toy = cls.toy
        for case, has in (
                ("test_bfloat16_fails_the_tolerance", toy.bf16_misses),
                ("test_a_reused_slot_serves_a_new_engine_s_tokens",
                 toy.reused),
                ("test_an_evicted_request_replays_to_the_same_tokens",
                 toy.evicted),
                ("test_slots_up_to_each_width_profile_match_the_full_forward",
                 toy.profiles)):
            if not has:
                setattr(cls, case, None)        # not collected

    def pytest_generate_tests(self, metafunc):
        for name, values in (("t0", self.toy.t0s),
                             ("option", sorted(self.toy.refused())),
                             ("at", self.toy.profiles)):
            if name in metafunc.fixturenames:
                metafunc.parametrize(name, values)

    # -- hooks ----------------------------------------------------------------

    def step_loads(self, loads, b, t0):
        """What the decode steps' loads hold (one a position from t0)."""

    def chunk_loads(self, loads, b):
        """What two fused chunks' loads hold."""

    def engine_counters(self, engine, st, placement):
        """The family's own counters after the requests are served."""

    watch = None        # (engine) -> None, after every engine step

    def together(self, served, placement=None):
        return served.together(placement, self.watch)

    # -- (i) the full forward against the reference ---------------------------

    def test_dalle_apply_matches_the_reference_logits(self, params,
                                                      sequences, ref_logits):
        self.toy.close(self.toy.apply(params, sequences), ref_logits)

    def test_bfloat16_fails_the_tolerance(self, sequences, ref_logits):
        """The same program with its weights and its arithmetic in
        bfloat16 misses the float32 tolerance by orders of magnitude: the
        comparison is tight enough to tell the precisions apart."""
        toy = self.toy
        got = np.asarray(toy.apply(toy.tree(dtype=jnp.bfloat16), sequences),
                         np.float32)
        fin = np.isfinite(ref_logits)
        assert np.abs(got[fin] - ref_logits[fin]).max() \
            > toy.bf16_misses * toy.atol

    # -- (ii) prefill, then the paged gather decode ---------------------------

    def test_prefill_then_paged_decode_matches_the_full_forward(
            self, params, sequences, ref_logits, t0):
        toy, d = self.toy, self.toy.dims
        h, pool, tables = toy.prefilled_pool(params, sequences, t0)
        b = sequences.shape[0]
        toy.close(toy.logits(params, h[:, -1], t0 - 1),
                  ref_logits[:, t0 - 1])            # the prefill's own row
        # position by position to the sequence's end, logits against the
        # reference's full forward
        step = toy.step(params, tables, b, traced_as="every_slot_active")
        step_pool, loads = pool, []
        for pos in range(t0, d.seq_len - 1):
            p = jnp.full((b,), pos, jnp.int32)
            x = D.decode_token_embed(params, toy.cfg,
                                     jnp.asarray(sequences[:, pos]), p)
            h_tok, step_pool, load = step(x, p, step_pool)
            toy.close(toy.logits(params, h_tok, pos), ref_logits[:, pos])
            loads.append(load)
        self.step_loads(loads, b, t0)
        if not toy.chunked:
            return
        # the same steps in chunks of 8 write the same pools and count alike
        embed_fn, sample_fn = toy.teacher_forced(params, sequences)
        cur, p = jnp.asarray(sequences[:, t0]), jnp.full((b,), t0, jnp.int32)
        chunk_pool, loads = pool, []
        for _ in range(2):
            cur, p, _, chunk_pool, ring, load = decode_ops.decode_loop_paged(
                params["transformer"], cur, p, jnp.ones((b,), bool),
                chunk_pool, tables, cfg=toy.tcfg,
                key_mask=jnp.ones((b, d.seq_len), bool), total_len=d.seq_len,
                steps=8, embed_fn=embed_fn, sample_fn=sample_fn)
            loads.append(load)
        self.chunk_loads(loads, b)
        np.testing.assert_array_equal(np.asarray(ring)[:, -1],
                                      sequences[:, t0 + 15])
        # the full pool's rows t0 .. t0 + 16 (the stepwise pool went on to
        # the end; two compiled programs round a row in another order)
        for name, layer, atol, rtol in toy.chunked:
            live, want = (np.asarray(decode_ops.layer_pool_view(
                pl[name], jnp.int32(layer), toy.full_table(tables))).reshape(
                    b, -1, pl[name].shape[-1]) for pl in (chunk_pool,
                                                          step_pool))
            np.testing.assert_allclose(live[:, t0:t0 + 16],
                                       want[:, t0:t0 + 16], atol=atol,
                                       rtol=rtol)

    def test_slots_up_to_each_width_profile_match_the_full_forward(
            self, params, sequences, ref_logits, profile_positions, reads_at,
            at, four_slots_a_group, switch_placement):
        """ISSUE 38: the reads of the pool whose rows lie in order stop at
        the rows that are written. Sixteen slots in shuffled phase order
        whose positions need profile ``at`` of the table's staircases (in
        every group a slot AT its width's edge, one a row before it, one
        a row after the edge of the group before; a slot at 1; the last
        row at the last profile): one step by the rule gives the
        reference's full-forward logits at every slot's own position, and
        the greedy tokens of the same step at full width; the profile
        before (the planted fault) fails the same comparison."""
        toy, ps = self.toy, self.toy.page_size
        page = (ps, toy.blk.buffer_row_width(toy.pools["full"][0]))
        assert decode_ops.view_slot_groups(16, toy.width, page,
                                           jnp.float32) == 4
        profiles = decode_ops.view_profiles(4, toy.width)
        assert len(profiles) == 4
        positions = profile_positions(profiles[at], ps, toy.dims.seq_len - 1)
        if "state" in toy.pools:
            # (a state after no token at all is no prompt's: the slot
            # parked at 0 is the classic pool's case)
            positions = np.where(positions == 0, 2, positions)
        assert int(decode_ops.view_profile_index(
            np.sort(positions), 4, toy.width, ps, xp=np)) == at
        if toy.ring:        # some rings unwrapped, some wrapped
            assert (positions < toy.ring * ps).sum() >= 2 <= (
                positions > toy.ring * ps).sum()
        rows = np.arange(len(positions)) % len(sequences)
        seqs, want = sequences[rows], ref_logits[rows, positions]
        got, _, plan = toy.step_at(params, seqs, positions,
                                   traced_as=(switch_placement, "by_rule"))
        toy.close(got, want)
        # where the switch stands (``block_view_plan``): one around the
        # span of scans that read the ordered pool, every reader at the
        # profile; or one a scanned read, a run of one layer whole
        readers = [r for r in T.layer_runs(toy.blk, toy.depth)
                   if r.kind.pool == "full"]
        lone = sum(r.count for r in readers if r.count == 1)
        assert lone and plan.groups == 4
        if switch_placement == "one_switch":
            assert plan.span is not None and plan.whole == 0
        else:
            assert plan.span is None and plan.whole == lone
        assert plan.by_rule + plan.whole == sum(r.count for r in readers)
        with reads_at("full_width"):
            whole, _, _ = toy.step_at(
                params, seqs, positions,
                traced_as=(switch_placement, "full_width"))
        toy.close(whole, want)
        np.testing.assert_array_equal(got.argmax(-1), whole.argmax(-1))
        if at:
            with reads_at("too_narrow"):
                cut, _, _ = toy.step_at(
                    params, seqs, positions,
                    traced_as=(switch_placement, "too_narrow"))
            with pytest.raises(AssertionError):
                toy.close(cut, want)

    # -- (iii) the engine -----------------------------------------------------

    def engine_serves(self, run, placement=None):
        """Greedy tokens are the reference's best at every served position
        (gap 0 but for float32 near-ties); one decode program; every page
        back; the pool's modeled bytes; then the family's counters."""
        toy, d = self.toy, self.toy.dims
        lens = [len(r.codes) for r in toy.requests]
        assert all(len(s) == d.seq_len for s in run.seqs)
        gaps, served = toy.family.reference.served_gaps(
            toy.seed, d, jnp.float32, run.seqs, lens)
        assert float(np.asarray(gaps)[np.asarray(served)].max()) < toy.gap
        engine, st = run.engine, run.stats
        assert engine.decode_traces == 1 and engine.alloc.in_use == 0
        assert (engine.window is None) == (not toy.ring)
        assert not toy.ring or engine.window.alloc.in_use == 0
        assert st["kv_hbm_bytes"] == KV.modeled_kv_bytes(
            toy.tcfg, kv="paged", num_slots=2, total_len=d.seq_len,
            page_size=toy.page_size)
        if getattr(d, "moe_layers", 0):
            assert st["moe_picks"] == (
                st["decode_steps"] * engine.num_slots
                * toy.blk.experts_per_token * d.moe_layers)
        else:
            assert "moe_picks" not in st
        self.engine_counters(engine, st, placement)

    def test_engine_serves_the_reference_s_tokens_in_chunks_of_8(self,
                                                                 served):
        """Through the engine: prompts of several lengths admitted in one
        bucket, admission's whole-page write into the pools, slots
        reused, the fused chunks."""
        self.engine_serves(self.together(served))

    def test_a_reused_slot_serves_a_new_engine_s_tokens(self, served):
        """One slot, every request one after the other: each starts in a
        slot whose rows and state its predecessor left, and serves what
        it is served beside the others in two slots (the first of which
        are new)."""
        assert served.one_slot().seqs == self.together(served).seqs

    def test_an_evicted_request_replays_to_the_same_tokens(self, served):
        toy, tight = self.toy, served.tight()
        roomy = self.together(served)
        assert tight.seqs == [roomy.seqs[i] for i in toy.evicted]
        assert tight.engine.evicted > 0 and roomy.engine.evicted == 0
        assert tight.engine.alloc.in_use == 0
        if toy.ring:
            assert tight.engine.window.alloc.in_use == 0
            assert tight.engine.window.alloc.num_pages \
                < roomy.engine.window.alloc.num_pages

    # -- (iv) every path that cannot run the block refuses it -----------------

    def test_every_refused_option_raises_the_one_typed_error(self, params,
                                                             option):
        toy = self.toy
        with pytest.raises(T.BlockOptionError) as e:
            toy.refused()[option](params)
        # (quantizing sees the parameter tree and nothing else)
        name = toy.tree_name if option == "quantize_int8" else toy.block_name
        assert toy.blk.name == toy.block_name and toy.tree_name \
            == T.block_name_of(params["transformer"])
        assert e.value.block == name and e.value.option
        assert name in str(e.value) and e.value.option in str(e.value)

    @pytest.mark.parametrize("call", ["export", "import"])
    def test_migration_refuses_the_block_and_falls_back_to_replay(
            self, params, call):
        """A MIGRATE frame's callers catch ``MigrationError`` and replay:
        the refusal is that error, naming the block and the option."""
        engine = self.toy.engine(params, page_size=self.toy.page_size)
        with pytest.raises(MigrationError, match=self.toy.block_name
                           + ".*export/import") as e:
            engine.export_slot(0) if call == "export" \
                else engine.import_slot({"weights_version": "0"})
        assert e.value.reason == "block"
