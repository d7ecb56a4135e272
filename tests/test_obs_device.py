"""Device scopes (ISSUE 24): the closed vocabulary of ``jax.named_scope``s
in the device programs, and the join from a compiled program's text back
to it (``obs/device.py``, ``Engine.device_scopes``,
``parallel.train.step_scopes``).

All CPU, tiny programs. What is checked here is what a trace's reader
relies on: every instruction that can take device time lands under a
scope of ``SCOPES``, the ones the compiler added inherit one through the
dataflow, recomputation is flagged, and no scope string in the package
is outside the vocabulary.
"""

import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import dalle_pytorch_tpu
from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models import vae as V
from dalle_pytorch_tpu.obs import device as odev
from dalle_pytorch_tpu.parallel.train import make_train_step, step_scopes
from dalle_pytorch_tpu.serve import Request, RequestQueue
from dalle_pytorch_tpu.serve.engine import Engine

VCFG = V.VAEConfig(image_size=16, num_tokens=24, codebook_dim=16,
                   num_layers=2, hidden_dim=8)
CFG = D.DALLEConfig(dim=32, depth=2, vae=VCFG, num_text_tokens=50,
                    text_seq_len=8, heads=2, dim_head=16,
                    sparse_attn=(True, False))
# what can take device time; plumbing (tuples, parameters) never does
HEAVY = re.compile(r"fusion|^dot|^gather|^scatter|^copy|^custom-call"
                   r"|^convolution")


# -- the path parser -----------------------------------------------------------

@pytest.mark.parametrize("path, scope", [
    ("jit(step)/jvp(ff)/tanh", "ff"),
    ("jit(step)/transpose(jvp(loss))/mul", "loss"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/ff/mul",
     "ff"),
    ("jit(step)/jvp()/while/body/closed_call/ff/norm/rsqrt", "norm"),
    ("jit(_decode_impl_paged)/while/body/closed_call/kv.view/jit(_take)"
     "/gather", "kv.view"),
    ("jit(step)/optimizer/mul;jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/jvp()/while/body/dynamic_slice", "unscoped"),
    ("reduce_window_sum", "unscoped"),
    ("jit(ff)/add", "unscoped"),      # a jitted function's name is no scope
])
def test_scope_of_path_takes_the_innermost_scope(path, scope):
    assert odev.scope_of_path(path) == scope


HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[8]}

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%p0), metadata={op_name="jit(step)/ff/tanh"}
}

%add_combiner (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body (carry: (s32[], f32[8], f32[4,8])) -> (s32[], f32[8], f32[4,8]) {
  %carry = (s32[], f32[8]{0}, f32[4,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%carry), index=0
  %h = f32[8]{0} get-tuple-element(%carry), index=1
  %pool = f32[4,8]{1,0} get-tuple-element(%carry), index=2
  %gather.3 = f32[8]{0} gather(%pool, %i), metadata={op_name="jit(step)/while/body/kv.view/jit(_take)/gather"}
  %fusion.7 = f32[8]{0:T(8)} fusion(%gather.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/checkpoint/rematted_computation/ff/tanh" source_file="x.py"}
  %dynamic-update-slice.2 = f32[4,8]{1,0} dynamic-update-slice(%pool, %fusion.7, %i), metadata={op_name="jit(step)/while/body/kv.store/dynamic_update_slice"}
  ROOT %tuple.5 = (s32[], f32[8]{0}, f32[4,8]{1,0}) tuple(%i, %fusion.7, %dynamic-update-slice.2)
}

%cond (carry.1: (s32[], f32[8], f32[4,8])) -> pred[] {
  %carry.1 = (s32[], f32[8]{0}, f32[4,8]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

%narrow (arg.1: (f32[8], f32[4,8])) -> (f32[8], f32[2,8]) {
  %arg.1 = (f32[8]{0}, f32[4,8]{1,0}) parameter(0)
  %rows.1 = f32[4,8]{1,0} get-tuple-element(%arg.1), index=1
  %slice.1 = f32[2,8]{1,0} slice(%rows.1), slice={[0:2], [0:8]}
  %q.1 = f32[8]{0} get-tuple-element(%arg.1), index=0
  %dot.1 = f32[8]{0} dot(%q.1, %slice.1), metadata={op_name="jit(step)/cond/branch_0_fun/attn.read/dot_general"}
  %stack.1 = f32[2,8]{1,0} dynamic-update-slice(%slice.1, %dot.1)
  ROOT %tuple.6 = (f32[8]{0}, f32[2,8]{1,0}) tuple(%dot.1, %stack.1)
}

%wide (arg.2: (f32[8], f32[4,8])) -> (f32[8], f32[2,8]) {
  %arg.2 = (f32[8]{0}, f32[4,8]{1,0}) parameter(0)
  %rows.2 = f32[4,8]{1,0} get-tuple-element(%arg.2), index=1
  %q.2 = f32[8]{0} get-tuple-element(%arg.2), index=0
  %dot.2 = f32[8]{0} dot(%q.2, %rows.2), metadata={op_name="jit(step)/cond/branch_1_fun/attn.read/dot_general"}
  %stack.2 = f32[2,8]{1,0} broadcast(%dot.2)
  ROOT %tuple.7 = (f32[8]{0}, f32[2,8]{1,0}) tuple(%dot.2, %stack.2)
}

ENTRY %main (x: f32[8], cache: f32[4,8]) -> f32[4,8] {
  %x = f32[8]{0} parameter(0)
  %cache = f32[4,8]{1,0} parameter(1), metadata={op_name="cache"}
  %zero = s32[] constant(0)
  %copy.1 = f32[4,8]{0,1} copy(%cache)
  %reduce.4 = f32[] reduce(%x, %zero), dimensions={0}, to_apply=%add_combiner, metadata={op_name="reduce_window_sum"}
  %tuple.1 = (s32[], f32[8]{0}, f32[4,8]{0,1}) tuple(%zero, %x, %copy.1)
  %while.1 = (s32[], f32[8]{0}, f32[4,8]{0,1}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  %get-tuple-element.8 = f32[4,8]{0,1} get-tuple-element(%while.1), index=2
  %multi.1 = (f32[2,3]{1,0}, s32[]) custom-call(%x), custom_call_target="Foo"
  %copy.3 = f32[4,8]{1,0} copy(%cache)
  %tuple.8 = (f32[8]{0}, f32[4,8]{1,0}) tuple(%x, %copy.3)
  %conditional.1 = (f32[8]{0}, f32[2,8]{1,0}) conditional(%zero, %tuple.8, %tuple.8), branch_computations={%narrow, %wide}, metadata={op_name="jit(step)/cond"}
  %get-tuple-element.9 = f32[2,8]{1,0} get-tuple-element(%conditional.1), index=1
  %store.1 = f32[2,8]{1,0} negate(%get-tuple-element.9), metadata={op_name="jit(step)/kv.store/neg"}
  ROOT %copy.2 = f32[4,8]{1,0} copy(%get-tuple-element.8)
}
"""


def test_scopes_of_hlo_on_a_small_text():
    m = odev.scopes_of_hlo(HLO)
    # fusion bodies, combiners and plumbing are not operations of their own
    assert {"tanh.1", "add.9", "p0", "carry", "tuple.5", "zero", "x"} \
        .isdisjoint(m)
    assert m["fusion.7"] == {
        "scope": "ff", "recompute": True, "shape": "f32[8]",
        "inherited": False, "op_name":
        "jit(step)/while/body/checkpoint/rematted_computation/ff/tanh"}
    assert m["gather.3"]["scope"] == "kv.view"
    assert not m["gather.3"]["recompute"]
    # a tuple result gives its first element's shape, as the trace's reader
    assert m["multi.1"]["shape"] == "f32[2,3]"
    # the compiler's own copy of the pool at entry reaches, through the
    # tuple and the loop's carry, the gather that reads it ...
    assert m["copy.1"] == {"scope": "kv.view", "recompute": False,
                           "op_name": "", "shape": "f32[4,8]",
                           "inherited": True}
    # ... and the one at exit was made from the loop's stored pool
    assert m["copy.2"]["scope"] == "kv.store" and m["copy.2"]["inherited"]
    # a conditional's branches are computations of their own, and a value
    # is followed through them (PR 38: a step's layers run inside one):
    # what a branch stacks for a store outside it reaches that store
    # through the branch's result, the entry's copy reaches a branch's
    # read through the conditional's operand
    assert m["dot.1"]["scope"] == m["dot.2"]["scope"] == "attn.read"
    assert m["stack.1"] == {"scope": "kv.store", "recompute": False,
                            "op_name": "", "shape": "f32[2,8]",
                            "inherited": True}
    assert m["stack.2"]["scope"] == "kv.store"
    assert m["copy.3"]["scope"] == "attn.read" and m["copy.3"]["inherited"]
    assert m["slice.1"]["scope"] == "attn.read"
    # an expansion that lost its path and reaches nothing stays unscoped,
    # and a loop or a conditional is never given its body's scope
    assert m["reduce.4"]["scope"] == "unscoped"
    assert m["while.1"]["scope"] == "unscoped"
    assert m["conditional.1"]["scope"] == "unscoped"


# -- the programs --------------------------------------------------------------

def coverage(scopes: dict):
    """(share of heavy instructions under a scope, share under one by
    their own path, the scopes seen)."""
    heavy = [e for n, e in scopes.items() if HEAVY.search(n)]
    named = [e for e in heavy if e["scope"] != odev.UNSCOPED]
    own = [e for e in named if not e["inherited"]]
    return (len(named) / len(heavy), len(own) / len(heavy),
            {e["scope"] for e in named})


@pytest.fixture(scope="module")
def params():
    return D.dalle_init(jax.random.PRNGKey(0), CFG)


def engine_of(params, kv, **kw):
    queue = RequestQueue(max_depth=8, max_prompt_len=CFG.text_seq_len)
    paged = {"page_size": 8} if kv == "paged" else {}
    return Engine(params, CFG, queue, num_slots=3, chunk_steps=4, kv=kv,
                  **paged, **kw), queue


@pytest.mark.parametrize("kv", ["paged", "dense"])
def test_decode_and_prefill_programs_are_scoped(params, kv):
    engine, _ = engine_of(params, kv)
    maps = engine.device_scopes(buckets=[8])
    decode = "_decode_impl_paged" if kv == "paged" else "_decode_impl"
    assert set(maps) == {decode, "prefill_b8"}
    expect = {"embed", "norm", "attn.proj", "attn.read", "kv.store", "ff",
              "head", "sample"}
    for name, view in ((decode, {"kv.view"} if kv == "paged" else set()),
                       ("prefill_b8", {"prefill.scatter"})):
        share, own, seen = coverage(maps[name])
        seen |= {"kv.store"} if name == "prefill_b8" else set()  # fused away
        assert share >= 0.95, (name, share)
        # on the CPU most of the rest are the compiler's own small copies
        assert own >= 0.40, (name, own)
        assert seen >= expect | view, (name, expect | view - seen)
        assert seen <= set(odev.SCOPES)
    # lowering the programs again from shapes is no retrace of the
    # serving path, and an engine's maps are made once
    assert engine.decode_traces == 0 and engine.prefill_traces == 0
    assert engine.device_scopes(buckets=[8])[decode] is maps[decode]


def test_device_scopes_of_a_served_engine_names_what_it_built(params):
    engine, queue = engine_of(params, "paged", prefix_cache=True)
    for seed in (1, 2):                     # a cold then a warm admission
        queue.submit(Request(codes=(3, 7, 9, 1, 2, 4, 6, 8), seed=seed))
        engine.run_until_idle()
    counts = (engine.decode_traces, engine.prefill_traces,
              engine.warm_admit_traces)
    maps = engine.device_scopes()
    assert set(maps) == {"_decode_impl_paged", "prefill_b8", "warm_admit"}
    assert coverage(maps["warm_admit"])[2] >= {"head", "sample",
                                               "prefill.scatter"}
    assert counts == (engine.decode_traces, engine.prefill_traces,
                      engine.warm_admit_traces) == (1, 1, 1)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_train_step_is_scoped_and_recomputation_flagged(params, remat):
    import dataclasses
    cfg = dataclasses.replace(CFG, remat=remat)
    optimizer = optax.adam(1e-3)

    def loss_fn(p, batch, rng):
        return D.dalle_apply(p, batch["text"], batch["image"], cfg=cfg,
                             rng=rng, train=True, return_loss=True)

    step = make_train_step(loss_fn, optimizer)
    batch = {"text": jnp.ones((2, CFG.text_seq_len), jnp.int32),
             "image": jnp.ones((2, CFG.image_seq_len), jnp.int32)}
    scopes = step_scopes(step, params, optimizer.init(params), batch,
                         jax.random.PRNGKey(0))
    share, own, seen = coverage(scopes)
    assert share >= 0.95 and own >= 0.40, (share, own)
    assert seen >= {"embed", "norm", "attn.proj", "attn.read", "ff", "head",
                    "loss", "optimizer"}
    recomputed = {e["scope"] for e in scopes.values() if e["recompute"]}
    if remat == "full":
        assert recomputed >= {"ff", "attn.proj", "norm"}
    else:
        assert not recomputed
    # the backward of a scope reads as the scope
    assert any("transpose(jvp" in e["op_name"] and e["scope"] == "ff"
               for e in scopes.values())


def test_flash_and_block_sparse_kernels_are_scoped():
    import dataclasses
    cfg = dataclasses.replace(CFG, attn_impl="flash", sparse_impl="pallas")
    params = D.dalle_init(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, batch, rng):
        return D.dalle_apply(p, batch["text"], batch["image"], cfg=cfg,
                             rng=rng, train=True, return_loss=True)

    optimizer = optax.adam(1e-3)
    step = make_train_step(loss_fn, optimizer)
    batch = {"text": jnp.ones((1, cfg.text_seq_len), jnp.int32),
             "image": jnp.ones((1, cfg.image_seq_len), jnp.int32)}
    scopes = step_scopes(step, params, optimizer.init(params), batch,
                         jax.random.PRNGKey(0))
    seen = {e["scope"] for e in scopes.values()}
    assert seen >= {"attn.flash_fwd", "attn.flash_bwd", "attn.sparse_fwd",
                    "attn.sparse_bwd"}


# -- the vocabulary is closed --------------------------------------------------

def package_sources():
    root = os.path.dirname(dalle_pytorch_tpu.__file__)
    for base, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    yield os.path.relpath(path, root), fh.read()


def pallas_calls(text: str):
    """The argument text of each ``pl.pallas_call(...)`` in a source."""
    for m in re.finditer(r"pl\.pallas_call\(", text):
        depth, i = 1, m.end()
        while depth:
            depth += (text[i] == "(") - (text[i] == ")")
            i += 1
        yield text[m.end():i - 1]


def test_every_scope_and_kernel_name_in_the_package_is_in_SCOPES():
    scope = re.compile(r"named_scope\(\s*([^)]*?)\s*\)")
    used, kernels = set(), 0
    for rel, text in package_sources():
        for m in scope.finditer(text):
            arg = m.group(1)
            assert re.fullmatch(r'"[\w.]+"', arg), \
                f"{rel}: named_scope({arg}) is not a literal of SCOPES"
            used.add(arg.strip('"'))
        for call in pallas_calls(text):
            name = re.search(r'\bname=("[\w.]+")', call)
            assert name, f"{rel}: a pallas_call passes no literal name="
            used.add(name.group(1).strip('"'))
            kernels += 1
    assert used == set(odev.SCOPES), used ^ set(odev.SCOPES)
    assert kernels == 7
