"""Names for device time: the closed vocabulary of ``jax.named_scope``s
the device programs carry, and the join from a profiler trace back to it.

A scope put into a jitted program arrives in the compiled HLO as part of
each instruction's ``metadata={op_name="jit(step)/.../ff/dot_general"}``
(a fusion keeps its root's). The profiler's ``XLA Ops`` events are named
by the instruction text WITHOUT that metadata, so the way from a device
event to a scope is: event name -> instruction name -> ``op_name`` ->
scope. ``scopes_of_hlo`` builds the middle of it from the compiled
program's text (``Engine.device_scopes()``, ``parallel.train.step_scopes``);
``POST /admin/profile`` writes the maps beside the capture as
``scopes.json`` and the benchmark's per-scope readers use the same maps
(docs/OBSERVABILITY.md, "Device scopes and the engine loop").

jax is imported only inside ``abstract`` and ``scopes_of_lowered``, which
lower and compile; the rest is text in, dicts out.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

# every string the package gives to jax.named_scope or as a pallas_call's
# name= is one of these (tests/test_obs_device.py greps for it)
SCOPES = (
    "embed",            # token + position embedding
    "norm",             # LayerNorms and RMSNorms
    "attn.proj",        # qkv (or q) and output projections
    "attn.latent",      # latent attention: the kv latent's projection, norm
                        # and RoPE, k_up / v_up (materialised or absorbed)
    "attn.read",        # q.k, mask, softmax, .v computed by XLA
    "attn.flash_fwd",   # the flash kernel, forward
    "attn.flash_bwd",   # its backward: the XLA blockwise one or the kernels
    "attn.sparse_fwd",  # the block-sparse kernel, forward
    "attn.sparse_bwd",  # its XLA backward
    "paged_attn",       # the ragged paged-attention kernel (inside attn.read)
    "kv.view",          # page pool / cache -> per-slot rows
    "kv.store",         # new rows -> cache or pool
    "kv.window",        # a window layer's pool: its ring's page gather and
                        # the new rows' store
    "attn.window",      # a window layer's read of the gathered ring
    "ff",               # the GEGLU block (or its capacity-MoE stand-in),
                        # a described block's dense SiLU-gated layer
    "moe.route",        # dropless routing: router, top-k, sort, combine
    "moe.experts",      # the grouped products over the experts with rows
    "moe.shared",       # the shared experts
    "ssm.proj",         # a state-space layer's four matrix products
    "ssm.scan",         # its convolution, the state's read, update, readout
                        # and write; in prefill the scan over the positions
    "gmu",              # a gated memory unit: its two products and the gate
    "conv.proj",        # a gated short convolution's input and output
                        # projections
    "conv.mix",         # its two gates, the taps, and the tail's read, roll
                        # and store
    "delta.proj",       # a gated delta-rule layer's two input projections
                        # and its output projection
    "delta.rule",       # its convolution and tail, the norms and gates, the
                        # state's read, decay, update, readout and write,
                        # and the gated norm
    "head",             # logits
    "sample",           # filtering and sampling
    "loss",             # cross-entropy
    "optimizer",        # the optax update and its apply
    "prefill.scatter",  # admission's write of state and rows into slots
)
UNSCOPED = "unscoped"
REMAT = "rematted_computation"      # jax.checkpoint's own path element

# ``%fusion.7 = bf16[4,8]{1,0:T(8,128)} fusion(...), ..., metadata={...}``;
# a tuple result gives its first element's shape, as the benchmark's
# ``reduce.short_name`` reads an event's name
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# ``%fused_computation.3 (p0: f32[8]) -> f32[8] {`` opens a computation;
# a fusion names its body ``calls=``, a reduce or scatter its combiner
# ``to_apply=`` (and so does a plain ``call``, whose body does execute)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INLINED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def scope_of_path(op_name: str) -> str:
    """The innermost element of an ``op_name`` path that is a scope or a
    transform's wrapping of one (``jvp(ff)``, ``transpose(jvp(loss))``;
    not ``jit(ff)``, a jitted function's own name), else ``unscoped``.
    Where XLA merged several ops the first path stands for all."""
    path = op_name.split(";", 1)[0]
    for element in reversed(path.split("/")):
        inner = element.rstrip(")").rsplit("(", 1)[-1]
        if inner in SCOPES and not element.startswith(("jit(", "pjit(")):
            return inner
    return UNSCOPED


class _Inst:
    """One parsed instruction: what the resolver below walks over."""
    __slots__ = ("name", "shape", "opcode", "operands", "index", "body",
                 "branches", "op_name", "comp", "root")

    def __init__(self, comp: str, line: str, m):
        self.comp, self.name, self.shape = comp, m.group(1), m.group(2) or ""
        self.root = line.lstrip().startswith("ROOT ")
        rest = line[m.end():]
        op = _OPCODE.search(rest)
        self.opcode = op.group(1) if op else ""
        args = rest[op.end():] if op else ""
        # operands: the %names up to the closing parenthesis of the call
        depth, end = 1, len(args)
        for i, ch in enumerate(args):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        self.operands = _NAME.findall(args[:end])
        idx = _INDEX.search(args[end:])
        self.index = int(idx.group(1)) if idx else None
        body = _BODY.search(args[end:])
        self.body = body.group(1) if body else None
        # a conditional's branch computations, in the order of its
        # operands after the index (or the predicate: true, false)
        listed = _BRANCHES.search(args[end:])
        self.branches = _NAME.findall(listed.group(1)) if listed else [
            m.group(1) for m in (_TRUE.search(args[end:]),
                                 _FALSE.search(args[end:])) if m]
        found = _OP_NAME.search(line)
        self.op_name = found.group(1) if found else ""


_OPCODE = re.compile(r"\s([\w\-]+)\(")
_NAME = re.compile(r"%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TRUE = re.compile(r"\btrue_computation=%?([\w.\-]+)")
_FALSE = re.compile(r"\bfalse_computation=%?([\w.\-]+)")


def _parse(text: str):
    """-> {computation: [instructions]} of the computations whose
    instructions run as operations of their own, in program order."""
    lines = text.splitlines()
    inlined = {m.group(1) for line in lines if " call(" not in line
               for m in _INLINED.finditer(line)}
    comps: Dict[str, list] = {}
    current = None
    for line in lines:
        opened = _COMPUTATION.match(line)
        if opened:
            name = opened.group(1)
            current = None if name in inlined else comps.setdefault(name, [])
            continue
        m = None if current is None else _INSTRUCTION.match(line)
        if m:
            current.append(_Inst(name, line, m))
    return comps


class _Flow:
    """Where a value goes and where it came from, through what the
    compiler adds without a path of its own: copies, bitcasts, tuples,
    the carries of loops and the operands and results of a conditional's
    branches. A walk stops at the first instruction that
    has a scope. A position is (instruction, element): element None for
    an array, k for the k-th element of a tuple-valued instruction."""

    def __init__(self, comps: Dict[str, list], scope_of: Dict[str, str]):
        self.scope_of = scope_of
        self.by_name = {i.name: i for insts in comps.values() for i in insts}
        self.users: Dict[str, list] = {}
        self.param, self.root, self.loop_of = {}, {}, {}
        self.branch_of = {}     # branch computation -> (conditional, nth)
        for comp, insts in comps.items():
            for i in insts:
                for o in i.operands:
                    self.users.setdefault(o, []).append(i)
                if i.opcode == "parameter" and comp not in self.param:
                    self.param[comp] = i
                if i.root:
                    self.root[comp] = i
                if i.opcode == "while" and i.body:
                    self.loop_of[i.body] = i
                for nth, branch in enumerate(i.branches):
                    self.branch_of[branch] = (i, nth)

    def _named(self, inst) -> Optional[str]:
        s = self.scope_of.get(inst.name, UNSCOPED)
        return None if s == UNSCOPED else s

    def resolve(self, inst, limit: int = 64) -> Optional[str]:
        """The scope of the first named instruction ``inst``'s value
        reaches, else of the first one it was made from."""
        for step in (self._forward, self._backward):
            seen, todo = set(), [(inst, None)]
            while todo and len(seen) < limit:
                at = todo.pop(0)
                if (at[0].name, at[1]) in seen:
                    continue
                seen.add((at[0].name, at[1]))
                for nxt in step(*at):
                    if nxt[1] is None and nxt[0] is not inst:
                        named = self._named(nxt[0])
                        if named:
                            return named
                    todo.append(nxt)
        return None

    def _forward(self, inst, k):
        out = []
        for u in self.users.get(inst.name, ()):
            if k is None:
                if u.opcode == "tuple":
                    out += [(u, j) for j, o in enumerate(u.operands)
                            if o == inst.name]
                else:
                    out.append((u, None))
            elif u.opcode == "get-tuple-element" and u.index == k:
                out.append((u, None))
            elif u.opcode == "while" and u.body in self.param:
                out.append((self.param[u.body], k))     # into the loop
            elif u.opcode == "conditional":             # into its branches:
                out += [(self.param[b], k)              # operand n + 1 is
                        for n, b in enumerate(u.branches)   # branch n's
                        if b in self.param and u.operands[n + 1:n + 2]
                        == [inst.name]]
        if inst.root and k is not None and inst.comp in self.loop_of:
            loop = self.loop_of[inst.comp]              # a carry: out of
            out += [(loop, k), (self.param[inst.comp], k)]  # it and around
        if inst.root and inst.comp in self.branch_of:   # a branch's result
            out.append((self.branch_of[inst.comp][0], k))   # is its
        return out                                      # conditional's

    def _backward(self, inst, k):
        ops = [self.by_name[o] for o in inst.operands if o in self.by_name]
        if k is None:
            if inst.opcode == "get-tuple-element":
                return [(o, inst.index) for o in ops[:1]]
            return [(o, None) for o in ops if o.opcode != "tuple"]
        if inst.opcode == "tuple":
            return [(ops[k], None)] if k < len(ops) else []
        if inst.opcode == "while" and inst.body in self.root:
            return [(self.root[inst.body], k)] + [(o, k) for o in ops[:1]]
        if inst.opcode == "conditional":
            return [(self.root[b], k) for b in inst.branches
                    if b in self.root]
        if inst.opcode == "parameter" and inst.comp in self.loop_of:
            loop = self.loop_of[inst.comp]
            return [(self.root[inst.comp], k)] + [
                (self.by_name[o], k) for o in loop.operands[:1]
                if o in self.by_name]
        if inst.opcode == "parameter" and inst.comp in self.branch_of:
            cond, nth = self.branch_of[inst.comp]
            return [(self.by_name[o], k) for o in
                    cond.operands[nth + 1:nth + 2] if o in self.by_name]
        return []


def scopes_of_hlo(text: str) -> Dict[str, dict]:
    """{instruction name: {"scope", "recompute", "op_name", "shape",
    "inherited"}} for every instruction of a compiled program's text
    (``compiled.as_text()``) that can run as an operation of its own: the
    bodies of fusions and the combiners of reduces and scatters are left
    out, loop and branch bodies are in. ``recompute`` is True where the
    path runs through ``jax.checkpoint``'s rematerialized forward.

    Not every instruction has a scope in its own path: what the compiler
    added (a layout copy of the KV pool at a program's entry, the pieces
    of an expanded cumsum) has no path at all, and what ``lax.scan`` does
    itself (a layer's slice of the stacked weights, a residual add
    between two scopes) has one that names no scope. Such an instruction
    takes the scope of the first named instruction its value reaches,
    followed through tuples and loop carries, else of the one it was
    made from, and says so: ``inherited`` True."""
    comps = _parse(text)
    out: Dict[str, dict] = {}
    for insts in comps.values():
        for i in insts:
            if i.opcode in _PLUMBING:
                continue
            out[i.name] = {
                "scope": scope_of_path(i.op_name) if i.op_name else UNSCOPED,
                "recompute": REMAT in i.op_name.split("/"),
                "op_name": i.op_name, "shape": i.shape, "inherited": False}
    flow = _Flow(comps, {n: e["scope"] for n, e in out.items()})
    for insts in comps.values():
        for i in insts:
            if i.name in out and out[i.name]["scope"] == UNSCOPED \
                    and i.opcode not in _CONTAINERS:
                found = flow.resolve(i)
                if found:
                    out[i.name].update(scope=found, inherited=True)
    return out


# never device operations: left out of the map
_PLUMBING = ("parameter", "tuple", "get-tuple-element", "constant")
# their events hold their bodies' operations: kept, never inherited into
_CONTAINERS = ("while", "conditional", "call")


def abstract(tree):
    """``ShapeDtypeStruct``s standing for a tree of arrays in a lowering
    that must come out as the one a call with the arrays themselves
    gets: an array placed by commitment (``device_put`` to a device or a
    mesh, a jit's ``out_shardings``) keeps its sharding, an uncommitted
    one gets none, or the lowering would annotate what the call's does
    not and the compiler would number its instructions otherwise.
    Leaves that are not arrays pass through."""
    import jax

    def one(a):
        if not hasattr(a, "shape") or not hasattr(a, "dtype"):
            return a
        placed = getattr(a, "committed", True)     # a struct: as given
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=getattr(a, "sharding", None) if placed else None)
    return jax.tree.map(one, tree)


def scopes_of_lowered(lowered) -> Dict[str, dict]:
    """``scopes_of_hlo`` of a ``jax.stages.Lowered``, compiled here.

    jax's persistent compile cache leaves metadata out of its key, so an
    executable cached before a scope was added (or moved) is a hit
    afterwards and its text carries the OLD paths; and a lowering equal
    to one this process has already compiled is handed that executable,
    stale or not. This compile puts the metadata into the cache's key
    (for this thread, for this call), so that it can only hit an entry
    made from the same paths, and names a compiler option that changes
    nothing, which jax takes as a reason to compile the lowering anew.
    It costs one compile of the program for each version of the code.
    The compiler is deterministic, so the instruction names are the
    serving program's own."""
    from jax._src import config as jax_config
    with jax_config.compilation_cache_include_metadata_in_key(True):
        compiled = lowered.compile(
            compiler_options={"xla_dump_hlo_as_text": False})
    return scopes_of_hlo(compiled.as_text())
