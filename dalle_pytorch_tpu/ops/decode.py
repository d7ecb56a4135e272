"""Incremental decoding engine: prefill + single-token step with a KV cache.

The reference samples by re-running the FULL forward for every generated
token with no KV cache — O(seq²) attention per step, O(seq³) per image
(reference dalle_pytorch/dalle_pytorch.py:332-337). This module is the
TPU-native replacement demanded by the north star: a fixed-shape, on-device
cache so the whole sampling loop jit-compiles into one XLA program
(models/dalle.py drives it with ``lax.scan``).

Design:
  * ``init_cache`` allocates (depth, b, heads, total_len, dim_head) K/V
    buffers once; every step writes one row — no dynamic shapes anywhere.
  * ``prefill`` runs the prompt through the stack in one batched pass (the
    queries span [0, t0)), filling cache rows [0, t0).
  * ``decode_step`` advances one position: the new token's q attends to the
    cached rows plus itself (its K/V row is concatenated as a 1-wide extra
    logit, then written back after the layer scan — so the cache is never
    read-after-written inside a step).
  * Both execution engines are supported, because generation must run the
    SAME computation the model was trained with: sequential residual layers,
    or the two-stream reversible forward whose output is the stream mean
    (reference reversible.py:149-157 — numerically different from
    sequential).
  * Per-layer dense/block-sparse selection works in the cache too: a sparse
    layer's query at position p sees keys allowed by row p of the
    (total_len, total_len) VariableSparsity token layout (ops.sparse).

No dropout: decoding is eval-mode by contract (the reference wraps
generate_images in eval_decorator, reference dalle_pytorch.py:30-36,318).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dalle_pytorch_tpu.ops import attention as attn_ops
from dalle_pytorch_tpu.ops import core, sparse
from dalle_pytorch_tpu.ops.moe import load_width

Array = jax.Array


# the most gathered pages that the TPU compiler was seen to keep in VMEM
# (of v5e's 128 MiB) from a gather to its readers: 89 MB yes, 178 MB no
# (AOT compiles, PERF.md section 6, PR 27)
_VIEW_VMEM_BYTES = 96 << 20
# what halving a slot group must save to pay for the further group, in
# gathered bytes (``view_slot_groups``): a group costs about 4.5 us
# whatever it reads (a gather's start is 2.5-3 us, its products' starts
# the rest), which the memory moves 3.5 MB in. On the chip (`tpot_ms`, one
# seed a cell, PERF.md section 6, PR 38): ruDALL-E (16 slots x 4.7 MB of
# table: halving groups of eight saves 4.7 MB a group) 9.952 at four slots
# a group, 10.025 at eight, 10.529 without the rule; kanana (32 slots x
# 5.6 MB: 2.8 MB a group) 13.751 at four, 13.494 at eight, 14.104 without.
# The constant lies between the two
_VIEW_GROUP_BYTES = 7 << 19
# the ladder of table widths (``view_widths``): a step is an eighth of
# the table, so a group reads at most that much past its furthest slot
_VIEW_WIDTH_STEPS = 8
# the staircases of widths a step chooses from, before the whole table
# (``view_profiles``), each one more copy of the switched layers to
# compile. Over the 704 dispatches of a ruDALL-E window the furthest slots
# stood 0 / 1 / 2 ladder steps out of the even staircase in 20 / 73 / 6%
# of the chunks and further in none (PERF.md section 6, PR 38): three hold
# them all
_VIEW_PROFILES = 3
# what the ONE switch of a described block's step may hand out of its
# branches (``block_view_plan``): it is written and read once more every
# step, 0.16 ms at the memory bandwidth
_VIEW_SWITCH_BYTES = 64 << 20
_TILE = (8, 128)    # a TPU tile: 8 rows of 128 lanes of 4-byte words


def _tile_of(dtype) -> Tuple[int, int]:
    """(rows, lanes) of one TPU tile of ``dtype``: 8 rows of four-byte
    words (16 bf16 rows, 32 int8 rows) by 128 lanes."""
    return (_TILE[0] * max(4 // jnp.dtype(dtype).itemsize, 1), _TILE[1])


def _halving_pays(per: int, slots: int, slot_bytes: int) -> bool:
    """Whether two groups of ``per`` / 2 read enough less than one of
    ``per`` (of ``slots`` evenly staggered, ``slot_bytes`` a slot's whole
    table): (per / 2) ** 2 / slots of a table."""
    return per * per * slot_bytes > 4 * slots * _VIEW_GROUP_BYTES


def view_slot_groups(slots: int, columns: int, page_shape, dtype,
                     ordered: bool = True) -> int:
    """The group rule of the paged gather reads (``_read_in_slot_groups``):
    the fewest equal groups of ``slots`` that satisfy both of

      * VMEM: a group's gathered pages, ONE buffer of one layer (K's
        readers finish before V's gather starts, ``_paged_gather_read``),
        stay under ``_VIEW_VMEM_BYTES`` at the table's full width; one
        slot a group where no divisor fits;
      * the ordering: a group is consecutive slots in the order of their
        positions and reads the width its FURTHEST slot needs
        (``view_profiles``). Of ``slots`` evenly staggered over the table
        a group of n spans n / slots of it, and halved, its nearer half
        stops n / 2 phases short: n / 2 slots x n / 2 x columns / slots
        columns fewer. A group is halved while that saves more bytes than
        the further group costs (``_VIEW_GROUP_BYTES``). (Not ``ordered``:
        a table that is read whole in slot order, a window ring or a
        sparse layer's visible columns, which VMEM alone decides.)

    It reads what a trace sees and nothing else: the slots, the table's
    ``columns``, a page's shape (rows, width: ``kv_pool.page_layout``'s,
    the same in every block) and the pool's dtype. The bytes are counted
    AS LAID OUT: the width is filled to whole 128-lane tiles (the classic
    block's ``heads * dim_head`` is whole tiles at every published head
    size) and the rows to whole tiles of 8 words (16 bf16 rows, 32 int8
    rows: the int8 pool's 16-row page takes the room of the bf16 one)."""
    itemsize = jnp.dtype(dtype).itemsize
    filled = [-(-n // t) * t for n, t in zip(page_shape, _tile_of(dtype))]
    slot_bytes = columns * math.prod(filled) * itemsize
    return next((g for g in range(1, slots) if slots % g == 0
                 and slots // g * slot_bytes <= _VIEW_VMEM_BYTES
                 and not (ordered and _halving_pays(
                     slots // g, slots, slot_bytes))), slots)


def _rows_buffer(pool: dict) -> Array:
    """The buffer of a page pool that decides its read's groups and
    widths: a latent pool's one buffer, else the K rows."""
    return pool["latent"] if "latent" in pool else pool["k"]


def pool_view_groups(pool: dict, slots: int, columns: int,
                     ordered: bool = True) -> int:
    """``view_slot_groups`` of a page pool read through a table of
    (slots, columns), trimmed as the read trims it."""
    buf = _rows_buffer(pool)
    return view_slot_groups(slots, columns, buf.shape[2:], buf.dtype,
                            ordered)


def view_widths(columns: int) -> Tuple[int, ...]:
    """The ladder of table widths a slot group may read, in columns
    (pages), from the table's ``columns`` alone: ``_VIEW_WIDTH_STEPS``
    equal steps, rounded up to whole pages, the last the whole table (a
    table of fewer columns: one step a column)."""
    steps = min(_VIEW_WIDTH_STEPS, columns)
    return tuple(-(-columns * i // steps) for i in range(1, steps + 1))


@functools.lru_cache(maxsize=None)     # (a trace and every chunk's count ask)
def view_profiles(groups: int, columns: int) -> Tuple[Tuple[int, ...], ...]:
    """The width profiles a step chooses from, from the shapes alone: a
    profile gives each slot group (the slots in the order of ``pos``, the
    group of the least first) its width in table columns. The first is
    the staircase of evenly staggered slots, group g of n reading the
    ladder's (``view_widths``) step that holds the first (g + 1) / n of
    the table; each next one reads one step of the ladder further in
    every group (slots behind a prompt, or not quite evenly spread);
    ``_VIEW_PROFILES`` such staircases, then the whole table for every
    group, which is the read without the rule. Each profile holds every
    row that the one before it holds, so "the first that holds" is the
    least."""
    ladder = view_widths(columns)
    top = len(ladder) - 1
    base = [-(-(g + 1) * len(ladder) // groups) - 1 for g in range(groups)]
    stairs = [tuple(ladder[min(top, at + shift)] for at in base)
              for shift in range(_VIEW_PROFILES)]
    # (a short ladder's last staircases are the whole table already)
    return tuple(dict.fromkeys(stairs + [(columns,) * groups]))


def view_profile_index(pos_sorted, groups: int, columns: int,
                       page_size: int, xp=jnp):
    """Which of ``view_profiles(groups, columns)`` a step reads:
    ``pos_sorted`` (..., slots) the slots' positions in ascending order, a
    group ``slots // groups`` consecutive ones -> the index (...) of the
    first profile in which every group's width holds every row before the
    group's furthest ``pos`` (rows 0 .. pos - 1 lie in its first ``ceil(pos
    / page_size)`` columns; the token's own row is the read's self logit,
    stored after the layers). The last profile, the whole table, holds
    any. A parked slot (``pos`` 0) is first in the order and fits the
    narrowest width. Pure, and the same on the device (``xp`` jnp, once a
    step) and on the host (numpy, every step of a chunk at once:
    ``ViewPlan.columns_read``, the engine's counter)."""
    per = pos_sorted.shape[-1] // groups
    furthest = pos_sorted[..., per - 1::per]
    live = xp.minimum((furthest + page_size - 1) // page_size, columns)
    widths = xp.asarray(view_profiles(groups, columns))     # (profiles, n)
    holds = xp.all(widths >= live[..., None, :], axis=-1)
    return xp.argmax(holds, axis=-1)


class ViewPlan(NamedTuple):
    """A decode step's gather reads of the pool whose rows lie in order
    (the classic pool, a latent pool, a full pool), as shapes: the table
    of ``columns`` pages of ``page_size`` rows a slot, trimmed to the
    sequence (a window pool's ring is read whole, all of it live once it
    has wrapped, and is no part of this); ``groups`` of ``slots`` in the
    order of ``pos`` (``view_slot_groups``); ``by_rule`` layers of a step
    read it at the step's width profile and ``whole`` layers at its full
    width; ``span``, in a described block, the scans that ONE switch a
    step stands around (``block_view_plan``: None where each read
    switches for itself). What the step reads by it, the host counts from
    it (``columns_read``)."""
    slots: int
    columns: int
    page_size: int
    groups: int
    by_rule: int
    whole: int = 0
    span: Optional[Tuple[int, int]] = None

    def columns_read(self, pos, steps: int = 1) -> Tuple[int, int]:
        """The host's evaluation of the width rule over a chunk: from the
        positions ``pos`` (numpy, slot order; 0 for a free slot, which
        stays parked) of its first step, a live slot one further each of
        its ``steps`` -> (table columns the chunk's reads gather, summed
        over layers and steps, what they would gather at full width)."""
        pos = np.asarray(pos)
        at = np.minimum(pos + np.arange(steps)[:, None] * (pos > 0),
                        self.columns * self.page_size)
        profile = view_profile_index(np.sort(at, axis=1), self.groups,
                                     self.columns, self.page_size, xp=np)
        widths = np.asarray(view_profiles(self.groups, self.columns))
        full = steps * self.slots * self.columns
        narrowed = self.slots // self.groups * int(
            widths.sum(axis=1)[profile].sum())
        return (self.by_rule * narrowed + self.whole * full,
                (self.by_rule + self.whole) * full)


def _nbytes(a) -> int:
    return math.prod(a.shape) * jnp.dtype(a.dtype).itemsize


def _table_of(pool: dict, slots: int, total_len: int):
    """(columns, page_size, groups) of the ordered pool's table through
    ``slots``, trimmed to ``total_len`` rows."""
    buf = _rows_buffer(pool)
    columns = -(-total_len // buf.shape[2])     # pages_for
    return columns, buf.shape[2], view_slot_groups(
        slots, columns, buf.shape[2:], buf.dtype)


def paged_view_plan(cfg, params: dict, pool: dict, slots: int,
                    total_len: int) -> Optional[ViewPlan]:
    """The plan of the gather step's reads of ``pool``, whichever block
    ``cfg`` has (what ``decode_loop_paged`` will run; the engine's
    counters read it)."""
    if cfg.block is None:       # every layer of the one scan reads at the
        # step's profile (``_decode_step_math``: one switch around the scan)
        return ViewPlan(slots, *_table_of(pool, slots, total_len), cfg.depth)
    return block_view_plan(cfg, params, pool, slots, total_len)


def block_view_plan(cfg, params: dict, pool: dict, slots: int,
                    total_len: int) -> Optional[ViewPlan]:
    """Where a described block's step switches to its width profile
    (``decode_step_block``), from shapes alone; None for a block without
    an ordered pool.

    A conditional costs the chip 14-24 us at its edges (PERF.md section
    6, PR 38), so ONE stands around the span of scans from the first to
    the last that holds a layer reading the ordered pool, and every such
    layer reads at the profile: what the branches close over they are
    handed in place, but what they write the compiler hands OUT of them,
    and so it does the routed experts' whole stacks, which a layer reads
    by its index (``block_stack``). Where those and the recurrent state
    that the span's layers write pass ``_VIEW_SWITCH_BYTES`` (kanana: 7.25
    GB of experts, the program no longer fits the chip; the state of
    phi's nine state-space layers, 94 MB, cost 0.5 ms a step around the
    whole stack), each read of a SCANNED run switches for itself and a
    run of one layer, which runs in the step's own body next to the
    pool's store, reads whole (a conditional over the pool there made
    phi's program copy the pool)."""
    from dalle_pytorch_tpu.ops import transformer as T
    blk = cfg.block
    if "latent" not in pool and "k" not in pool:
        return None
    scans = T.stack_scans(blk, cfg.depth)
    reading = [i for i, scan in enumerate(scans)
               if any(run.kind.pool == "full" for run in scan)]
    first, stop = reading[0], reading[-1] + 1
    inside = [run for scan in scans[first:stop] for run in scan]
    experts = {blk.stack_of(run.kind) for run in inside if run.moe}
    handed = sum(_nbytes(leaf) for stack in experts for leaf in
                 jax.tree.leaves(params[stack]["ff"]["experts"]))
    handed += sum(run.count * _nbytes(pool[name]) // pool[name].shape[0]
                  for run in inside if run.kind.pool == "state"
                  for name in blk.pool_buffers("state"))
    readers = [run for run in inside if run.kind.pool == "full"]
    table = _table_of(pool, slots, total_len)
    if handed <= _VIEW_SWITCH_BYTES:
        return ViewPlan(slots, *table, sum(run.count for run in readers),
                        span=(first, stop))
    return ViewPlan(
        slots, *table,
        by_rule=sum(run.count for run in readers if run.count > 1),
        whole=sum(run.count for run in readers if run.count == 1))


class _View(NamedTuple):
    """One table of a step's paged gather reads as ``_read_in_slot_groups``
    takes it. ``tables`` (b, columns) lie in READ order: the slots' order
    by position (``order`` (b,): which slot a read row is; ``inverse`` the
    way back) with ``widths`` the static width in columns of each slot
    group (a profile of ``view_profiles``), or, where all three are None,
    slot order at full width (a sparse layer's visible columns, which lie
    in no order of ``pos``; a window ring). ``window`` names the work in
    a trace."""
    tables: Array
    order: Optional[Array] = None
    inverse: Optional[Array] = None
    widths: Optional[Tuple[int, ...]] = None
    window: bool = False

    def ordered(self, *xs):
        """Per-slot arrays (b, ...) brought into read order."""
        if self.order is None:
            return xs
        with attn_ops._read_scope(self.window):
            return tuple(x[self.order] for x in xs)


def _slot_order(pos: Array) -> Tuple[Array, Array]:
    """The slots in ascending order of ``pos`` (b,), ties in slot order
    -> (order (b,): the slot at each place, inverse (b,): each slot's
    place). By counting, b x b comparisons: cheaper on the chip than two
    sorts of at most a few dozen numbers."""
    i = jnp.arange(pos.shape[0])
    ahead = (pos[None, :] < pos[:, None]) | (
        (pos[None, :] == pos[:, None]) & (i[None, :] < i[:, None]))
    inverse = jnp.sum(ahead, axis=1)
    order = jnp.sum(jnp.where(inverse[None, :] == i[:, None], i[None, :],
                              0), axis=1)
    return order, inverse


def _width_profile(pool: dict, columns: int, pos_sorted: Array):
    """The step's width profile, once a step, outside the layer scan: ->
    (the index (int32 scalar) of the narrowest of ``view_profiles`` that
    holds the slots' rows (``view_profile_index``), the profiles) for the
    reads of ``pool``'s table of ``columns`` through the slots in the
    order of ``pos``."""
    groups = pool_view_groups(pool, pos_sorted.shape[0], columns)
    with jax.named_scope("kv.view"):
        at = view_profile_index(
            pos_sorted, groups, columns,
            _rows_buffer(pool).shape[2]).astype(jnp.int32)
    return at, view_profiles(groups, columns)


def _by_width_profile(profile, run):
    """``run(widths)`` at the step's width profile (``_width_profile``'s):
    ONE ``lax.switch`` over the static profiles, every branch the same
    computation with the paged reads at its own per-group ``widths``.
    What a narrower profile drops is masked in the widest, so every
    branch computes the same softmax from the rows that count.

    A conditional costs the chip 14-24 us of waiting at its edges
    (PERF.md section 6, PR 38: one a slot group a layer, 96 a step, took
    2.1 of ruDALL-E's 11.6 ms and lost what the narrower reads won), so
    there are as few as the program's shape allows: the classic step
    switches its whole layer scan, once a step (what a branch hands out,
    the new rows, is small); a described block switches the scans that
    read the ordered pool the same way, or one layer's read at a time
    where what they would hand out is too much (``block_view_plan``)."""
    at, profiles = profile
    return lax.switch(at, [functools.partial(run, widths)
                           for widths in profiles])


def _read_in_slot_groups(pool: dict, view: _View, read) -> Array:
    """The one slot-group loop of the paged gather reads, the classic
    block's and a described block's. Two things decide what a group
    reads.

    VMEM: a layer's gathered pages stay in VMEM between the gather and
    the contractions that read them only if they fit it, and what does
    not fit is written to HBM and read back by each contraction (three
    crossings of every page where one is needed).

    The rows that are written: the gather runs at the memory bandwidth,
    so its time is the columns it reads, and the rows past a slot's
    ``pos`` are masked in every layer. The view's rows lie in the order
    of ``pos``, so a group's slots are about as far along as each other,
    and the group reads ``view.widths[g]`` columns, the step's profile
    (``_by_width_profile``): ``read(sl, w)`` gathers and attends the read
    rows of the slice ``sl`` through ``view.tables[sl, :w]`` under
    ``allowed[sl, :w * page_size]``.

    ``read`` runs once a group of ``pool_view_groups``; the outputs are
    concatenated along the read rows and brought back to slot order. A
    slot's result is computed from the same rows in the same dtype
    whichever slots share its group (on the chip, bit-equal under a plain
    ``jit``; between two whole engine programs the compiler may still
    round a layer's output in another place: PERF.md section 6, PR 31)."""
    slots, columns = view.tables.shape
    groups = pool_view_groups(pool, slots, columns, view.order is not None)
    per = slots // groups
    widths = view.widths or (columns,) * groups
    outs = [read(slice(g * per, (g + 1) * per), widths[g])
            for g in range(groups)]
    # (a read may give more than its output, each a row a slot: a sink's
    # weight, ``ops.attention.gqa_attend_rows``)
    with attn_ops._read_scope(view.window):
        out = outs[0] if groups == 1 else jax.tree.map(
            lambda *parts: jnp.concatenate(parts), *outs)
        return out if view.inverse is None else jax.tree.map(
            lambda a: a[view.inverse], out)


def _refuse_block(cfg, option: str, why: str = "") -> None:
    """The one typed refusal of a path that cannot run a described block
    (``cfg.block``): it runs through ``prefill`` and the paged gather
    step (``decode_step_block``) alone."""
    if cfg.block is not None:
        from dalle_pytorch_tpu.ops.transformer import BlockOptionError
        raise BlockOptionError(cfg.block.name, option, why)


def init_cache(cfg, batch: int, total_len: int, dtype=jnp.float32,
               quantized: bool = False) -> dict:
    """K/V buffers. ``quantized=True`` stores int8 rows with per-row f32
    scales (beyond reference: cache reads are the term of a decode
    step's bytes that grows with the batch, and int8 halves them). Rows are written once and read
    every later step, so the quantization cost is paid once per row.

    Accuracy contract: the int8 rows carry ~0.4% relative error
    (symmetric per-row quantization, step = row_max/127), and
    ``decode_step`` applies the f32 scales AFTER casting them to the
    score/weight dtype — under bf16 params that cast is a SECOND ~0.4%
    quantization of the scale itself (deliberate: an f32 multiply would
    promote the whole decode scan carry to f32 and double the vector
    bytes). The compounded per-layer attention error is therefore
    bounded at roughly 1% relative; tests/test_quant.py pins the
    end-to-end parity of the int8-KV path at < 2%, and that tolerance
    is this contract, not slack."""
    _refuse_block(cfg, "kv='dense'", "its cache is the latent page pool")
    shape = (cfg.depth, batch, cfg.heads, total_len, cfg.dim_head)
    if quantized:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_rows(x: Array):
    """(..., dh) -> (int8 rows, (...,) f32 scales), symmetric per row."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                        1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


@jax.named_scope("kv.store")
def _store_rows(cache: dict, ks: Array, vs: Array, pos) -> dict:
    """Write K/V rows (depth, b, heads, rows, dh) into the cache starting
    at ``pos`` — the ONE definition of the cache write for prefill and
    decode_step, quantizing iff the cache is the int8 variant (so the
    two writers can never diverge on layout).

    ``pos`` may also be a (b,) vector of per-batch-row positions (then
    ks/vs must be single rows, rows == 1): each batch row writes its own
    cache row — the serve engine's continuous-batching step, where every
    slot sits at a different sequence position (serve/engine.py)."""
    if getattr(pos, "ndim", 0) == 1:
        return _store_rows_per_slot(cache, ks, vs, pos)
    if "k_scale" in cache:
        kq, ksc = _quantize_rows(ks)
        vq, vsc = _quantize_rows(vs)
        return {
            "k": lax.dynamic_update_slice(cache["k"], kq,
                                          (0, 0, 0, pos, 0)),
            "v": lax.dynamic_update_slice(cache["v"], vq,
                                          (0, 0, 0, pos, 0)),
            "k_scale": lax.dynamic_update_slice(cache["k_scale"], ksc,
                                                (0, 0, 0, pos)),
            "v_scale": lax.dynamic_update_slice(cache["v_scale"], vsc,
                                                (0, 0, 0, pos)),
        }
    return {
        "k": lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, pos, 0)),
        "v": lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, pos, 0)),
    }


@jax.named_scope("kv.store")
def _store_rows_per_slot(cache: dict, ks: Array, vs: Array,
                         pos: Array) -> dict:
    """Scatter variant of ``_store_rows``: ks/vs are single rows
    (depth, b, heads, 1, dh) and ``pos`` is (b,) — batch row i writes cache
    row pos[i] of its own slot. Same quantization contract as the
    contiguous path (one write definition per layout)."""
    b = pos.shape[0]
    bidx = jnp.arange(b)

    def put_rows(buf, rows):
        # buf (depth, b, heads, L, dh); advanced indices at dims 1 and 3
        # are non-adjacent, so the update value is (b, depth, heads, dh)
        return buf.at[:, bidx, :, pos, :].set(
            jnp.moveaxis(rows[:, :, :, 0, :], 0, 1))

    def put_scales(buf, sc):
        # buf (depth, b, heads, L); value (b, depth, heads)
        return buf.at[:, bidx, :, pos].set(
            jnp.moveaxis(sc[:, :, :, 0], 0, 1))

    if "k_scale" in cache:
        kq, ksc = _quantize_rows(ks)
        vq, vsc = _quantize_rows(vs)
        return {"k": put_rows(cache["k"], kq),
                "v": put_rows(cache["v"], vq),
                "k_scale": put_scales(cache["k_scale"], ksc),
                "v_scale": put_scales(cache["v_scale"], vsc)}
    return {"k": put_rows(cache["k"], ks), "v": put_rows(cache["v"], vs)}


def _full_key_mask(prompt_mask: Optional[Array], batch: int, prompt_len: int,
                   total_len: int) -> Array:
    """(b, total_len) bool: prompt pad mask over [0, t0), True beyond — the
    reference grows its mask with True for every generated position
    (reference dalle_pytorch.py:344-347)."""
    full = jnp.ones((batch, total_len), bool)
    if prompt_mask is not None:
        full = full.at[:, :prompt_len].set(prompt_mask)
    return full


def _sparse_layout(cfg, total_len: int) -> Array:
    """(total_len, total_len) token-level allowed mask for sparse layers."""
    import numpy as np
    block = cfg.sparse_block
    padded = ((total_len + block - 1) // block) * block
    layout = sparse.token_layout_mask(padded, block, causal=cfg.causal)
    # jaxlint: disable=JL001 — layout is host data built from static
    # config only (no tracer flows in); this is trace-time constant
    # construction, hoisted into the program once per compile
    return jnp.asarray(np.asarray(layout)[:total_len, :total_len])


def _sparse_page_visibility(cfg, total_len: int, page_size: int):
    """Static per-position PAGE visibility for sparse layers — the page-
    granular reduction of ``_sparse_layout``, resolved from config and
    delegated to the CACHED shared source
    (``ops.sparse.visible_pages_causal``; the engine's stats model and
    bench read the same tables, so the precompute can never drift
    between them).

    Returns ``(vis (L, W) int32, cnt (L,), cnt_causal (L,))``: row p's
    visible page ids ascending with ``cnt[p]`` live entries (the
    any-token-in-page oracle), and ``cnt_causal[p]`` the decode trip
    count."""
    return sparse.visible_pages_causal(total_len, page_size,
                                       cfg.sparse_block,
                                       causal=cfg.causal)


@jax.named_scope("attn.read")
def _kernel_read(q: Array, k: Array, v: Array, pool_k: Array,
                 pool_v: Array, block_tables: Array, pos: Array,
                 allowed: Array, *, scale: float,
                 ksc: Optional[Array] = None,
                 vsc: Optional[Array] = None,
                 visible: Optional[Array] = None,
                 visible_cnt: Optional[Array] = None) -> Array:
    """The kernel half of the cached-attention read seam: Pallas ragged
    paged-attention partials over the raw page pool (``pool_k/pool_v``
    consumed through the block tables in place), completed with the
    current token's self-logit by the two-estimate softmax merge —
    exactly ``softmax(concat([scores, self]))`` up to summation order,
    the gather oracle's computation. ``visible``/``visible_cnt`` switch
    the kernel to a sparse layer's statically visible page list
    (sparsity-aware decode reads). Returns the (b, h, 1, dh) attention
    output BEFORE out_sync/out-projection — the caller owns those."""
    from dalle_pytorch_tpu.ops import paged_attention as PA
    acc, m, l = PA.paged_decode_attention(
        q[:, :, 0, :], pool_k, pool_v, block_tables, pos, allowed,
        scale=scale, k_scales=ksc, v_scales=vsc, visible=visible,
        visible_cnt=visible_cnt)
    self_s = (jnp.einsum("bhqd,bhqd->bhq", q, k)[:, :, 0]
              .astype(jnp.float32) * scale)                  # (b, h)
    m_t = jnp.maximum(m, self_s)           # self is finite: m_t too
    alpha = jnp.exp(m - m_t)
    w_self = jnp.exp(self_s - m_t)
    denom = l * alpha + w_self             # >= w_self > 0: no 0-div
    out = (acc * alpha[..., None]
           + w_self[..., None] * v[:, :, 0, :]
           .astype(jnp.float32)) / denom[..., None]
    return out.astype(q.dtype)[:, :, None, :]


def _softmax_with_self(scores: Array, allowed: Array, q: Array, k: Array,
                       scale: float) -> Array:
    """The one masked softmax of the cached-attention reads: scores
    (b, h, 1, rows) over the cached rows, dead where ``allowed`` (b, rows)
    is False, plus the current token's self logit as the last column.
    Returns the weights (b, h, 1, rows + 1)."""
    scores = jnp.where(allowed[:, None, None, :], scores,
                       core.neg_inf(scores.dtype))
    self_score = jnp.einsum("bhqd,bhqd->bhq", q, k)[..., None] * scale
    return jax.nn.softmax(jnp.concatenate([scores, self_score], -1),
                          axis=-1)


@jax.named_scope("attn.read")
def _gather_read(q: Array, k: Array, v: Array, ck: Array, cv: Array,
                 allowed: Array, *, scale: float,
                 ksc: Optional[Array] = None,
                 vsc: Optional[Array] = None) -> Array:
    """The dense-view half of the cached-attention read seam: one
    einsum softmax over a (b, heads, L, dh) view of the cached rows
    (the real dense slot cache, or ``paged_view``'s block-table gather;
    the page pool itself is read by ``_paged_gather_read``) plus the
    self-logit. The
    int8 cache reads int8 rows and upcasts in registers, scales
    applied OUTSIDE the contractions (along j) so no dequantized copy
    materializes — same trick as ops/quant. Returns the (b, h, 1, dh)
    output BEFORE out_sync/out-projection."""
    quantized = ksc is not None
    ckc = ck.astype(q.dtype) if quantized else ck
    scores = jnp.einsum("bhqd,bhjd->bhqj", q, ckc) * scale
    if quantized:
        # scales applied in the SCORE dtype: an f32 multiply would
        # promote the whole decode carry to f32 under bf16 params
        # (scan carry dtype mismatch) and double the vector bytes
        scores = scores * ksc[:, :, None, :].astype(scores.dtype)
    w = _softmax_with_self(scores, allowed, q, k, scale)
    wj = w[..., :-1]
    if quantized:
        wj = wj * vsc[:, :, None, :].astype(wj.dtype)
        cvc = cv.astype(q.dtype)
    else:
        cvc = cv
    return jnp.einsum("bhqj,bhjd->bhqd", wj, cvc) + w[..., -1:] * v


def _view_tables(block_tables: Array, total_len: int,
                 page_size: int) -> Array:
    """The block tables trimmed to the ``ceil(total_len / page_size)``
    columns a view of ``total_len`` rows reads: a caller's table is sized
    for the pool's longest sequence, and wholly unmapped logical pages
    beyond ``total_len`` must never reach a gather."""
    return block_tables[:, :-(-total_len // page_size)]   # pages_for


def _pool_scope(window: bool):
    """A pool access's name in a trace: a window pool's gather and store
    go by ``kv.window``, every other pool's by ``kv.view`` / ``kv.store``
    (this is the gather's)."""
    return jax.named_scope("kv.window") if window \
        else jax.named_scope("kv.view")


def layer_pool_view(buf: Array, layer: Array, tables: Array,
                    window: bool = False) -> Array:
    """ONE layer's pages of one pool buffer through the block tables, read
    where they lie: ``buf`` (depth, P, ps, row) is any buffer of any
    block's page pool (``kv_pool.page_layout``: a page is ``(page_size,
    row)`` in every block: the classic block's ``k`` / ``v`` rows of
    ``heads * dim_head``, its int8 pool's scale rows of ``heads``, a
    latent pool's ``latent``, a grouped-query pool's rows); ``layer`` a
    traced scalar, tables (b, w) -> (b, w, ps, row). The one per-layer
    view of BOTH step maths and of a described block's step: the full
    table trimmed to ``ceil(total_len / ps)`` columns and then to the
    width its slot group reads, or a sparse layer's visible slice of it,
    always the rows of ONE slot group (``_read_in_slot_groups`` decides
    the groups and their widths), so that the pages stay in VMEM from
    this gather to the contraction that reads them.

    Three choices keep this a gather of whole pages and nothing else,
    each read off the compiled TPU program (PERF.md, PR 25):
      * indexed by ``layer * P + page`` into the (depth * P, ...) pool
        (a bitcast), not taken from a scanned per-layer slice: the
        scan's slice of a layer is a copy of that layer;
      * page-major and unrelaid — a page is one contiguous run of the
        pool, and the reads contract its rows as they lie
        (``_gathered_rows``); the slot-major ``moveaxis`` + ``reshape``
        of ``paged_view`` is what turned the read into transposing
        copies of the pool;
      * ``mode='clip'``: tables are in range by construction, and the
        default fill mode adds a select over every gathered row.

    ``window`` names the gather in a trace ``kv.window``: a window pool's
    (its pages are a ring, ``window_rows``)."""
    with _pool_scope(window):
        return jnp.take(buf.reshape((-1,) + buf.shape[2:]),
                        layer * buf.shape[1] + tables, axis=0, mode="clip")


def _gathered_rows(buf: Array, layer: Array, tables: Array,
                   window: bool = False, after=None) -> Array:
    """One slot group's pages of a pool buffer (``layer_pool_view``),
    merged to rows in the order they were gathered -> (b, w * ps, row): a
    bitcast when the page is whole sublane tiles (no axis lies between
    page and row). ``after`` ties the gather to a value that must be
    computed first (the softmax's weights, for V's pages): among several
    slot groups the TPU scheduler was seen to lift one group's V gather
    above that group's K gather (AOT, PR 31: of 12b's six gathers a layer
    one then misses VMEM), and the budget of a group is ONE gathered
    buffer (``view_slot_groups``), so there the order is stated."""
    if after is not None:
        _, tables = lax.optimization_barrier((after, tables))
    pages = layer_pool_view(buf, layer, tables, window)
    with _pool_scope(window):
        return pages.reshape(pages.shape[0], -1, pages.shape[-1])


def _paged_gather_read(pool: dict, layer: Array, tables: Array, q: Array,
                       k: Array, v: Array, allowed: Array, *, scale: float,
                       v_after_k: bool = False, mesh: bool = False) -> Array:
    """``_gather_read`` for ONE slot group over the classic block's page
    pool: tables (b, w) into the K/V pool, q/k/v (b, h, 1, dh), allowed
    (b, rows) (logical row j is page j // ps, offset j % ps; a partial
    last page's tail rows are dead; where the group reads fewer columns
    than the mask has rows for, a narrower width of its step's profile,
    the first w * ps rows count). A page is whole
    rows, ``(ps, heads * dh)``, so the gathered pages ARE the slot's rows
    in logical order (``_gathered_rows``: a bitcast), and the read is the
    grouped-query one at ``kv_heads == heads``
    (``ops.attention.gqa_attend_rows``, which the described blocks run):
    every head's query against whole rows in one product on the matrix
    unit, float32 scores (b, heads, w * ps) in row order, the masked
    softmax over rows 0..rows-1 plus the self logit, exactly the dense
    view's; then V's pages the same way, gathered once K's readers are
    done (``v_after_k`` states that order where a layer reads several
    groups). The int8 pool's scale pages ``(ps, heads)`` are gathered
    with their rows and apply outside the contractions, to the float32
    scores and to the weights. Returns (b, h, 1, dh).

    ``mesh`` (whether the step was handed ``out_sync``) asks for the same
    rows read per head: with the pool's row sharded over the heads a
    whole-row contraction would sum partial scores across chips."""
    rows = tables.shape[1] * pool["k"].shape[2]
    with jax.named_scope("attn.read"):    # a partial last page's tail
        allowed = jnp.pad(allowed[:, :rows], ((0, 0), (
            0, max(rows - allowed.shape[1], 0))))
    scales = {}
    if "k_scale" in pool:
        scales = dict(
            k_scale=_gathered_rows(pool["k_scale"], layer, tables),
            gather_v_scale=lambda: _gathered_rows(pool["v_scale"], layer,
                                                  tables))
    out = attn_ops.gqa_attend_rows(
        q[:, :, 0], k[:, :, 0], v[:, :, 0],
        _gathered_rows(pool["k"], layer, tables),
        lambda wts: _gathered_rows(pool["v"], layer, tables,
                                   after=wts if v_after_k else None),
        allowed, scale, False, per_head=mesh, **scales)
    return out[:, :, None, :]


def _paged_gather_attend(pool: dict, layer: Array, view,
                         q: Array, k: Array, v: Array, allowed: Array, *,
                         scale: float, mesh: bool = False) -> Array:
    """One layer's paged gather read of the classic block, whole:
    ``_paged_gather_read`` over the slots of ``view`` (a ``_View``: the
    step's tables in the order of ``pos``, or plain tables (b, w), read
    in slot order at full width), a slot group at a time
    (``_read_in_slot_groups`` decides the groups from the shapes and each
    group's width from the view; ``mesh``, whether the step was handed
    ``out_sync``, the form of a group's two contractions). q/k/v (b, h,
    1, dh) in slot order, allowed (b, rows) in the view's order -> (b, h,
    1, dh) BEFORE out_sync/out-projection."""
    if not isinstance(view, _View):
        view = _View(view)
    several = pool_view_groups(pool, *view.tables.shape,
                               view.order is not None) > 1
    q, k, v = view.ordered(q, k, v)

    def read(sl, w):
        return _paged_gather_read(
            pool, layer, view.tables[sl, :w], q[sl], k[sl], v[sl],
            allowed[sl], scale=scale, v_after_k=several, mesh=mesh)
    return _read_in_slot_groups(pool, view, read)


def _attn_with_kv(lp: dict, h: Array, allowed: Array, cfg,
                  out_sync=None) -> Tuple[Array, Array, Array]:
    """PreNorm attention over an explicit allowed-mask; returns out, k, v.

    h: (b, n, dim); allowed: broadcastable to (b, 1, n, n) (True = attend).
    ``out_sync`` is the same mesh seam as ``_decode_step_math``'s: the
    per-head output re-replicated before the out projection, so GSPMD
    can never partial-sum the projection's contraction across head
    shards (prefill writes a heads-sharded cache under the mesh engine,
    and an unconstrained partitioner choice upstream of that output
    would reassociate floats — byte-identity must not rest on a cost
    model's mood).
    """
    p = lp["attn"]
    hn = core.layernorm(p["ln"], h)
    q, k, v = attn_ops.qkv_project(p, hn, cfg.heads)
    with jax.named_scope("attn.read"):
        dots = jnp.einsum("bhid,bhjd->bhij", q, k) * cfg.scale
        dots = jnp.where(allowed, dots, core.neg_inf(dots.dtype))
        out = jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(dots, axis=-1),
                         v)
    if out_sync is not None:
        out = out_sync(out)
    out = attn_ops.output_tail(p, out)
    return out, k, v


def prefill(params: dict, x: Array, *, cfg, total_len: int,
            prompt_mask: Optional[Array] = None,
            quantize_cache: bool = False,
            out_sync=None,
            lens: Optional[Array] = None) -> Tuple[Array, dict]:
    """Run the prompt embeddings x (b, t0, dim) through the stack.

    Returns (h_out (b, t0, dim), cache with rows [0, t0) filled).
    ``quantize_cache`` stores the cache int8 (see init_cache). ``lens``
    (b,) is each row's own prompt length where the rows are padded to t0:
    a described block's recurrent state is the one after exactly that many
    tokens (rows need no telling: a padded row is never attended before
    the decode overwrites it).
    """
    from dalle_pytorch_tpu.ops import transformer as T
    b, t0, _ = x.shape
    if cfg.block is not None:
        # a described block's prefill IS its full forward (the
        # materialised read); the cache it returns is the prompt's rows
        # alone, a buffer of the pool each (``block_stack``): {"latent":
        # (depth, b, t0, row_width)}, or {"k", "v", "window_k",
        # "window_v": (the pool's layers, b, t0, kv_heads, head_dim)},
        # and a state-space layer's state after each row's last token:
        # its store is the page pool, and the engine's admission writes
        # whole pages
        if quantize_cache:
            _refuse_block(cfg, "quantize_cache")
        h_out, entries, _ = T.block_apply_full(params, x, cfg, prompt_mask,
                                               lens)
        return h_out, entries
    sparse_flags = jnp.asarray(cfg.sparse_pattern)
    any_sparse = any(cfg.sparse_pattern)

    with jax.named_scope("attn.read"):       # the masks
        tri = jnp.tril(jnp.ones((t0, t0), bool))[None, None]
        pad_ok = jnp.ones((b, 1, t0, t0), bool)
        if prompt_mask is not None:
            pad_ok = (prompt_mask[:, None, :, None]
                      & prompt_mask[:, None, None, :])
        dense_allowed = tri & pad_ok
        if any_sparse:
            layout = _sparse_layout(cfg, total_len)[:t0, :t0][None, None]
            sparse_allowed = dense_allowed & layout
        else:
            sparse_allowed = dense_allowed  # dead value for scan symmetry

    def body(carry, xs):
        lp, is_sparse = xs
        with jax.named_scope("attn.read"):
            allowed = jnp.where(is_sparse, sparse_allowed, dense_allowed) \
                if any_sparse else dense_allowed
        if cfg.reversible:
            x1, x2 = carry
            a, k, v = _attn_with_kv(lp, x2, allowed, cfg, out_sync)
            y1 = x1 + a
            y2 = x2 + T.ff_or_moe(lp, y1, cfg, None, False)[0]
            return (y1, y2), (k, v)
        h = carry
        a, k, v = _attn_with_kv(lp, h, allowed, cfg, out_sync)
        h = h + a
        h = h + T.ff_or_moe(lp, h, cfg, None, False)[0]
        return h, (k, v)

    carry0 = (x, x) if cfg.reversible else x
    carry, (ks, vs) = lax.scan(body, carry0, (params, sparse_flags))
    h_out = (carry[0] + carry[1]) * 0.5 if cfg.reversible else carry

    cache = init_cache(cfg, b, total_len, ks.dtype,
                       quantized=quantize_cache)
    return h_out, _store_rows(cache, ks, vs, 0)


def decode_loop(params: dict, cur_tok: Array, pos: Array, active: Array,
                cache: dict, *, cfg, key_mask: Array, steps: int,
                embed_fn, sample_fn,
                out_sync=None) -> Tuple[Array, Array, Array, dict,
                                        Array]:
    """Fuse ``steps`` decode steps into ONE device program: a ``lax.scan``
    over ``decode_step`` that carries (cur_tok, pos, active, cache) as
    device state and stacks each step's emitted token into an emit ring —
    the serve engine's steady-state loop, where the host must not be in
    the per-token path (one host round-trip per K tokens instead of one
    per token; docs/SERVING.md).

    cur_tok/pos: (b,) per-slot current token and position. active: (b,)
    bool — a slot emits only while active; a slot whose position reaches
    the cache end mid-loop deactivates itself and keeps computing into a
    dead mask (parked at pos 0, rewriting its dead row — fixed shapes,
    so the program never retraces) until the host's next harvest notices.
    ``embed_fn(cur_tok, pos) -> (b, dim)`` and
    ``sample_fn(h, pred_pos) -> (b,)`` are the model-level halves
    (``models.dalle.decode_token_embed`` / ``to_logits`` + per-slot
    sampling) so this ops layer stays model-agnostic.

    Returns (cur_tok, pos, active, cache, emit_ring) with emit_ring
    (b, steps) int32: slot b's tokens in step order, -1 where the slot
    was inactive (the harvest sentinel — real tokens are >= 0, image ids
    are stored offset-free exactly as ``generate_images`` emits them).
    """
    total_len = cache["k"].shape[3]

    def one_step(carry, _):
        cur_tok, pos, act, cache = carry
        emit = jnp.where(act, cur_tok, -1)
        x = embed_fn(cur_tok, pos)
        h, cache = decode_step(params, x, pos, cache, cfg=cfg,
                               key_mask=key_mask, out_sync=out_sync)
        nxt = sample_fn(h, pos + 1)
        pos = pos + 1
        act = act & (pos < total_len)
        # dead slots (finished, killed, or never admitted) park at
        # (tok 0, pos 0): they rewrite their dead row 0 instead of
        # scattering past the cache end, and emit the -1 sentinel
        cur_tok = jnp.where(act, nxt, 0)
        pos = jnp.where(act, pos, 0)
        return (cur_tok, pos, act, cache), emit

    (cur_tok, pos, active, cache), emits = lax.scan(
        one_step, (cur_tok, pos, active, cache), None, length=steps)
    return cur_tok, pos, active, cache, jnp.moveaxis(emits, 0, 1)


def decode_step(params: dict, x_tok: Array, pos: Array, cache: dict, *, cfg,
                key_mask: Array, out_sync=None) -> Tuple[Array, dict]:
    """Advance one token. x_tok: (b, dim) embedding of the token at position
    ``pos`` (traced scalar, or a (b,) vector of PER-ROW positions — the
    serve engine's continuous-batching step, where each slot of the fixed
    batch sits at its own point in its own sequence). key_mask:
    (b, total_len) validity of cache rows (pad-aware; rows >= pos are
    masked by the causal check regardless).

    Returns (h_out (b, dim), updated cache).
    """
    h_out, ks, vs = _decode_step_math(params, x_tok, pos, cache, cfg=cfg,
                                      key_mask=key_mask, out_sync=out_sync)
    return h_out, _store_rows(cache, ks, vs, pos)


def _decode_step_math(params: dict, x_tok: Array, pos: Array, cache: dict,
                      *, cfg, key_mask: Array, attn_impl: str = "gather",
                      block_tables: Optional[Array] = None,
                      sparse_reads: bool = False,
                      out_sync=None) -> Tuple[Array, Array, Array]:
    """The read half of ``decode_step``: attention over the cached rows
    plus self, WITHOUT the cache write-back. Returns (h_out (b, dim),
    new ks, new vs (depth, b, heads, 1, dh)) so the two cache layouts —
    the dense slot cache (``_store_rows``) and the paged page pool
    (``_store_rows_paged``) — share one definition of the math and can
    never diverge on what a step computes (``decode_step_paged`` is the
    paged writer).

    ``cache`` is read in one of three ways. Without ``block_tables`` it
    is DENSE per-slot rows ``(depth, b, heads, L, dh)`` — the real dense
    slot cache, or a ``paged_view`` an oracle built — read by one einsum
    softmax (``_gather_read``). With ``block_tables`` it is the raw PAGE
    POOL ``(depth, P, page_size, heads * dh)`` and ``attn_impl`` is the
    paged-read seam: ``'gather'`` (default) has the scan's body gather
    ITS OWN layer's pages through the tables trimmed to
    ``ceil(total_len / page_size)`` columns (``layer_pool_view``) and
    contract them as the rows they are under the same masked softmax
    (``_paged_gather_read``), a slot group at a time
    (``_paged_gather_attend``) — one read of the pool a step, and no
    buffer of the pool's size besides the pool. Every head's query is
    contracted against a slot's whole rows in one product on the matrix
    unit; under a mesh (``out_sync``), whose chips each hold some heads'
    columns of every row, a head's query against its own columns;
    ``'kernel'`` consumes
    the tables in place via the Pallas ragged paged-attention kernel
    (``ops.paged_attention``), which fetches only each slot's mapped
    live pages into VMEM and returns online-softmax partials that the
    self-logit merge below completes. ``paged_view`` + ``_gather_read``
    stay the parity ORACLE of both: outputs allclose under the same
    masks (rows >= pos dead, trash-page rows never attended), and
    emitted tokens byte-identical under greedy/seeded sampling
    (tests/test_paged_attention.py).

    ``sparse_reads=True`` is the per-layer VISIBILITY seam (sparsity-
    aware decode reads): ``cache`` must be the raw page pool for BOTH
    impls, and sparse layers read only their statically visible pages
    (``_decode_step_math_sparse_reads``) while dense layers read
    exactly as here."""
    _refuse_block(cfg, "this decode step",
                  "kv='dense', paged_attn='kernel', sparse_reads and the "
                  "oracle view read K/V per head")
    if sparse_reads:
        if block_tables is None:
            raise ValueError("sparse_reads requires block_tables — page "
                             "visibility lives in the paged KV layout")
        return _decode_step_math_sparse_reads(
            params, x_tok, pos, cache, cfg=cfg, key_mask=key_mask,
            attn_impl=attn_impl, block_tables=block_tables,
            out_sync=out_sync)
    from dalle_pytorch_tpu.ops import transformer as T
    b = x_tok.shape[0]
    total_len = key_mask.shape[1]
    sparse_flags = jnp.asarray(cfg.sparse_pattern)
    any_sparse = any(cfg.sparse_pattern)
    per_slot = getattr(pos, "ndim", 0) == 1
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', "
                         f"got {attn_impl!r}")
    kernel_mode = attn_impl == "kernel"
    if kernel_mode:
        if not per_slot:
            raise ValueError("attn_impl='kernel' requires per-slot (b,) "
                             "positions (the serving decode shape)")
        if block_tables is None:
            raise ValueError("attn_impl='kernel' requires block_tables")
    paged_gather = block_tables is not None and not kernel_mode
    at, valid = pos, key_mask       # the masks' positions and rows' validity
    if paged_gather:
        # the gather reads go through the slots in the order of ``pos``
        # (``_read_in_slot_groups``): the tables and the masks are laid
        # in that order here, once a step
        with jax.named_scope("attn.read"):
            order, inverse = _slot_order(jnp.broadcast_to(pos, (b,)))
            at, valid = pos[order] if per_slot else pos, key_mask[order]
        with jax.named_scope("kv.view"):
            tables = _view_tables(block_tables, total_len,
                                  cache["k"].shape[2])[order]

    with jax.named_scope("attn.read"):       # the masks
        j = jnp.arange(total_len)
        # strictly-before rows; self added as the concatenated extra logit
        causal_ok = (j[None, :] < at[:, None]) if per_slot \
            else (j < at)[None, :]
        dense_allowed = causal_ok & valid                        # (b, L)
        if any_sparse:
            layout = _sparse_layout(cfg, total_len)
            if per_slot:
                row = jnp.take(layout, at, axis=0)               # (b, L)
                sparse_allowed = dense_allowed & row
            else:
                row = lax.dynamic_slice(layout, (at, 0), (1, total_len))[0]
                sparse_allowed = dense_allowed & row[None, :]
        else:
            sparse_allowed = dense_allowed

    h_in = x_tok[:, None, :]                                  # (b, 1, dim)

    def attn_cached(lp, h, kv, is_sparse, widths):
        p = lp["attn"]
        hn = core.layernorm(p["ln"], h)
        q, k, v = attn_ops.qkv_project(p, hn, cfg.heads)      # (b, h, 1, dh)
        with jax.named_scope("attn.read"):
            allowed = jnp.where(is_sparse, sparse_allowed, dense_allowed) \
                if any_sparse else dense_allowed
        if paged_gather:
            # kv is this layer's INDEX: gather its pages from the pool
            # and contract them as they lie
            out = _paged_gather_attend(
                cache, kv, _View(tables, order, inverse, widths), q, k, v,
                allowed, scale=cfg.scale, mesh=out_sync is not None)
        elif kernel_mode:
            # kv is the raw page pool for this layer; the kernel walks
            # the block tables in place (_kernel_read completes the
            # softmax with the self-logit merge)
            out = _kernel_read(q, k, v, kv["k"], kv["v"], block_tables,
                               pos, allowed, scale=cfg.scale,
                               ksc=kv.get("k_scale"),
                               vsc=kv.get("v_scale"))
        else:
            out = _gather_read(q, k, v, kv["k"], kv["v"], allowed,
                               scale=cfg.scale, ksc=kv.get("k_scale"),
                               vsc=kv.get("v_scale"))
        if out_sync is not None:
            # mesh-sharded serving (parallel/serve_specs.py): the
            # per-head output is re-replicated HERE, so the out
            # projection sees gathered heads (data movement) and
            # never partial-sums its contraction across shards —
            # the byte-identity contract's load-bearing constraint
            out = out_sync(out)
        return attn_ops.output_tail(p, out), k, v

    def body(widths, carry, xs):
        lp, kv, is_sparse = xs
        if cfg.reversible:
            x1, x2 = carry
            a, k, v = attn_cached(lp, x2, kv, is_sparse, widths)
            y1 = x1 + a
            y2 = x2 + T.ff_or_moe(lp, y1, cfg, None, False)[0]
            return (y1, y2), (k, v)
        h = carry
        a, k, v = attn_cached(lp, h, kv, is_sparse, widths)
        h = h + a
        h = h + T.ff_or_moe(lp, h, cfg, None, False)[0]
        return h, (k, v)

    carry0 = (h_in, h_in) if cfg.reversible else h_in
    # the scan hands each layer its slice of the cache, except on the
    # paged gather path: there the slice would be a copy of the layer,
    # so the layer gets its index and reads the pool itself
    xs = (params, jnp.arange(cfg.depth) if paged_gather else cache,
          sparse_flags)

    def layers(widths):
        return lax.scan(functools.partial(body, widths), carry0, xs)

    if paged_gather:
        carry, (ks, vs) = _by_width_profile(_width_profile(
            cache, tables.shape[1], jnp.broadcast_to(at, (b,))), layers)
    else:
        carry, (ks, vs) = layers(None)
    h_out = (carry[0] + carry[1]) * 0.5 if cfg.reversible else carry

    return h_out[:, 0, :], ks, vs


def _decode_step_math_sparse_reads(params: dict, x_tok: Array, pos: Array,
                                   pool: dict, *, cfg, key_mask: Array,
                                   attn_impl: str, block_tables: Array,
                                   out_sync=None
                                   ) -> Tuple[Array, Array, Array]:
    """Sparsity-aware read twin of ``_decode_step_math`` (its
    ``sparse_reads=True`` branch): the model's sparse layers were
    trained to see only a block-local window plus the global blocks
    (``_sparse_layout``), so at decode time most cached pages carry
    exactly-zero attention weight for them — pure wasted read traffic.
    Here each sparse layer reads ONLY its statically visible pages
    (``_sparse_page_visibility``), dense layers read exactly what
    ``_decode_step_math`` reads, and both impls consume the RAW page
    pool (``pool``) through the block tables:

      * ``'kernel'``: the Pallas ragged walk follows the per-slot
        visible-page LIST instead of the prefix ``0..pages_for(pos)``
        (token-causally trimmed counts). Skipped pages are fully
        masked, so under the finite ``neg_inf`` fill the online
        recurrence is BIT-EQUAL to the prefix walk.
      * ``'gather'``: sparse layers gather only the visible slice of
        the block table (``kv_pool.visible_table_view``, width = the
        static max visible count) with the row mask remapped onto the
        trimmed columns; dense layers gather the full table. Both go
        through the one per-layer view and read that
        ``_decode_step_math`` uses (``layer_pool_view``,
        ``_paged_gather_read``).

    The dense/sparse choice is resolved STATICALLY by unrolling one
    period of ``cfg.sparse_pattern`` inside the layer scan (the
    ops.transformer periodic idiom) — the trimmed sparse read has a
    different SHAPE than the dense read, which a traced flag could
    never select between. Aperiodic patterns are rejected upstream
    (serve/engine.py) and here."""
    from dalle_pytorch_tpu.ops import transformer as T
    from dalle_pytorch_tpu.serve import kv_pool as KV
    b = x_tok.shape[0]
    total_len = key_mask.shape[1]
    pattern = cfg.sparse_pattern
    if not any(pattern):
        raise ValueError("sparse_reads on a stack with no sparse layers "
                         "would be a silent no-op — drop the flag")
    period = T._pattern_period(pattern)
    if period > T._MAX_UNROLL_PERIOD:
        raise ValueError(
            f"sparse_reads needs a periodic sparse pattern (period <= "
            f"{T._MAX_UNROLL_PERIOD}) so the per-layer read shapes "
            f"resolve statically; pattern {pattern} has period {period}")
    if getattr(pos, "ndim", 0) != 1:
        raise ValueError("sparse_reads requires per-slot (b,) positions "
                         "(the serving decode shape)")
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', "
                         f"got {attn_impl!r}")
    kernel_mode = attn_impl == "kernel"
    ps = pool["k"].shape[2]

    with jax.named_scope("attn.read"):   # masks and visibility tables
        j = jnp.arange(total_len)
        causal_ok = j[None, :] < pos[:, None]
        dense_allowed = causal_ok & key_mask                     # (b, L)
        layout = _sparse_layout(cfg, total_len)
        sparse_allowed = dense_allowed & jnp.take(layout, pos, axis=0)

        vis_np, cnt_np, ccnt_np = _sparse_page_visibility(cfg, total_len, ps)
        width = vis_np.shape[1]
        # jaxlint: disable=JL001 — static-config visibility tables, trace-
        # time constants hoisted once per compile (the _sparse_layout idiom)
        vis_rows = jnp.take(jnp.asarray(vis_np), pos, axis=0)    # (b, W)
        vis_cnt = jnp.take(jnp.asarray(cnt_np), pos)             # (b,)
        vis_ccnt = jnp.take(jnp.asarray(ccnt_np), pos)           # (b,)

        bt = _view_tables(block_tables, total_len, ps)
        vis_bt = KV.visible_table_view(bt, vis_rows)             # (b, W)
        # remap the row mask onto the trimmed columns: column w*ps + o of
        # the visible view is logical row vis_rows[:, w]*ps + o; columns
        # past the live count are dead (they would re-count page 0), and so
        # are tail rows past total_len on a partial last page
        cols = (vis_rows[:, :, None] * ps
                + jnp.arange(ps)[None, None, :]).reshape(b, width * ps)
        pad_ok = jnp.repeat(
            jnp.arange(width)[None, :] < vis_cnt[:, None], ps, axis=1)
        vis_allowed = (jnp.take_along_axis(
            sparse_allowed, jnp.minimum(cols, total_len - 1), axis=1)
            & pad_ok & (cols < total_len))

    def attn_layer(lp, h, kv, is_sparse: bool):
        # kv: this layer's slice of the pool (kernel), or its index
        # (gather: ``layer_pool_view`` reads the pool itself)
        p = lp["attn"]
        hn = core.layernorm(p["ln"], h)
        q, k, v = attn_ops.qkv_project(p, hn, cfg.heads)  # (b, h, 1, dh)
        if kernel_mode:
            out = _kernel_read(
                q, k, v, kv["k"], kv["v"], block_tables, pos,
                sparse_allowed if is_sparse else dense_allowed,
                scale=cfg.scale, ksc=kv.get("k_scale"),
                vsc=kv.get("v_scale"),
                visible=vis_rows if is_sparse else None,
                visible_cnt=vis_ccnt if is_sparse else None)
        else:
            out = _paged_gather_attend(
                pool, kv, vis_bt if is_sparse else bt, q, k, v,
                vis_allowed if is_sparse else dense_allowed,
                scale=cfg.scale, mesh=out_sync is not None)
        if out_sync is not None:
            # the mesh seam, unchanged: gather heads before the out
            # projection instead of letting GSPMD partial-sum it
            out = out_sync(out)
        return attn_ops.output_tail(p, out), k, v

    h_in = x_tok[:, None, :]                                  # (b, 1, dim)
    nsteps = cfg.depth // period
    period_pat = tuple(bool(s) for s in pattern[:period])

    def fold(a):
        return a.reshape(nsteps, period, *a.shape[1:])

    xs = jax.tree.map(fold, (params, pool if kernel_mode
                             else jnp.arange(cfg.depth)))

    def body(carry, xs):
        ks_p, vs_p = [], []
        for i, is_sparse in enumerate(period_pat):
            lpi, kvi = jax.tree.map(lambda a, _i=i: a[_i], xs)
            if cfg.reversible:
                x1, x2 = carry
                a, k, v = attn_layer(lpi, x2, kvi, is_sparse)
                y1 = x1 + a
                y2 = x2 + T.ff_or_moe(lpi, y1, cfg, None, False)[0]
                carry = (y1, y2)
            else:
                h = carry
                a, k, v = attn_layer(lpi, h, kvi, is_sparse)
                h = h + a
                carry = h + T.ff_or_moe(lpi, h, cfg, None, False)[0]
            ks_p.append(k)
            vs_p.append(v)
        return carry, (jnp.stack(ks_p), jnp.stack(vs_p))

    carry0 = (h_in, h_in) if cfg.reversible else h_in
    carry, (ks, vs) = lax.scan(body, carry0, xs)
    h_out = (carry[0] + carry[1]) * 0.5 if cfg.reversible else carry
    ks = ks.reshape(cfg.depth, *ks.shape[2:])
    vs = vs.reshape(cfg.depth, *vs.shape[2:])
    return h_out[:, 0, :], ks, vs


# ---------------------------------------------------------------------------
# paged KV: block-table gather / scatter over a shared page pool
# ---------------------------------------------------------------------------
#
# The serve engine's dense slot cache reserves num_slots x total_len rows of
# HBM whether or not a slot is anywhere near total_len. The paged layout
# (PAPERS.md "Ragged Paged Attention"; serve/kv_pool.py is the allocator)
# stores K/V in a shared pool of fixed-size PAGES of whole rows, (depth,
# num_pages, page_size, heads * dim_head), and gives each slot a small int32
# block table mapping logical page j -> physical page id. Requests at different
# positions then share one physical budget: a slot 10 tokens into its
# sequence holds ceil(11/page_size) pages, not total_len rows.
#
# The decode step reads the pool WHERE IT LIES (``attn_impl='gather'``, the
# default): inside the layer scan each layer gathers its own pages through
# the block tables (``layer_pool_view``: whole pages, page-major, indexed
# by layer into the pool itself) and contracts them as the rows they are
# (``_paged_gather_read``: whole rows against all heads' queries on the
# matrix unit, the described blocks' grouped-query read at kv_heads ==
# heads); the gathered pages are the slot's rows in logical order, so the
# softmax is the dense step's and paged-vs-dense tokens are equal. The
# slots are read a group at a time, in the order of their positions, so
# that a group's gathered pages stay in VMEM between the gather and its
# readers and a group reads little further into its table than its
# furthest slot has written: the step's layers run at the narrowest of a
# few static profiles of widths (``view_profiles``) that holds every
# group's rows. The groups and the profiles are decided from the shapes
# and the step's profile from ``pos`` in ONE place (``view_slot_groups``,
# ``view_profile_index``; ``_by_width_profile`` switches the layer stack,
# ``_read_in_slot_groups`` loops the groups) for this step and a
# described block's (``decode_step_block``). The new row is written by in-place row updates
# (``_store_entries_paged``), so the pool keeps one layout, a page one
# contiguous run, through the whole chunk: the compiled program holds no
# buffer of the pool's size besides the pool (tests/test_paged_attention.py
# pins it), and a step reads the mapped table's pages once. A view of ALL
# layers, relaid slot-major before the layer scan, is about seven
# pool-sized passes a step where attention needs one (PERF.md, PR 25).
#
# ``paged_view`` is that view, and the ORACLE: row j of the view is
# position j, so ``_decode_step_math`` over it is literally the dense
# step. The tests hold the per-layer read, the kernel and the engines to
# it; the speculative verify (``decode_loop_spec_paged``) reads through it,
# having no per-layer page-major wide read. The gather reads the
# table's columns up to its slot group's width, live or not (what lies
# between a slot's ``pos`` and its group's width is read and masked); the
# HBM win of paging is *residency* — the pool can be far smaller than
# num_slots x total_len. ``attn_impl='kernel'`` reads only each slot's
# LIVE pages: the
# Pallas ragged paged-attention kernel (ops/paged_attention.py) consumes
# the block tables in place, HBM->VMEM.


@jax.named_scope("kv.view")
def paged_view(pool: dict, block_tables: Array, total_len: int,
               heads: int) -> dict:
    """Dense per-slot view of the classic block's page pool, ALL layers
    at once — the parity oracle of the per-layer read (``layer_pool_view``
    + ``_paged_gather_read``) and of the kernel, and the read of the
    speculative verify. The decode step does not call it: it is a relaid
    copy of the whole pool. Pool (depth, P, page_size, heads * dh)
    gathered through block_tables (b, max_pages) into (depth, b, heads,
    total_len, dh) — logical row j reads physical page ``block_tables[i,
    j // page_size]`` at offset ``j % page_size``, and a row's ``heads``
    runs of dh numbers become the dense cache's head axis. Unmapped table
    entries point at the reserved trash page 0; their rows are never
    attended (causality masks every row >= the slot's pos, and the
    allocator maps pages ahead of pos). Scales (depth, P, page_size,
    heads) gather the same way for the int8 pool
    (kv_pool.init_page_pool).

    The gather width is TRIMMED to ``ceil(total_len / page_size)``
    table columns up front: a caller handing a wider table (block
    tables are sized for the pool's max sequence, not this view's)
    must not drag K/V — or the int8 pool's k_scale/v_scale pages —
    for wholly-unmapped logical pages beyond ``total_len`` through the
    gather just to slice them off; rows and scales share the one trim
    so their shape contract ((..., total_len[, dh])) cannot drift
    (tests/test_paged_attention.py pins it)."""
    block_tables = _view_tables(block_tables, total_len,
                                pool["k"].shape[2])

    def view(buf, per_head):
        g = jnp.take(buf, block_tables, axis=1)   # (d, b, mp, ps, row)
        g = g.reshape(g.shape[:2] + (-1, heads) + per_head)
        return jnp.moveaxis(g[:, :, :total_len], 2, 3)

    dh = pool["k"].shape[-1] // heads
    return {name: view(buf, () if name.endswith("_scale") else (dh,))
            for name, buf in pool.items()}


def _token_rows(x: Array) -> Array:
    """K or V rows per head (depth, b, heads, n, dh), or their int8 scales
    (depth, b, heads, n) -> (depth, b, n, heads * dh) / (depth, b, n,
    heads): a token's row as the page pool holds it
    (``kv_pool.page_layout``), every head's numbers side by side,
    head-major."""
    x = jnp.moveaxis(x, 2, 3)
    return x.reshape(x.shape[:3] + (-1,))


def _store_prompt_pages(buf: Array, rows: Array, page_ids: Array) -> Array:
    """The admission's write of one pool buffer, every block's: the
    prompts' rows ``rows`` (layers, G, n, width) go into ``buf`` (layers,
    P, ps, width) as WHOLE pages: each prompt's rows cut into pages of ps
    rows (the last one filled up with zeros: rows past a prompt are never
    read before the decode overwrites them) and written by page id alone,
    ``page_ids`` (G * ceil(n / ps),) in prompt order, so no index falls in
    a page's tiled (row, width) dims and the pool keeps its layout (a row
    scatter there made the TPU compiler relay the pool: PERF.md, PR 25).
    The trash page takes the pages of unused group rows."""
    ps = buf.shape[2]
    fill = [(0, 0)] * rows.ndim
    fill[2] = (0, -rows.shape[2] % ps)
    rows = jnp.pad(rows, fill)
    return buf.at[:, page_ids].set(
        rows.reshape((rows.shape[0], -1, ps) + rows.shape[3:]))


def _store_rows_paged(pool: dict, ks: Array, vs: Array, pos: Array,
                      block_tables: Array, active: Array,
                      total_len: Optional[int] = None) -> dict:
    """The classic block's new K/V rows (depth, b, heads, W, dh) into its
    page pool: row i of slot b is position ``pos[b] + i``, a row of the
    pool as ``_token_rows`` forms it, stored by ``_store_entries_paged``
    (which says where it lands, and why an INACTIVE slot's goes to the
    trash page). Same quantization contract as the dense writers: the
    int8 pool stores int8 rows and their scales. The decode step writes
    W = 1; the speculative verify W = k rows, of which those past
    ``total_len`` go to the trash page too (the engine's ``_map_ahead``
    maps the FULL speculative horizon before dispatch, so every in-range
    row finds its page mapped)."""
    new = {"k": ks, "v": vs}
    if "k_scale" in pool:
        new["k"], new["k_scale"] = _quantize_rows(ks)
        new["v"], new["v_scale"] = _quantize_rows(vs)
    rows = {name: _token_rows(x) for name, x in new.items()}
    for i in range(ks.shape[3]):
        at, on = pos + i, active
        if total_len is not None:
            on = active & (at < total_len)
            at = jnp.minimum(at, total_len - 1)
        pool = _store_entries_paged(
            pool, {name: r[:, :, i] for name, r in rows.items()}, at,
            block_tables, on)
    return pool


def _store_entries_paged(pool: dict, entries: dict, pos: Array,
                         block_tables: Array, active: Array,
                         ring: bool = False) -> dict:
    """The one store of a decode step's new rows, every block's (a page is
    whole rows in all of them, ``kv_pool.page_layout``): slot i's new row
    of every layer, ``entries[name]`` (layers, b, width), lands in
    ``pool[name]`` (layers, P, ps, width) in physical page
    ``block_tables[i, pos[i] // page_size]`` at offset ``pos[i] %
    page_size``, by one in-place update a slot a buffer: the pool keeps
    the layout in which a page is one contiguous run (a scatter whose
    indices fall in a page's tiled dims made the TPU compiler relay the
    whole pool every step: PERF.md, PR 25). INACTIVE slots are redirected
    to the reserved trash page 0: a dead slot parks at pos 0, and its
    block-table entry 0 may map a physical page the allocator has already
    handed to a NEWER request: writing through it would corrupt live rows
    (the dense layout never has this hazard because a slot owns its rows
    forever). A window pool's
    table is a ``ring`` of its columns: the column is ``pos[i] //
    page_size`` modulo their number, and the store goes by ``kv.window``
    in a trace."""
    with jax.named_scope("kv.window") if ring \
            else jax.named_scope("kv.store"):
        ps = next(iter(pool.values())).shape[2]
        bidx = jnp.arange(pos.shape[0])
        column = pos // ps
        if ring:
            column = column % block_tables.shape[1]
        page = jnp.where(active, block_tables[bidx, column], 0)
        off = jnp.where(active, pos % ps, 0)
        out = {}
        for name, buf in pool.items():      # (layers, P, ps, width)
            for i in range(pos.shape[0]):
                buf = lax.dynamic_update_slice(
                    buf, entries[name][:, i][:, None, None, :],
                    (0, page[i], off[i], 0))
            out[name] = buf
        return out


def window_rows(pos: Array, rows: int, window: int) -> Array:
    """Which position each row of a slot's window ring holds: the ring is
    ``rows`` long, position p is written at row ``p % rows``, so at
    position ``pos`` (b,) row r holds the latest position before ``pos``
    that is r modulo ``rows``. -> (position (b, rows), negative where the
    row was never written; in_window (b, rows): written and less than
    ``window`` behind ``pos``). A row is overwritten ``rows`` positions
    after it was written, when it has left the window
    (``WindowGQABlock.ring_pages``)."""
    # the turn of the ring that ``pos - 1`` lies in, a division a slot;
    # a row behind ``pos - 1`` on that turn holds it, one ahead of it
    # still holds the turn before
    last = pos[:, None] - 1
    turn = jnp.floor_divide(last, rows) * rows + jnp.arange(rows)[None, :]
    held = jnp.where(turn <= last, turn, turn - rows)
    return held, (held >= 0) & (pos[:, None] - held < window)


def ring_key_mask(key_mask: Array, held: Array) -> Array:
    """``key_mask`` (b, total_len) at the positions ``held`` (b, rows)
    that a ring's rows hold (``window_rows``; False where negative). Row r
    only ever holds the positions r, r + rows, ...: one select a turn of
    the ring over whole rows, where a gather of each row's own position
    was 0.66 ms of an 8.2 ms step at 16 x 4112 rows (PERF.md section 6,
    PR 33)."""
    b, rows = held.shape
    total_len = key_mask.shape[1]
    turns = -(-total_len // rows)
    by_turn = jnp.pad(key_mask, (
        (0, 0), (0, turns * rows - total_len))).reshape(b, turns, rows)
    ok = jnp.zeros(held.shape, bool)
    for t in range(turns):
        ok = ok | ((held >= t * rows) & (held < (t + 1) * rows)
                   & by_turn[:, t])
    return ok


def _block_reads(cfg, pool: dict, block_tables: dict, pos: Array,
                 key_mask: Array):
    """The reads of a described block's decode step, one query a slot
    (``block_tables``: a table a page pool, ``{"full": ..., "window":
    ...}``): ->
    (``read_of(layer, run, widths)``, which gives ``block_layer`` its read
    for the layer ``layer`` (traced, its index in the cache it reads) of
    the run ``run``, at the static per-group ``widths`` of the profile
    that the caller switched the layer's scan to, or None; the step's
    width profile for the ordered pool, ``_width_profile``'s, or None). A
    paged read gathers its layer's pages through the tables
    (``layer_pool_view``), a slot group at a time (the groups are decided
    by ``view_slot_groups``, the classic step's rule, and looped by
    ``_read_in_slot_groups``), and contracts them as they lie; a layer
    that reads ANOTHER layer's rows (``LayerKind.stores`` False) reads the
    same pool at that layer's index. A recurrent layer's read (a
    state-space layer's, a short convolution's) takes what every slot
    carries of the layer and advances it by the token."""
    from dalle_pytorch_tpu.ops import transformer as T
    blk = cfg.block
    total_len = key_mask.shape[1]
    # the reads of the pool whose rows lie in order go through the slots
    # in the order of ``pos``, at the step's width profile
    # (``_by_width_profile``): its table and mask are laid in that order
    # and the profile is chosen here, once a step. A window pool's ring
    # stays in slot order, whole (all of it is live once it has wrapped),
    # and so does the ordered pool for a layer that is no scan's (a run of
    # ONE layer runs in the step's own body)
    with jax.named_scope("attn.read"):
        order, inverse = _slot_order(pos)

    def full_views():
        """The ordered pool's table as its reads take it, {scanned: (view,
        its rows strictly before ``pos`` (b, w * ps): self is each read's
        own extra logit; rows past total_len on a partial last page are
        dead; the step's profile)}: for a scanned run's layer in the
        order of ``pos``, for a lone layer in slot order with no
        profile."""
        ps = _rows_buffer(pool).shape[2]
        with jax.named_scope("kv.view"):
            tables = _view_tables(block_tables["full"], total_len, ps)
            views = {False: _View(tables),
                     True: _View(tables[order], order, inverse)}
        out = {}
        for scanned, view in views.items():
            with jax.named_scope("attn.read"):
                at, valid = (pos[order], key_mask[order]) if scanned \
                    else (pos, key_mask)
                rows_len = tables.shape[1] * ps
                allowed = (jnp.arange(rows_len)[None, :] < at[:, None]) \
                    & jnp.pad(valid, ((0, 0), (0, rows_len - total_len)))
            out[scanned] = (view, allowed, _width_profile(
                pool, tables.shape[1], at) if scanned else None)
        return out
    full = full_views() if "latent" in pool or "k" in pool else None

    def at_profile(profile, widths, rows, view, read_group):
        """The read at the step's profile: whole in slot order where the
        view has none; at ``widths`` where the layer's scan was switched
        (``decode_step_block``); else its own switch."""
        if profile is None or widths is not None:
            return _read_in_slot_groups(
                rows, view._replace(widths=widths), read_group)
        return _by_width_profile(
            profile, lambda widths: _read_in_slot_groups(
                rows, view._replace(widths=widths), read_group))

    def full_view(run, widths):
        """A layer's view of the ordered pool (``block_view_plan``): in
        the order of ``pos`` at its switched scan's ``widths``, or with a
        switch of its own inside a scanned run; a lone layer outside any
        switch whole in slot order."""
        return full[widths is not None or run.count > 1]

    if isinstance(blk, T.LatentMoEBlock):
        ps = pool["latent"].shape[2]

        def read_of(layer, run, widths=None):
            view, allowed, profile = full_view(run, widths)

            def read(p, query, entry):
                # all slots' pages at once miss VMEM at the published
                # widths (178 MB), so they are read a slot group at a time
                q_nope, q_rope, own = view.ordered(*query, entry)

                def read_group(sl, w):
                    return attn_ops.latent_attend_absorbed(
                        p, q_nope[sl], q_rope[sl],
                        _gathered_rows(pool["latent"], layer,
                                       view.tables[sl, :w]),
                        allowed[sl, :w * ps], own[sl], blk, cfg.scale)
                return at_profile(profile, widths, pool, view, read_group)
            return read
        return read_of, full[True][2]

    # pools of whole K and V rows: a pool and a table a layer type. A full
    # layer's table is as wide as the sequence and its rows lie in order;
    # a window layer's is a ring of ``ring_pages`` columns
    if "window_k" in pool:
        ring_t = block_tables["window"]
        with jax.named_scope("attn.window"):
            held, ring_ok = window_rows(
                pos, ring_t.shape[1] * pool["window_k"].shape[2], blk.window)
            ring = (_View(ring_t, window=True),
                    ring_ok & ring_key_mask(key_mask, held), None)

    def read_of(layer, run, widths=None):
        if run.kind.pool is None:
            return None             # a layer that reads no cache
        if run.kind.pool == "state":
            def advance(p, x, _entry):
                with T.state_scope(blk):
                    state = tuple(lax.dynamic_index_in_dim(
                        pool[name], layer, keepdims=False)
                        for name in blk.pool_buffers("state"))
                return T.STATE_STEP[run.kind.mixer](
                    p, x, state if len(state) > 1 else state[0])
            return advance
        window = not run.full
        k_name, v_name = blk.pool_buffers(run.kind.pool)
        if window:
            view, allowed, profile = ring
            widths = None           # (a switched stack's ring all the same)
        else:
            view, allowed, profile = full_view(run, widths)
        rows = {"k": pool[k_name]}      # what decides the slot groups
        ps = pool[k_name].shape[2]
        several = pool_view_groups(rows, *view.tables.shape,
                                   view.order is not None) > 1

        def read(p, q, entry):
            lam = attn_ops.diff_lambda(p) if "lam" in p else None
            q, own_k, own_v = view.ordered(q, *entry)

            def read_group(sl, w):
                t = view.tables[sl, :w]
                return attn_ops.gqa_attend_rows(
                    q[sl], own_k[sl], own_v[sl],
                    _gathered_rows(pool[k_name], layer, t, window),
                    lambda wts: _gathered_rows(
                        pool[v_name], layer, t, window,
                        after=wts if several else None),
                    allowed[sl, :w * ps], cfg.scale, window, diff_lam=lam,
                    sink=p.get("sink"))
            return at_profile(profile, widths, rows, view, read_group)
        return read
    return read_of, full[True][2] if full else None


def _store_block_rows(cfg, pool: dict, entries: dict, pos: Array,
                      block_tables, active: Array) -> dict:
    """A decode step's new entries (``block_stack``'s, a buffer of the
    pool each) into a described block's pool: the new rows of every layer
    of a paged pool through its table (``_store_entries_paged``), a
    recurrent layer's new state over the old one where the slot is
    active."""
    from dalle_pytorch_tpu.ops import transformer as T
    blk = cfg.block
    out = {}
    for kind, names in blk.pools(cfg.depth).items():
        if kind == "state":
            with T.state_scope(blk):
                for name in names:
                    on = active.reshape((1, -1) + (1,) * (
                        pool[name].ndim - 2))
                    out[name] = jnp.where(on, entries[name], pool[name])
            continue
        # (layers, b, kv_heads, dh) -> (layers, b, kv_heads * dh): a row
        out.update(_store_entries_paged(
            {n: pool[n] for n in names},
            {n: entries[n].reshape(entries[n].shape[:2] + (-1,))
             for n in names},
            pos, block_tables[kind], active, ring=kind == "window"))
    return out


def decode_step_block(params: dict, x_tok: Array, pos: Array, pool: dict,
                      block_tables, *, cfg, key_mask: Array,
                      active: Array) -> Tuple[Array, dict, Array]:
    """One token a slot through a described block (``cfg.block``) against
    its page pool(s): ``decode_step_paged``'s gather step with the block's
    branches (``ops.transformer.block_layer``) and the block's reads
    (``_block_reads``): the latent block's ABSORBED read of its one latent
    pool; grouped-query or differential reads, a full layer's of the full
    pool in row order and a window layer's of the window pool's ring
    (``block_tables`` is then ``{"full": ..., "window": ...}``), a cross
    layer's of the full pool at the sharing layer's index; a state-space
    layer's step against its slot's state. The new rows and states are
    written after the scan; an inactive slot's state stays as it was.
    x_tok (b, dim), pos (b,) -> (h_out (b, dim), pool, load int32: the
    routed layers' load summed over them, ops.moe.dropless_apply). Of a
    block whose window layers hold a sink the load is float32 and two
    numbers longer (``load_like``): the sink's softmax weight summed over
    the window layers, the query heads and the ACTIVE slots, and the
    number of softmaxes that is summed over."""
    from dalle_pytorch_tpu.ops import transformer as T
    if not isinstance(block_tables, dict):      # one pool: its one table
        block_tables = {"full": block_tables}
    read_of, profile = _block_reads(cfg, pool, block_tables, pos, key_mask)

    def layer_fn(lp, h, shared, layer, run, widths=None):
        return T.block_layer(lp, h, shared, pos, read_of(layer, run, widths),
                             cfg, run)

    # the one switch of the step, around the scans whose layers read the
    # ordered pool, where ``block_view_plan`` has one; else ``read_of``
    # switches a scanned run's reads one by one
    plan = block_view_plan(cfg, params, pool, x_tok.shape[0],
                           key_mask.shape[1])
    span = None if plan is None or plan.span is None else (
        *plan.span, functools.partial(_by_width_profile, profile))
    h_out, entries, loads, shared = T.block_stack(params, x_tok, layer_fn,
                                                  cfg, span)
    load = jnp.sum(loads, axis=0)
    if cfg.block.sink:
        with jax.named_scope("attn.window"):
            reads = len(cfg.block.cache_layers("window", cfg.depth)) \
                * cfg.heads * jnp.sum(active)
            load = jnp.concatenate([load, jnp.stack([
                jnp.sum(jnp.where(active, shared["sink_mass"], 0.0)),
                reads])], dtype=load_like(cfg.block).dtype)
    return (h_out, _store_block_rows(cfg, pool, entries, pos, block_tables,
                                     active), load)


def load_like(blk) -> jax.ShapeDtypeStruct:
    """What a described block's decode step counts
    (``decode_step_block``): ``ops.moe.load_width`` int32s; with a sink
    two more, and all of them float32, since the sink's weight is one (the
    counts stay whole: a chunk's are far under 2 ** 24)."""
    if blk.sink:
        return jax.ShapeDtypeStruct((load_width(blk) + 2,), jnp.float32)
    return jax.ShapeDtypeStruct((load_width(blk),), jnp.int32)


def decode_step_paged(params: dict, x_tok: Array, pos: Array, pool: dict,
                      block_tables: Array, *, cfg, key_mask: Array,
                      active: Array, attn_impl: str = "gather",
                      sparse_reads: bool = False,
                      out_sync=None) -> Tuple[Array, dict]:
    """``decode_step`` against the paged pool: the one shared step math
    is handed the RAW pool and the block tables, whatever the impl.
    ``attn_impl='gather'`` (default) gathers each layer's pages inside
    the layer scan and attends them where they lie (``layer_pool_view``
    + ``_paged_gather_read``): the rows, masks and softmax of the dense
    step, so tokens equal the dense step's. ``attn_impl='kernel'`` has
    the Pallas ragged paged-attention kernel consume the tables in
    place (only each slot's live pages move) and the same body merge
    its partials; the two impls share every line outside the K/V read
    itself. Either way the new row scatters back into its page;
    ``active`` routes dead slots' writes to the trash page
    (``_store_rows_paged``).

    ``sparse_reads=True``: sparse layers read only their statically
    visible pages while dense layers read as before
    (``_decode_step_math_sparse_reads``) — same step math, same
    writers, fewer bytes moved per token."""
    h_out, ks, vs = _decode_step_math(
        params, x_tok, pos, pool, cfg=cfg, key_mask=key_mask,
        attn_impl=attn_impl, block_tables=block_tables,
        sparse_reads=sparse_reads, out_sync=out_sync)
    return h_out, _store_rows_paged(pool, ks, vs, pos, block_tables, active)


def decode_loop_paged(params: dict, cur_tok: Array, pos: Array,
                      active: Array, pool: dict, block_tables: Array, *,
                      cfg, key_mask: Array, total_len: int, steps: int,
                      embed_fn, sample_fn, attn_impl: str = "gather",
                      sparse_reads: bool = False,
                      out_sync=None
                      ) -> Tuple[Array, Array, Array, dict, Array]:
    """``decode_loop`` over the paged pool: the same one-compile fused
    K-step scan and emit-ring contract, with (cur_tok, pos, active, pool)
    as the carry and the block tables a per-chunk constant (the host
    grows them BEFORE dispatch — serve/engine.py maps every page the K
    steps could write, so a mid-chunk page-boundary crossing finds its
    page already mapped). Dead slots park at (tok 0, pos 0) writing the
    trash page; emit semantics (-1 sentinel) are identical to the dense
    loop. ``attn_impl`` selects the per-step K/V read: the per-layer
    page gather or the in-place Pallas kernel — both run inside the
    SAME fused scan, so the one-compile/emit-ring regime is unchanged.
    ``sparse_reads`` turns on sparsity-aware reads for the sparse
    layers (visibility tables are trace-time constants, so the fused
    program still traces exactly once).

    A described block (``cfg.block``) runs the gather step with its own
    branches (``decode_step_block``) and the program returns one value
    more, after the ring: the routed layers' load (``load_like``) summed
    over the chunk's steps, for the engine to fetch with the ring; its
    ``block_tables`` are what ``decode_step_block`` takes
    (a table a pool for a window-and-full block)."""
    blk = cfg.block
    if blk is not None:
        for option, on in (("paged_attn='kernel'", attn_impl != "gather"),
                           ("sparse_reads", sparse_reads),
                           ("a mesh (out_sync)", out_sync is not None)):
            if on:
                _refuse_block(cfg, option)

    def one_step(carry, _):
        cur_tok, pos, act, pool, *load = carry
        emit = jnp.where(act, cur_tok, -1)
        x = embed_fn(cur_tok, pos)
        if blk is None:
            h, pool = decode_step_paged(
                params, x, pos, pool, block_tables, cfg=cfg,
                key_mask=key_mask, active=act, attn_impl=attn_impl,
                sparse_reads=sparse_reads, out_sync=out_sync)
        else:
            h, pool, step_load = decode_step_block(
                params, x, pos, pool, block_tables, cfg=cfg,
                key_mask=key_mask, active=act)
            load = [load[0] + step_load]
        nxt = sample_fn(h, pos + 1)
        pos = pos + 1
        act = act & (pos < total_len)
        cur_tok = jnp.where(act, nxt, 0)
        pos = jnp.where(act, pos, 0)
        return (cur_tok, pos, act, pool, *load), emit

    load0 = ()
    if blk is not None:
        like = load_like(blk)
        load0 = (jnp.zeros(like.shape, like.dtype),)
    (cur_tok, pos, active, pool, *load), emits = lax.scan(
        one_step, (cur_tok, pos, active, pool, *load0), None, length=steps)
    return (cur_tok, pos, active, pool, jnp.moveaxis(emits, 0, 1), *load)


# ---------------------------------------------------------------------------
# speculative decode: draft-and-verify inside the fused serving loop
# ---------------------------------------------------------------------------
#
# The single biggest latency lever left after the fused-K loop: sequential
# image-token steps are latency-bound on the FULL stack's depth, but most
# tokens are cheap to predict. Draft-and-verify runs a SHALLOW draft (the
# first d transformer layers + the same logit head — an early exit, no
# extra weights) to propose k tokens, then ONE k-wide pass through the
# full model verifies all of them at once. Because sampling here is a
# DETERMINISTIC function of (logits, fold_in(rng, position)) — see
# models.dalle.sample_per_slot — the verify pass computes exactly the
# token the eager loop would have emitted at every offset: accept the
# longest prefix where the draft matched, take the verify sample at the
# first mismatch as the (always-correct) continuation, and the emitted
# stream is BYTE-IDENTICAL to eager generate_images by construction —
# not distributionally equivalent, identical. Rejection costs nothing
# but the wasted draft work: the cache rows written past the accepted
# prefix are stale-by-invariant (reads only ever touch rows < the
# chunk-start pos, and the next round rewrites them before pos crosses),
# so pos never rewinds and no KV pages are ever unmapped on a rejection.
#
# The wide verify is structurally a K-wide decode chunk: the same
# layernorm/qkv/read/store seams as ``_decode_step_math``, with W query
# rows per slot instead of one. Query i (position pos+i) attends the
# CACHED prefix (rows j < pos — rows >= pos are stale and never read)
# plus the chunk's own fresh K/V rows 0..i (triangular intra mask, self
# always attended — the narrow path's concatenated self-logit,
# generalized). W = 1 reduces to the narrow math exactly, so k=1
# speculation IS the eager loop.


@jax.named_scope("attn.read")
def _gather_read_wide(q: Array, k: Array, v: Array, ck: Array, cv: Array,
                      allowed_cached: Array, allowed_intra: Array, *,
                      scale: float, ksc: Optional[Array] = None,
                      vsc: Optional[Array] = None) -> Array:
    """W-wide twin of ``_gather_read``: q/k/v (b, h, W, dh) fresh chunk
    rows, ck/cv (b, h, L, dh) cached rows, allowed_cached (b, W, L) the
    per-query cached-row mask, allowed_intra (b, W, W) the intra-chunk
    mask (triangular, diagonal True — self is always attended, exactly
    the narrow path's unmasked self-logit). One softmax over the
    concatenated [cached, intra] logits per query; int8 scales applied
    outside the contractions in score dtype, the narrow path's
    contract. Returns (b, h, W, dh)."""
    quantized = ksc is not None
    ckc = ck.astype(q.dtype) if quantized else ck
    scores = jnp.einsum("bhqd,bhjd->bhqj", q, ckc) * scale
    if quantized:
        scores = scores * ksc[:, :, None, :].astype(scores.dtype)
    scores = jnp.where(allowed_cached[:, None], scores,
                       core.neg_inf(scores.dtype))
    intra = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    intra = jnp.where(allowed_intra[:, None], intra,
                      core.neg_inf(intra.dtype))
    w = jax.nn.softmax(jnp.concatenate([scores, intra], -1), axis=-1)
    L = ck.shape[2]
    wj, wi = w[..., :L], w[..., L:]
    if quantized:
        wj = wj * vsc[:, :, None, :].astype(wj.dtype)
        cvc = cv.astype(q.dtype)
    else:
        cvc = cv
    return (jnp.einsum("bhqj,bhjd->bhqd", wj, cvc)
            + jnp.einsum("bhqk,bhkd->bhqd", wi, v))


@jax.named_scope("attn.read")
def _kernel_read_wide(q: Array, k: Array, v: Array, pool_k: Array,
                      pool_v: Array, block_tables: Array, pos: Array,
                      allowed_cached: Array, allowed_intra: Array, *,
                      scale: float, ksc: Optional[Array] = None,
                      vsc: Optional[Array] = None) -> Array:
    """W-wide twin of ``_kernel_read``: one Pallas ragged-paged-attention
    call per offset (a static python loop — W is a small compile-time
    constant), each walking the cached pages up to the CHUNK-START
    ``pos`` with that offset's row mask, then a generalized two-estimate
    merge folds in the offset's intra-chunk logits (keys 0..i, self
    included). W = 1 with an all-True 1x1 intra mask is exactly the
    narrow merge."""
    from dalle_pytorch_tpu.ops import paged_attention as PA
    W = q.shape[2]
    outs = []
    for i in range(W):
        acc, m, l = PA.paged_decode_attention(
            q[:, :, i, :], pool_k, pool_v, block_tables, pos,
            allowed_cached[:, i, :], scale=scale, k_scales=ksc,
            v_scales=vsc)
        s = (jnp.einsum("bhd,bhkd->bhk", q[:, :, i, :],
                        k[:, :, :i + 1, :]).astype(jnp.float32) * scale)
        s = jnp.where(allowed_intra[:, None, i, :i + 1], s,
                      core.neg_inf(jnp.float32))
        m2 = jnp.max(s, axis=-1)               # self is finite: m2 too
        m_t = jnp.maximum(m, m2)
        alpha = jnp.exp(m - m_t)
        wk = jnp.exp(s - m_t[..., None])
        denom = l * alpha + jnp.sum(wk, axis=-1)
        out = (acc * alpha[..., None]
               + jnp.einsum("bhk,bhkd->bhd", wk,
                            v[:, :, :i + 1, :].astype(jnp.float32))) \
            / denom[..., None]
        outs.append(out.astype(q.dtype))
    return jnp.stack(outs, axis=2)


def _decode_chunk_math(params: dict, x_toks: Array, pos: Array,
                       cache: dict, *, cfg, key_mask: Array,
                       attn_impl: str = "gather",
                       block_tables: Optional[Array] = None,
                       out_sync=None) -> Tuple[Array, Array, Array]:
    """W-wide generalization of ``_decode_step_math`` — the speculative
    verify (and draft) program's core. x_toks (b, W, dim) are the
    embeddings of the tokens at positions pos..pos+W-1 (pos (b,) the
    per-slot chunk start); the cache holds valid rows STRICTLY below
    ``pos`` only (rows at/past pos are stale and never read — the
    chunk's own K/V is carried fresh through the triangular intra mask
    instead). Returns (h_out (b, W, dim), ks, vs (depth, b, heads, W,
    dh)) — the caller owns the write-back, same split as the narrow
    math. ``attn_impl='kernel'`` reads ``cache`` as the raw page pool
    through ``block_tables`` (one kernel walk per offset); ``'gather'``
    reads it as a dense per-slot view (the dense slot cache or
    ``paged_view``). Sparse layers mask by the layout row of each
    query's own position, intra keys included; the chunk-local self is
    always attended (the narrow path's self-logit contract)."""
    from dalle_pytorch_tpu.ops import transformer as T
    b, W, _ = x_toks.shape
    total_len = key_mask.shape[1]
    sparse_flags = jnp.asarray(cfg.sparse_pattern)
    any_sparse = any(cfg.sparse_pattern)
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', "
                         f"got {attn_impl!r}")
    kernel_mode = attn_impl == "kernel"
    if kernel_mode and block_tables is None:
        raise ValueError("attn_impl='kernel' requires block_tables")
    if getattr(pos, "ndim", 0) != 1:
        raise ValueError("the wide chunk math requires per-slot (b,) "
                         "positions (the serving decode shape)")

    with jax.named_scope("attn.read"):       # the masks
        j = jnp.arange(total_len)
        offs = jnp.arange(W)
        # cached rows: strictly before the CHUNK START for every query
        # (rows in [pos, pos+i) are stale — the fresh intra keys stand in)
        causal_c = j[None, :] < pos[:, None]                      # (b, L)
        dense_cached = jnp.broadcast_to(
            (causal_c & key_mask)[:, None, :], (b, W, total_len))
        # intra-chunk: key kk visible to query i iff kk <= i (self included)
        tri = offs[:, None] >= offs[None, :]                      # (W, W)
        dense_intra = jnp.broadcast_to(tri[None], (b, W, W))
        if any_sparse:
            layout = _sparse_layout(cfg, total_len)
            qrows = jnp.minimum(pos[:, None] + offs[None, :],
                                total_len - 1)                    # (b, W)
            lrows = jnp.take(layout, qrows, axis=0)               # (b, W, L)
            sparse_cached = dense_cached & lrows
            intra_lay = jnp.take_along_axis(
                lrows, jnp.broadcast_to(qrows[:, None, :], (b, W, W)),
                axis=2)                      # (b, W, W): layout[p+i, p+kk]
            # jaxlint: disable=JL001 — static W identity, trace-time const
            self_eye = jnp.eye(W, dtype=bool)[None]
            sparse_intra = dense_intra & (intra_lay | self_eye)
        else:
            sparse_cached, sparse_intra = dense_cached, dense_intra

    quantized = "k_scale" in cache

    def attn_cached(lp, h, ck, cv, is_sparse, ksc=None, vsc=None):
        p = lp["attn"]
        hn = core.layernorm(p["ln"], h)
        q, k, v = attn_ops.qkv_project(p, hn, cfg.heads)  # (b, h, W, dh)
        with jax.named_scope("attn.read"):
            a_c = jnp.where(is_sparse, sparse_cached, dense_cached) \
                if any_sparse else dense_cached
            a_i = jnp.where(is_sparse, sparse_intra, dense_intra) \
                if any_sparse else dense_intra
        if kernel_mode:
            out = _kernel_read_wide(q, k, v, ck, cv, block_tables, pos,
                                    a_c, a_i, scale=cfg.scale, ksc=ksc,
                                    vsc=vsc)
        else:
            out = _gather_read_wide(q, k, v, ck, cv, a_c, a_i,
                                    scale=cfg.scale, ksc=ksc, vsc=vsc)
        if out_sync is not None:
            # the mesh seam, unchanged: gather heads before the out
            # projection instead of letting GSPMD partial-sum it
            out = out_sync(out)
        return attn_ops.output_tail(p, out), k, v

    def body(carry, xs):
        if quantized:
            lp, ck, cv, ksc, vsc, is_sparse = xs
        else:
            lp, ck, cv, is_sparse = xs
            ksc = vsc = None
        if cfg.reversible:
            x1, x2 = carry
            a, k, v = attn_cached(lp, x2, ck, cv, is_sparse, ksc, vsc)
            y1 = x1 + a
            y2 = x2 + T.ff_or_moe(lp, y1, cfg, None, False)[0]
            return (y1, y2), (k, v)
        h = carry
        a, k, v = attn_cached(lp, h, ck, cv, is_sparse, ksc, vsc)
        h = h + a
        h = h + T.ff_or_moe(lp, h, cfg, None, False)[0]
        return h, (k, v)

    carry0 = (x_toks, x_toks) if cfg.reversible else x_toks
    xs = (params, cache["k"], cache["v"], cache["k_scale"],
          cache["v_scale"], sparse_flags) if quantized else \
        (params, cache["k"], cache["v"], sparse_flags)
    carry, (ks, vs) = lax.scan(body, carry0, xs)
    h_out = (carry[0] + carry[1]) * 0.5 if cfg.reversible else carry
    return h_out, ks, vs


@jax.named_scope("kv.store")
def _store_rows_wide(cache: dict, ks: Array, vs: Array,
                     pos: Array) -> dict:
    """W-wide twin of ``_store_rows_per_slot``: ks/vs (depth, b, heads,
    W, dh), slot b's row i lands at cache row pos[b]+i. Rows past the
    cache end are DROPPED (``mode='drop'``) — the chunk near the
    sequence end writes only its in-range rows, and a parked dead slot
    rewrites rows 0..W-1, which admission's prefill and the first
    verify chunk always overwrite before any read (the stale-rows
    invariant). Same quantization contract as every other writer."""
    b = pos.shape[0]
    W = ks.shape[3]
    bidx = jnp.arange(b)[:, None]                             # (b, 1)
    rows = pos[:, None] + jnp.arange(W)[None, :]              # (b, W)

    def put_rows(buf, r):
        # buf (depth, b, heads, L, dh); advanced indices at dims 1 and 3
        # are non-adjacent, so the update value is (b, W, depth, heads,
        # dh)
        return buf.at[:, bidx, :, rows, :].set(
            jnp.transpose(r, (1, 3, 0, 2, 4)), mode="drop")

    def put_scales(buf, sc):
        # buf (depth, b, heads, L); value (b, W, depth, heads)
        return buf.at[:, bidx, :, rows].set(
            jnp.transpose(sc, (1, 3, 0, 2)), mode="drop")

    if "k_scale" in cache:
        kq, ksc = _quantize_rows(ks)
        vq, vsc = _quantize_rows(vs)
        return {"k": put_rows(cache["k"], kq),
                "v": put_rows(cache["v"], vq),
                "k_scale": put_scales(cache["k_scale"], ksc),
                "v_scale": put_scales(cache["v_scale"], vsc)}
    return {"k": put_rows(cache["k"], ks), "v": put_rows(cache["v"], vs)}


def speculative_draft(draft_params: dict, cur_tok: Array, pos: Array,
                      read_cache: dict, *, cfg, key_mask: Array, k: int,
                      embed_fn, sample_fn, attn_impl: str = "gather",
                      block_tables: Optional[Array] = None,
                      out_sync=None) -> Array:
    """Propose k-1 draft tokens with the SHALLOW early-exit head:
    ``draft_params`` is the first-d-layers slice of the stacked
    transformer params and ``cfg`` its depth-d config
    (``models.dalle.draft_transformer_config``), run through the same
    logit head and the SAME per-slot sampler — so with d == depth the
    draft IS the target model and every proposal verifies (the
    acceptance-test lever). Stash-free: draft step t recomputes the
    t-wide chunk math over the tokens so far (no cache write, ~d·k²/2
    rows — cheap for the small k this targets). Returns (b, k-1) int32
    (an empty (b, 0) when k == 1: no draft runs, speculation degrades
    to the eager step exactly)."""
    toks = [cur_tok]
    for t in range(1, k):
        xs = jnp.stack([embed_fn(tok, pos + i)
                        for i, tok in enumerate(toks)], axis=1)
        h, _, _ = _decode_chunk_math(
            draft_params, xs, pos, read_cache, cfg=cfg,
            key_mask=key_mask, attn_impl=attn_impl,
            block_tables=block_tables, out_sync=out_sync)
        toks.append(sample_fn(h[:, -1, :], pos + t))
    if k == 1:
        return jnp.zeros((cur_tok.shape[0], 0), jnp.int32)
    return jnp.stack(toks[1:], axis=1)


def speculative_verify(params: dict, cur_tok: Array, drafts: Array,
                       pos: Array, act: Array, read_cache: dict, *, cfg,
                       key_mask: Array, total_len: int, embed_fn,
                       sample_fn, attn_impl: str = "gather",
                       block_tables: Optional[Array] = None,
                       out_sync=None):
    """ONE full-model pass over [cur_tok, drafts] (k tokens wide),
    accept the longest matching prefix. Per offset i the verify sample
    ``s_i = sample_fn(h_i, pos+i+1)`` is EXACTLY the token the eager
    loop would emit at that position (deterministic fold_in(rng, pos)
    sampling), so acceptance is equality — not a stochastic test — and
    the first rejected offset's verify sample is itself the correct
    continuation (the "free" token: even total rejection advances one
    position, like eager). The accepted length is clamped at the
    sequence end so the emitted window never crosses ``total_len``.

    Returns ``(emit (b, k), cur_new, pos_new, act_new, ks, vs)``:
    emit[i] holds the token at position pos+i or the -1 harvest
    sentinel; ks/vs are ALL k fresh K/V rows (depth, b, heads, k, dh)
    for the caller's write-back — rows past the accepted prefix are
    stale-by-invariant, overwritten by the next round before the
    chunk-start pos ever crosses them, so rejection needs no rewind
    and no page unmapping."""
    b = cur_tok.shape[0]
    k = drafts.shape[1] + 1
    toks = [cur_tok] + [drafts[:, t] for t in range(k - 1)]
    xv = jnp.stack([embed_fn(tok, pos + i)
                    for i, tok in enumerate(toks)], axis=1)
    h, ks, vs = _decode_chunk_math(
        params, xv, pos, read_cache, cfg=cfg, key_mask=key_mask,
        attn_impl=attn_impl, block_tables=block_tables,
        out_sync=out_sync)
    s = jnp.stack([sample_fn(h[:, i, :], pos + i + 1)
                   for i in range(k)], axis=1)                # (b, k)
    if k > 1:
        match = (s[:, :k - 1] == drafts).astype(jnp.int32)
        jm = jnp.sum(jnp.cumprod(match, axis=1), axis=1)      # [0, k-1]
    else:
        jm = jnp.zeros_like(pos)
    # accepted END offset: positions pos..pos+e emit (e+1 tokens),
    # clamped so the last emitted position stays < total_len (an active
    # slot always has pos <= total_len-1, so e >= 0)
    e = jnp.minimum(jm, total_len - 1 - pos)
    offs = jnp.arange(k)
    emit_vals = jnp.concatenate([cur_tok[:, None], s[:, :k - 1]],
                                axis=1)
    emit = jnp.where(act[:, None] & (offs[None, :] <= e[:, None]),
                     emit_vals, -1)
    cur_new = jnp.take_along_axis(s, e[:, None], axis=1)[:, 0]
    pos_new = pos + e + 1
    act_new = act & (pos_new < total_len)
    # dead slots park at (tok 0, pos 0), the eager loop's contract
    cur_new = jnp.where(act_new, cur_new, 0)
    pos_new = jnp.where(act_new, pos_new, 0)
    return emit, cur_new, pos_new, act_new, ks, vs


@jax.named_scope("kv.view")
def _draft_cache_view(read_cache: dict, depth: int) -> dict:
    """The draft's read view: the first ``depth`` layers of the full
    cache/view/pool (every KV layout carries depth on the leading
    axis, int8 scales included)."""
    return {key: buf[:depth] for key, buf in read_cache.items()}


def decode_loop_spec(params: dict, draft_params: dict, cur_tok: Array,
                     pos: Array, active: Array, cache: dict, *, cfg,
                     draft_cfg, key_mask: Array, steps: int, k: int,
                     embed_fn, sample_fn, out_sync=None
                     ) -> Tuple[Array, Array, Array, dict, Array]:
    """``decode_loop`` with draft-and-verify speculation: each of the
    ``steps`` scanned rounds drafts k-1 tokens through the shallow head,
    verifies all k in ONE full-model k-wide pass, and emits the accepted
    prefix — between 1 and k tokens per round, every one byte-identical
    to the eager loop's. Same one-compile fused-program regime; the emit
    ring widens to (b, steps*k) with the -1 sentinel filling rejected
    offsets and finished slots, which the harvest's ``row[row >= 0]``
    already handles (delivered tokens only — rejected drafts never
    reach the host accounting)."""
    _refuse_block(cfg, "speculative")
    total_len = cache["k"].shape[3]

    def one_round(carry, _):
        cur_tok, pos, act, cache = carry
        drafts = speculative_draft(
            draft_params, cur_tok, pos,
            _draft_cache_view(cache, draft_cfg.depth), cfg=draft_cfg,
            key_mask=key_mask, k=k, embed_fn=embed_fn,
            sample_fn=sample_fn, out_sync=out_sync)
        emit, cur_tok, pos_new, act, ks, vs = speculative_verify(
            params, cur_tok, drafts, pos, act, cache, cfg=cfg,
            key_mask=key_mask, total_len=total_len, embed_fn=embed_fn,
            sample_fn=sample_fn, out_sync=out_sync)
        cache = _store_rows_wide(cache, ks, vs, pos)
        return (cur_tok, pos_new, act, cache), emit

    (cur_tok, pos, active, cache), emits = lax.scan(
        one_round, (cur_tok, pos, active, cache), None, length=steps)
    ring = jnp.moveaxis(emits, 0, 1).reshape(cur_tok.shape[0],
                                             steps * k)
    return cur_tok, pos, active, cache, ring


def decode_loop_spec_paged(params: dict, draft_params: dict,
                           cur_tok: Array, pos: Array, active: Array,
                           pool: dict, block_tables: Array, *, cfg,
                           draft_cfg, key_mask: Array, total_len: int,
                           steps: int, k: int, embed_fn, sample_fn,
                           attn_impl: str = "gather", out_sync=None
                           ) -> Tuple[Array, Array, Array, dict, Array]:
    """``decode_loop_paged`` with draft-and-verify speculation: the
    paged twin of ``decode_loop_spec`` — the k-wide verify rides the
    block tables (``paged_view``'s all-layer dense view, which the
    narrow step has left — the wide read has no per-layer page-major
    form yet — or one in-place Pallas kernel walk per offset under
    ``attn_impl='kernel'``), and all k fresh rows go back through
    ``_store_rows_paged`` (inactive/overflow rows to the trash
    page). The host maps the FULL speculative horizon (steps*k rows)
    before dispatch, and rejection never unmaps anything — pos only
    advances, so the no-alloc-churn contract holds per round, not just
    per chunk. ``sparse_reads`` does not compose (rejected at engine
    construction): the wide verify has no trimmed-visibility wide read."""
    _refuse_block(cfg, "speculative")
    kernel = attn_impl == "kernel"

    def one_round(carry, _):
        cur_tok, pos, act, pool = carry
        read = pool if kernel else paged_view(pool, block_tables,
                                              total_len, cfg.heads)
        bt = block_tables if kernel else None
        impl = "kernel" if kernel else "gather"
        drafts = speculative_draft(
            draft_params, cur_tok, pos,
            _draft_cache_view(read, draft_cfg.depth), cfg=draft_cfg,
            key_mask=key_mask, k=k, embed_fn=embed_fn,
            sample_fn=sample_fn, attn_impl=impl, block_tables=bt,
            out_sync=out_sync)
        emit, cur_tok, pos_new, act, ks, vs = speculative_verify(
            params, cur_tok, drafts, pos, act, read, cfg=cfg,
            key_mask=key_mask, total_len=total_len, embed_fn=embed_fn,
            sample_fn=sample_fn, attn_impl=impl, block_tables=bt,
            out_sync=out_sync)
        pool = _store_rows_paged(pool, ks, vs, pos, block_tables, act,
                                 total_len)
        return (cur_tok, pos_new, act, pool), emit

    (cur_tok, pos, active, pool), emits = lax.scan(
        one_round, (cur_tok, pos, active, pool), None, length=steps)
    ring = jnp.moveaxis(emits, 0, 1).reshape(cur_tok.shape[0],
                                             steps * k)
    return cur_tok, pos, active, pool, ring
