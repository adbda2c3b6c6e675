"""Fused Pallas TPU kernel for grouped aggregation over sorted projections.

The hot loop the reference specializes bytecode for
(processing/src/main/java/org/apache/druid/query/groupby/epinephelinae/
GroupByQueryEngineV2.java:413 — per-row hash-table aggregate) becomes ONE
fused TPU kernel over the sorted, key-compacted projection
(druid_tpu/engine/grouping.py Projection):

  * rows arrive clustered by compact group id, so each 1-2k-row block's keys
    span a small window W;
  * the kernel holds the FULL [G] accumulator grid for every aggregator
    resident in VMEM across the whole grid (the BufferArrayGrouper insight,
    scaled to 131k+ groups);
  * each block builds a local window one-hot on the VPU and accumulates into
    the grid with a *dynamic-slice* add at the block's aligned base — the
    block-granular scatter XLA cannot express without a full-grid scatter op;
  * int32 long sums ride a lo/hi limb pair flushed every K blocks, restoring
    exact int64 semantics outside the kernel (the same chunking bound as
    SumKernel.chunk_rows).

The windowed XLA path needs a sorted layout plus an L2 scatter pass; this
kernel fuses the whole reduction. (No rate of either is measured on this
installation — PERF.md.)

Value columns that staged bit-packed (data/packed.py) stream into the
kernel AS WORDS: R // vpw word rows per block, read out of a resident
(WORD_TILE_ROWS, 128) word tile and unpacked to the [R, 128] value tile
with int32 shifts/masks in VMEM — the compressed-domain execution of the
ROADMAP's HBM-wall item. The decoded column never exists in HBM; unpack is
exact, so packed and dense runs are bit-identical.

ONE kernel (grouped_reduce) serves both the staged strategy (pallas_reduce)
and the fused one (megakernel.mega_reduce, which adds the mask-word
operand). Every shape usable() admits compiles through Mosaic on a v5e
(jax 0.9.0 / libtpu 0.0.34: both block sizes, all PACK_WIDTHS, with and
without the mask operand, the slot cap at the group cap). Off-TPU the
projection takes the XLA windowed path (grouping._windowed_reduce); tests
exercise this kernel via the pallas interpreter (force_interpret()).
"""
from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.engine.contracts import (BLK_SMALL_W, BLK_WIDE_W, LANE,
                                        MAX_PALLAS_FIELDS, MAX_PALLAS_GROUPS,
                                        MAX_PALLAS_SLOTS, MAX_W,
                                        MEGA_MASK_VPW, PALLAS_KERNEL_NAMES,
                                        SPAN_BLOCK, VMEM_SCRATCH_BYTES,
                                        WORD_TILE_ROWS)

#: log2(WORD_TILE_ROWS): every in-kernel divide/modulo by a tile, word or
#: block count is a shift/mask on int32 operands (all are powers of two)
_TILE_SHIFT = WORD_TILE_ROWS.bit_length() - 1
assert 1 << _TILE_SHIFT == WORD_TILE_ROWS

_FORCE_INTERPRET = False
_BROKEN: Optional[str] = None


def force_interpret(on: bool = True):
    """Testing hook: run the kernel through the pallas interpreter on CPU."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = on


class KernelBuildError(Exception):
    """A pallas-class program failed to BUILD — trace, Pallas lowering or
    the Mosaic compile (grouping._build_kernel_program raises it with the
    original exception as __cause__). The one failure the strategy latch
    in grouping._dispatch_segment catches; nothing raised while a
    built program RUNS is ever this."""


def mark_broken(exc: BaseException) -> None:
    """Latch the pallas path off for this process after a kernel build
    failure — the caller already fell back to an XLA strategy; retrying a
    known-broken compile on every query would cost seconds each time. The
    reason stays readable (broken_reason) so no caller has to guess
    whether a result came from the kernel or from its fallback."""
    global _BROKEN
    _BROKEN = f"{type(exc).__name__}: {exc}"


def broken_reason() -> Optional[str]:
    """Why the pallas path is latched off in this process, or None while
    it is live. chip_smoke.py and the benchmark fail on a non-None value: a
    result computed after the latch is an XLA result under another name."""
    return _BROKEN


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def backend_ok() -> bool:
    """Pallas availability probe — one of the two platform predicates
    blessed by `donorguard-platform-gate` (the other is
    contracts.donation_supported): backend comparisons anywhere else in
    the tree fail the donate-platform-gate rule, so strategy and
    donation decisions cannot scatter into inline checks. Only the
    platform decides: on a TPU a failed import of the TPU pallas modules
    raises — a broken installation must not read as "no Pallas here"."""
    if _FORCE_INTERPRET or os.environ.get("DRUID_TPU_PALLAS") == "interpret":
        return True
    if os.environ.get("DRUID_TPU_PALLAS") == "0" or _BROKEN is not None:
        return False
    import jax
    if jax.default_backend() != "tpu":
        return False
    from jax.experimental import pallas as pl  # noqa: F401
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401
    return True


def _interpret() -> bool:
    if _FORCE_INTERPRET or os.environ.get("DRUID_TPU_PALLAS") == "interpret":
        return True
    return False


def plan_window(span: int) -> Tuple[int, int]:
    """(block rows, aligned window W) for a projection span, or (0, 0)."""
    for blk in (BLK_SMALL_W, BLK_WIDE_W):
        eff_span = span * max(blk // SPAN_BLOCK, 1)
        w = _round_up(max(eff_span, 1), 128) + 128
        if w <= MAX_W:
            return blk, w
    return 0, 0


def canonical_span(span: int) -> int:
    """The widest span that plans exactly like `span` (same block rows,
    same window). The kernel depends on a span only through plan_window,
    while the span a projection measures is data (389, 392, 393 on three
    12.5M-row headline segments): keying programs on the canonical span
    lets every segment of a query share ONE compiled kernel instead of
    compiling one per segment."""
    blk, w = plan_window(span)
    return (w - LANE) // max(blk // SPAN_BLOCK, 1) if blk else span


#: ops that read one value column (a VMEM input tile each)
_VALUE_OPS = ("sum_i32", "sum_f32", "min_i32", "max_i32", "min_f32",
              "max_f32")


def op_fields(ops: Sequence) -> list:
    """Distinct value columns the kernel streams in, sorted (the in-spec
    layout pallas_reduce builds)."""
    return sorted({op[1] for op in ops if op[0] in _VALUE_OPS})


def op_slots(ops: Sequence) -> int:
    """Output slot count pallas_reduce's out_defs will have: the counts
    grid + a lo/hi limb pair per int32 sum + one grid per other value op."""
    return 1 + sum(2 if op[0] == "sum_i32" else
                   1 if op[0] in _VALUE_OPS else 0
                   for op in ops)


def build_out_defs(ops: Sequence) -> list:
    """Authoritative output-slot layout for a plan's ops: [(name, np
    dtype)], the counts grid leading. Shared by pallas_reduce, the fused
    megakernel (engine/megakernel.py), and its donated-carry allocator, so
    the three cannot drift; op_slots() (which usable() sized the plan with)
    must agree — asserted at every consumer."""
    out_defs = [("count", np.int32)]
    for i, op in enumerate(ops):
        if op[0] == "count":
            pass                       # shares the leading counts grid
        elif op[0] == "sum_i32":
            out_defs.append((f"lo{i}", np.int32))
            out_defs.append((f"hi{i}", np.int32))
        elif op[0] == "sum_f32":
            out_defs.append((f"f{i}", np.float32))
        elif op[0] in ("min_i32", "max_i32"):
            out_defs.append((f"m{i}", np.int32))
        elif op[0] in ("min_f32", "max_f32"):
            out_defs.append((f"m{i}", np.float32))
        elif op[0] in ("zero", "empty"):
            pass
    return out_defs


def usable(kernels: Sequence, col_dtypes: Dict, span: int,
           num_total: int) -> bool:
    if not backend_ok():
        return False
    if num_total > MAX_PALLAS_GROUPS:
        # the full accumulator grid lives in VMEM across the whole grid;
        # beyond the contract cap the vmem-budget guarantee no longer holds
        return False
    blk, _ = plan_window(span)
    if not blk:
        return False
    ops = [k.pallas_op(col_dtypes) for k in kernels]
    if not all(o is not None for o in ops):
        return False
    return len(op_fields(ops)) <= MAX_PALLAS_FIELDS \
        and op_slots(ops) <= MAX_PALLAS_SLOTS


def _word_tile_index(word_rows_per_block: int, i):
    """Index map of a packed field's (WORD_TILE_ROWS, 128) word tile: block
    i's word rows start at array row i · word_rows_per_block, which lies in
    tile (i · word_rows_per_block) // WORD_TILE_ROWS. A named function bound
    with functools.partial, not a lambda: a comprehension's lambdas would
    all close over the LAST field's row count. Typed int32 shift/multiply
    only — under the repo-global x64 flag `i // 8` passes the Python int as
    an i64 operand, and Mosaic's conversion helper recurses without end on
    it (RecursionError at lowering, seen on the chip)."""
    import jax.numpy as jnp
    return ((i * jnp.int32(word_rows_per_block)) >> jnp.int32(_TILE_SHIFT),
            jnp.int32(0))


def _stage_fields(arrays: Dict, ops: Sequence, BLK: int, n: int, n2: int,
                  packed_cols: Optional[Dict] = None):
    """Traced: the kernel's value operands for a plan's ops —
    (vals2, field_ix, n_dense, packed_desc). Dense fields lead as
    [n2 // 128, 128] tiles; fields that staged bit-packed (data/packed.py)
    trail AS WORDS, their rows padded to whole WORD_TILE_ROWS tiles (zero
    words decode to `base` on padding rows; padding rows are masked, so no
    op ever matches them). packed_desc = ((width, vpw, base), ...) per
    packed field, in operand order."""
    import jax.numpy as jnp

    R = BLK // LANE
    uniq_fields = op_fields(ops)
    assert len(uniq_fields) <= MAX_PALLAS_FIELDS, \
        f"{len(uniq_fields)} value columns exceed the pallas field cap"
    pcs = {}
    for f in uniq_fields:
        pc = (packed_cols or {}).get(f)
        # vpw divides R by the PACK_WIDTHS contract; a descriptor that
        # violates it (or a row-count mismatch) falls back to the dense
        # view of that field — correctness never depends on packing. No
        # decode-counter record here: split_resident already counted each
        # packed column once at the program top (XLA dead-code-eliminates
        # that unpack when the kernel consumes the words instead).
        if pc is not None and R % pc.vpw == 0 and pc.rows == n:
            pcs[f] = pc
    dense_fields = [f for f in uniq_fields if f not in pcs]
    packed_fields = [f for f in uniq_fields if f in pcs]
    field_ix = {f: i for i, f in enumerate(dense_fields + packed_fields)}

    def pad_to(a, rows, fill):
        if a.shape[0] == rows:
            return a
        return jnp.concatenate(
            [a, jnp.full((rows - a.shape[0],), fill, a.dtype)])

    vals2 = [pad_to(arrays[f], n2, np.array(0, arrays[f].dtype))
             .reshape(n2 // LANE, LANE) for f in dense_fields]
    packed_desc = []
    for f in packed_fields:
        pc = pcs[f]
        word_rows = _round_up(n2 // pc.vpw // LANE, WORD_TILE_ROWS)
        vals2.append(pad_to(pc.words, word_rows * LANE,
                            np.array(0, pc.words.dtype))
                     .reshape(word_rows, LANE))
        packed_desc.append((pc.width, pc.vpw, pc.base))
    return vals2, field_ix, len(dense_fields), tuple(packed_desc)


def _finish_states(outs: Sequence, kernels: Sequence, ops: Sequence,
                   num_total: int):
    """Traced: (counts, per-kernel states) from the kernel's raw grids —
    the contract of grouping's scatter/blocked paths. int32 limb pairs
    widen to exact int64 sums HERE, outside the kernel."""
    import jax.numpy as jnp

    slot_ix = {name: j for j, (name, _) in enumerate(build_out_defs(ops))}
    flat = [o.reshape(-1)[:num_total] for o in outs]
    counts = flat[slot_ix["count"]]
    states = []
    for oi, (k, op) in enumerate(zip(kernels, ops)):
        if op[0] == "count":
            states.append(counts)
        elif op[0] == "sum_i32":
            lo = flat[slot_ix[f"lo{oi}"]].astype(jnp.int64)
            hi = flat[slot_ix[f"hi{oi}"]].astype(jnp.int64)
            states.append((hi << 16) + lo)
        elif op[0] == "sum_f32":
            states.append(flat[slot_ix[f"f{oi}"]])
        elif op[0] in ("min_i32", "max_i32", "min_f32", "max_f32"):
            states.append(flat[slot_ix[f"m{oi}"]])
        elif op[0] in ("zero", "empty"):
            states.append(jnp.asarray(
                np.broadcast_to(k.empty_state(1), (num_total,)).copy()))
        else:  # pragma: no cover
            raise AssertionError(f"unknown pallas op {op}")
    return counts, tuple(states)


def grouped_reduce(arrays: Dict, key, mwords2, kernels: Sequence,
                   num_total: int, span: int,
                   packed_cols: Optional[Dict] = None):
    """Traced: THE pallas_call — (counts, per-kernel states, raw accumulator
    grids in build_out_defs order). Both the staged strategy
    (pallas_reduce: mask folded into the keys outside the kernel) and the
    fused one (megakernel.mega_reduce: mask arrives as words) dispatch this
    one kernel, so a Mosaic repair lands once.

    key      int32 [n] keys; masked rows carry the int32-max sentinel when
             `mwords2` is None
    mwords2  optional int32 [rows, 128] width-1 mask words covering at
             least the block-padded rows (rows a multiple of
             WORD_TILE_ROWS): the kernel sentinels rows whose bit is 0
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    col_dtypes = {c: a.dtype for c, a in arrays.items()}
    ops = [k.pallas_op(col_dtypes) for k in kernels]
    assert all(o is not None for o in ops), \
        "pallas strategy selected but a kernel has no pallas op"
    BLK, W = plan_window(span)
    assert BLK, f"span {span} too wide for the pallas window"
    n = key.shape[0]
    n2 = _round_up(max(n, BLK), BLK)
    keyx = key
    if n2 != n:
        keyx = jnp.concatenate(
            [key, jnp.full((n2 - n,), jnp.int32(2**31 - 1), jnp.int32)])
    keyx = keyx.reshape(n2 // LANE, LANE)
    vals2, field_ix, n_dense, packed_desc = _stage_fields(
        arrays, ops, BLK, n, n2, packed_cols)

    assert num_total <= MAX_PALLAS_GROUPS, \
        f"num_total {num_total} above the pallas group cap (vmem contract)"
    R = BLK // LANE
    Wr = W // LANE
    G2 = _round_up(num_total, LANE) + W
    nblk = n2 // BLK
    BPW = MEGA_MASK_VPW // R            # blocks per mask word row
    bpw_shift = BPW.bit_length() - 1
    assert 1 << bpw_shift == BPW
    has_mask = mwords2 is not None
    n_mask = int(has_mask)
    packed_rws = [R // vpw for _, vpw, _ in packed_desc]

    # flush period for int32 limb sums: lo grows ≤ BLK·max_abs per block and
    # chunk_rows·max_abs ≤ 2^30 by SumKernel's analysis, so chunk_rows // BLK
    # blocks stay under 2^31 even with the ≤ 2^16 post-flush residue
    K = None
    for op in ops:
        if op[0] == "sum_i32":
            k_op = max(op[2] // BLK, 1)
            K = k_op if K is None else min(K, k_op)

    # the shared builder is authoritative; op_slots() (which usable() sized
    # the plan with) must agree, so a new op kind cannot drift between them
    out_defs = build_out_defs(ops)
    slot_ix = {name: j for j, (name, _) in enumerate(out_defs)}
    assert len(out_defs) == op_slots(ops), \
        f"out_defs {len(out_defs)} != op_slots {op_slots(ops)} — a new " \
        f"pallas op kind updated one layout but not the other"
    assert len(out_defs) <= MAX_PALLAS_SLOTS, \
        f"{len(out_defs)} output slots exceed the pallas slot cap"

    def kernel(key_ref, *refs):
        mw_ref = refs[0] if has_mask else None
        refs = refs[n_mask:]
        vrefs = refs[:len(vals2)]
        orefs = refs[len(vals2):]
        i = pl.program_id(0)

        @pl.when(i == jnp.int32(0))
        def _init():
            for j, (name, dt) in enumerate(out_defs):
                if name.startswith("m"):
                    op = ops[int(name[1:])]
                    if op[0] == "min_i32":
                        ident = jnp.int32(2**31 - 1)
                    elif op[0] == "max_i32":
                        ident = jnp.int32(-(2**31))
                    elif op[0] == "min_f32":
                        ident = jnp.float32(jnp.inf)
                    else:
                        ident = jnp.float32(-jnp.inf)
                    orefs[j][:, :] = jnp.full((G2 // 128, 128), ident)
                else:
                    orefs[j][:, :] = jnp.zeros((G2 // 128, 128), dt)

        # tile row index of every cell of this block's [R, 128] tiles
        rows = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 0)

        kb = key_ref[:, :]                       # [R, 128] int32
        if has_mask:
            # this block's R tile rows live in ONE word row (MEGA_MASK_VPW
            # % R == 0) at bit base (i % BPW)·R: read that row out of the
            # resident word tile and shift along the sublane axis — no
            # reshape, no gather. Masked rows read the key sentinel, built
            # INSIDE the kernel (a closure-captured jnp scalar is rejected
            # as a captured tracer).
            wrow = (i >> jnp.int32(bpw_shift)) \
                & jnp.int32(WORD_TILE_ROWS - 1)
            wt = mw_ref[pl.ds(wrow, 1), :]       # (1, 128) int32
            bit0 = (i & jnp.int32(BPW - 1)) * jnp.int32(R)
            mbit = (wt >> (bit0 + rows)) & jnp.int32(1)
            kb = jnp.where(mbit > jnp.int32(0), kb, jnp.int32(2**31 - 1))
        base = jnp.min(kb)
        # all-scalar int32 math: mixed weak-type promotion recurses forever
        # in the Mosaic conversion helper
        c128 = jnp.int32(128)
        abase = (base // c128) * c128
        abase = jnp.maximum(jnp.minimum(abase, jnp.int32(G2 - W)),
                            jnp.int32(0))
        local = kb - abase                       # valid rows in [0, W)
        r0 = abase // c128
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128, 128), 2)

        # materialize every field's [R, 128] value tile once per block.
        # A packed field's Rw = R // vpw word rows sit inside the resident
        # (WORD_TILE_ROWS, 128) word tile at row (i·Rw) % WORD_TILE_ROWS;
        # value row r reads word row r // vpw at bit slot r % vpw — exactly
        # the tile-planar order pack_padded encoded. The gather over at
        # most WORD_TILE_ROWS word rows is a select chain on static
        # single-row reads (sub-(8, 128) blocks and the (Rw, vpw, 128) →
        # (R, 128) reshape do not lower); arithmetic >> is safe because
        # the mask cuts the sign-extension bits.
        vals_t = [vrefs[j][:, :] for j in range(n_dense)]
        for j, (wd, vpw, vbase) in enumerate(packed_desc):
            wref = vrefs[n_dense + j]
            lg = vpw.bit_length() - 1            # vpw is a power of two
            src = ((i * jnp.int32(R // vpw))
                   & jnp.int32(WORD_TILE_ROWS - 1)) + (rows >> jnp.int32(lg))
            words = jnp.zeros((R, 128), jnp.int32)
            for q in range(WORD_TILE_ROWS):
                words = jnp.where(src == jnp.int32(q), wref[q:q + 1, :],
                                  words)
            pv = (words >> ((rows & jnp.int32(vpw - 1)) * jnp.int32(wd))) \
                & jnp.int32((1 << wd) - 1)
            if vbase:
                pv = pv + jnp.int32(vbase)
            vals_t.append(pv)

        # per window-row matches, shared across every op
        for wr in range(Wr):
            match = ((local - wr * 128)[:, :, None] == lane)  # [R,128,128]
            row = r0 + wr
            # every sum pins its dtype: under x64 an int32 sum would promote
            # to int64, which Mosaic cannot lower on this chip
            cnt = jnp.sum(match.astype(jnp.int32), axis=(0, 1),
                          dtype=jnp.int32)
            cref = orefs[slot_ix["count"]]
            cref[row, :] = cref[row, :] + cnt
            for oi, op in enumerate(ops):
                if op[0] in ("count", "zero", "empty"):
                    continue
                v = vals_t[field_ix[op[1]]]
                if op[0] == "sum_i32":
                    part = jnp.sum(jnp.where(match, v[:, :, None],
                                             jnp.int32(0)),
                                   axis=(0, 1), dtype=jnp.int32)
                    ref = orefs[slot_ix[f"lo{oi}"]]
                    ref[row, :] = ref[row, :] + part
                elif op[0] == "sum_f32":
                    part = jnp.sum(jnp.where(match, v[:, :, None],
                                             jnp.float32(0)), axis=(0, 1),
                                   dtype=jnp.float32)
                    ref = orefs[slot_ix[f"f{oi}"]]
                    ref[row, :] = ref[row, :] + part
                else:
                    kind = op[0]
                    if kind == "min_i32":
                        ident, red = jnp.int32(2**31 - 1), jnp.min
                        comb = jnp.minimum
                    elif kind == "max_i32":
                        ident, red = jnp.int32(-(2**31)), jnp.max
                        comb = jnp.maximum
                    elif kind == "min_f32":
                        ident, red = jnp.float32(jnp.inf), jnp.min
                        comb = jnp.minimum
                    else:
                        ident, red = jnp.float32(-jnp.inf), jnp.max
                        comb = jnp.maximum
                    part = red(jnp.where(match, v[:, :, None], ident),
                               axis=(0, 1))
                    ref = orefs[slot_ix[f"m{oi}"]]
                    ref[row, :] = comb(ref[row, :], part)

        if K is not None:
            @pl.when((i % jnp.int32(K)) == jnp.int32(K - 1))
            def _flush():
                for oi, op in enumerate(ops):
                    if op[0] != "sum_i32":
                        continue
                    lo_ref = orefs[slot_ix[f"lo{oi}"]]
                    hi_ref = orefs[slot_ix[f"hi{oi}"]]
                    lo = lo_ref[:, :]
                    hi_ref[:, :] = hi_ref[:, :] + (lo >> 16)
                    lo_ref[:, :] = lo & 0xFFFF

    out_shapes = [jax.ShapeDtypeStruct((G2 // 128, 128), dt)
                  for _, dt in out_defs]
    # index-map constants must be typed AND built inside the lambda: under
    # the repo-global x64 flag a Python-int 0 promotes to i64 and Mosaic
    # fails to legalize the (i32, i64) func.return of the index map, while a
    # closure-captured jnp scalar is rejected as a captured tracer
    # (tracecheck pallas-accum-dtype guards it). Word tiles — mask and
    # packed values alike — are whole (WORD_TILE_ROWS, 128) blocks whose
    # index maps OVERLAP deliberately: consecutive blocks read the same
    # resident tile at different rows/bit bases (Mosaic refuses blocks
    # whose second-minor dim is neither a multiple of 8 nor the array's).
    grid_spec = pl.GridSpec(
        grid=(nblk,),
        in_specs=([pl.BlockSpec((R, 128), lambda i: (i, jnp.int32(0)),
                                memory_space=pltpu.VMEM)]
                  + [pl.BlockSpec((WORD_TILE_ROWS, 128),
                                  lambda i: (i >> jnp.int32(bpw_shift
                                                            + _TILE_SHIFT),
                                             jnp.int32(0)),
                                  memory_space=pltpu.VMEM)] * n_mask
                  + [pl.BlockSpec((R, 128), lambda i: (i, jnp.int32(0)),
                                  memory_space=pltpu.VMEM)] * n_dense
                  + [pl.BlockSpec((WORD_TILE_ROWS, 128),
                                  functools.partial(_word_tile_index, Rw),
                                  memory_space=pltpu.VMEM)
                     for Rw in packed_rws]),
        out_specs=[pl.BlockSpec((G2 // 128, 128),
                                lambda i: (jnp.int32(0), jnp.int32(0)),
                                memory_space=pltpu.VMEM)] * len(out_defs),
    )
    # the pipeline double-buffers every blocked operand — the resident
    # output grids included — and the [R, 128, 128] one-hot temporaries
    # spill to Mosaic's internal scratch; at the group/slot caps that
    # passes the default scoped-VMEM limit, so the limit is stated
    tile_bytes = 4 * (G2 * len(out_defs) + BLK * (1 + n_dense)
                      + WORD_TILE_ROWS * 128
                      * (n_mask + len(packed_rws)))
    outs = pl.pallas_call(
        kernel, out_shape=out_shapes, grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * tile_bytes + VMEM_SCRATCH_BYTES),
        interpret=_interpret(),
        # a stable kernel name for the profiler's operations (the
        # megakernel variant takes the filter mask as words)
        name=PALLAS_KERNEL_NAMES[1 if has_mask else 0],
    )(keyx, *([mwords2] if has_mask else []), *vals2)
    counts, states = _finish_states(outs, kernels, ops, num_total)
    return counts, states, tuple(outs)


def pallas_reduce(arrays: Dict, mask, key, kernels: Sequence, num_total: int,
                  span: int, packed_cols: Optional[Dict] = None):
    """Traced: (counts int32 [num_total], per-kernel states), the same
    contract as grouping's scatter/blocked paths.

    `arrays` is the dense view; `packed_cols` (data/packed.py
    PackedColumns) supplies bit-packed words for value fields that staged
    compressed — those stream into the kernel AS WORDS and unpack per tile
    in VMEM, so the decoded column never materializes in HBM. Unpack is
    exact, so results stay bit-identical to the dense path."""
    import jax.numpy as jnp

    keyx = jnp.where(mask, key.astype(jnp.int32), jnp.int32(2**31 - 1))
    counts, states, _raw = grouped_reduce(arrays, keyx, None, kernels,
                                          num_total, span, packed_cols)
    return counts, states
