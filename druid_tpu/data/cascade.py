"""Cascaded encodings + code-domain aggregation: never decode what you
don't have to.

PR 9 proved ONE rung of the compression ladder — fixed-width bit-packing
(data/packed.py) with in-kernel decode. Following *GPU Acceleration of SQL
Analytics on Compressed Data* (PAPERS.md), this module adds the cascade on
top of it and, where the query allows, stops decoding entirely:

  * **RLE** (`RleColumn`): low-run-count int32 columns (dimension-sorted
    rollup dims, near-constant metrics) stage as run values + pow2-padded
    inclusive run ends; the traced decode is one searchsorted + gather.
    Run metadata is ~8 bytes/run vs 4 bytes/ROW decoded, so sorted real
    data multiplies the device pool's effective capacity far past the
    bit-packing ratio.
  * **prefix mask** (`PrefixMaskColumn`): a stacked segment's `__valid`
    is its row count; the traced decode is `iota < n_rows`, one compare
    (parallel/distributed.py builds it; 4 bytes a segment for R bools).
  * **delta / FOR** (`DeltaColumn` / `ForColumn`): `__time_offset` in
    rollup segments is near-constant — it stages as base-biased
    range-packed words (FOR) or width-packed non-negative deltas with an
    in-program cumsum (delta, time-ordered segments only). The derived
    `__key`/`__bucket` projection columns ride the same FOR rung
    (grouping._pad_device_cached): their range is the group/bucket space,
    known exactly at plan time.
  * **LZ4** (`Lz4Column`): cold float columns whose raw bytes compress
    ≥ 2x stay LZ4-BLOCK-compressed in HBM; the traced decoder resolves
    match back-references with a pointer-doubling shift window (log2(n)
    gathers) over the token arrays — an exact, device-side LZ4 block
    decode. Host staging comparison fallback: DRUID_TPU_LZ4=host
    decompresses on host before staging (native/druid_native.cpp or the
    pure-python codec, druid_tpu/native/lz4block.py).
  * **code-domain aggregation** (`try_run_domain`): when every referenced
    column (group dims, filter columns, aggregated values) is constant
    within one shared run partition and the query is a granularity-"all"
    dense-key aggregation whose intervals cover the segment, the whole
    grouped aggregate executes over RUN METADATA — count = Σ mask·len,
    sum = Σ value·len, min/max over run values, filters decided once per
    run (LUT gather on run values) — with NO row-width array anywhere:
    nothing decodes, nothing row-sized even stages. Exact by construction
    for count/int-sum/min/max (modular int arithmetic and identical
    identities), so results are bit-identical to the row-domain oracle.

Eligibility everywhere is a PURE function of cached column stats (run
count, value range, max delta, compressed size) with pow2-quantized
padded shapes, so plan signatures stay stable and batching shape buckets
stay shared (the data/packed.py discipline). Every encoding's descriptor
joins the device-pool staging key, the jit-cache structure signature, and
batching._Plan.digest. Opt-out: DRUID_TPU_CASCADE=0 restores the
packed-only world bit-for-bit.

The decode counter (`decode_stats`) increments at TRACE time whenever any
decode (packed/rle/prefix/delta/lz4) enters a program — the "code-domain paths
perform ZERO unpack" acceptance gate is asserted against its deltas.
"""
from __future__ import annotations

import collections
import threading
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.data import packed as packed_mod
from druid_tpu.utils.emitter import Monitor

_LANE = 128

_ENABLED = os.environ.get("DRUID_TPU_CASCADE", "1").lower() \
    not in ("0", "false", "no")
#: "device" = XLA pointer-doubling decode; "host" = host-staging comparison
#: fallback (decompress before device_put); "0" = rung off
_LZ4_MODE = os.environ.get("DRUID_TPU_LZ4", "device").lower()
_STATE_LOCK = threading.Lock()

#: RLE stages only when its run metadata is at least this many times
#: smaller than the best row-width alternative (packed or decoded bytes).
RLE_MIN_WIN = 2
#: run-domain aggregation requires at least this many rows per run on
#: average — below it the row program is already cheap and the run tables
#: would churn the pool for nothing.
RUN_DOMAIN_MIN_ROWS_PER_RUN = 16
#: __time_offset cascades only when genuinely near-constant (rollup
#: segments): widths above this mean real time spread, where the decoded
#: int32 column is cheap relative to everything else staged.
TIME_MAX_WIDTH = 8
#: LZ4 stages only at a real compression win on the RAW column bytes.
LZ4_MIN_RATIO = 2.0


def set_enabled(on: bool) -> bool:
    """Flip the process-wide cascade default; returns the previous value
    (bench/test toggle, the packed.set_enabled discipline)."""
    global _ENABLED
    with _STATE_LOCK:
        prev = _ENABLED
        _ENABLED = bool(on)
        return prev


def enabled() -> bool:
    return _ENABLED


_RUN_DOMAIN = True


def set_run_domain_enabled(on: bool) -> bool:
    """Toggle ONLY the code-domain (run-space) execution path, leaving the
    cascade STAGING rungs on — tests/benches that measure staged bytes or
    the row program pin this off so an eligible shape cannot route around
    what they measure."""
    global _RUN_DOMAIN
    with _STATE_LOCK:
        prev = _RUN_DOMAIN
        _RUN_DOMAIN = bool(on)
        return prev


def run_domain_enabled() -> bool:
    return _RUN_DOMAIN


def set_lz4_mode(mode: str) -> str:
    global _LZ4_MODE
    with _STATE_LOCK:
        prev = _LZ4_MODE
        _LZ4_MODE = mode
        return prev


def lz4_mode() -> str:
    return _LZ4_MODE


def _contracts():
    # lazy: importing the engine package at data-module import time would
    # cycle (the packed.py pattern)
    from druid_tpu.engine import contracts
    return contracts


def pad_pow2(n: int, floor: int = 8) -> int:
    n = max(int(n), 1)
    p = floor
    while p < n:
        p <<= 1
    return p


# ---------------------------------------------------------------------------
# Decode counter (trace-time): the zero-unpack witness
# ---------------------------------------------------------------------------

_DECODES: "collections.Counter" = collections.Counter()
_DECODES_LOCK = threading.Lock()


def record_decode(kind: str, n: int = 1) -> None:
    """Count one decode entering a traced program. Trace-time by design:
    a jit-cache hit re-dispatches a program whose decodes were already
    counted once — zero stays zero exactly when no program containing a
    decode of that column kind was ever built."""
    with _DECODES_LOCK:
        _DECODES[kind] += n


def decode_stats() -> Dict[str, int]:
    with _DECODES_LOCK:
        return dict(_DECODES)


def reset_decode_stats() -> None:
    with _DECODES_LOCK:
        _DECODES.clear()


# ---------------------------------------------------------------------------
# Pytree registration (the packed._ensure_registered discipline)
# ---------------------------------------------------------------------------

_REGISTERED: set = set()
_REGISTER_LOCK = threading.Lock()


def _register(cls, flatten, unflatten) -> None:
    with _REGISTER_LOCK:
        if cls in _REGISTERED:
            return
        import jax

        jax.tree_util.register_pytree_node(cls, flatten, unflatten)
        _REGISTERED.add(cls)


# ---------------------------------------------------------------------------
# RleColumn
# ---------------------------------------------------------------------------

class RleColumn:
    """Run-length-encoded int column: run values + EXCLUSIVE run ends —
    ends[j] is the index one past run j's last row (start of the next
    run; the final entry equals n_rows) — both int32, pow2-padded; pad
    entries repeat the final end so the side="right" searchsorted decode
    stays monotone. rows beyond n_rows decode to the staging pad fill
    (0), exactly like decoded staging.

    `n_rows` rides as a DEVICE SCALAR leaf, not treedef aux: a
    per-segment raw row count in the aux would give every segment its
    own treedef and silently retrace the shared jitted program (the
    DeltaColumn.first rule)."""

    cascade_kind = "rle"
    __slots__ = ("values", "ends", "n_rows", "padded_rows", "dtype_str")

    def __init__(self, values, ends, n_rows, padded_rows: int,
                 dtype_str: str = "int32"):
        _register(RleColumn,
                  lambda c: ((c.values, c.ends, c.n_rows),
                             (c.padded_rows, c.dtype_str)),
                  lambda aux, leaves: RleColumn(leaves[0], leaves[1],
                                                leaves[2], *aux))
        self.values = values
        self.ends = ends
        self.n_rows = n_rows
        self.padded_rows = int(padded_rows)
        self.dtype_str = dtype_str

    @property
    def nbytes(self) -> int:
        return int(getattr(self.values, "nbytes", 0)
                   + getattr(self.ends, "nbytes", 0)
                   + getattr(self.n_rows, "nbytes", 0))

    @property
    def logical_nbytes(self) -> int:
        return int(self.padded_rows * np.dtype(self.dtype_str).itemsize)

    def __repr__(self):
        return (f"RleColumn(runs={self.values.shape[0]}, "
                f"rows={self.padded_rows}, {self.dtype_str})")


def rle_encode(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(run values, EXCLUSIVE run ends — start-of-next-run indices, last
    entry = row count) of a RAW (unpadded) 1-D column."""
    v = np.asarray(values)
    if v.shape[0] == 0:
        return (np.zeros(0, dtype=np.int32), np.zeros(0, dtype=np.int32))
    b = np.empty(v.shape[0], dtype=bool)
    b[0] = True
    np.not_equal(v[1:], v[:-1], out=b[1:])
    starts = np.flatnonzero(b)
    ends = np.concatenate(
        [starts[1:], [v.shape[0]]]).astype(np.int32)
    return v[starts].astype(np.int32), ends


def rle_decode_device(rc: RleColumn):
    """Traced: expand runs to the padded decoded column. Exact: real rows
    gather their run's value, pad rows read the staging fill (0)."""
    import jax.numpy as jnp

    record_decode("rle")
    iota = jnp.arange(rc.padded_rows, dtype=jnp.int32)
    idx = jnp.searchsorted(rc.ends, iota, side="right")
    idx = jnp.clip(idx, 0, rc.ends.shape[0] - 1)
    v = jnp.where(iota < rc.n_rows, rc.values[idx], 0)
    dt = jnp.dtype(rc.dtype_str)
    return v.astype(dt) if v.dtype != dt else v


# ---------------------------------------------------------------------------
# PrefixMaskColumn
# ---------------------------------------------------------------------------

class PrefixMaskColumn:
    """A bool column that is True on rows [0, n_rows) and False on the
    padding after them — a stacked segment's `__valid` — carried as what
    it is: the row count. The traced decode is ONE compare against an
    iota, which XLA fuses into every consumer; no run table, no search.

    `n_rows` rides as a DEVICE SCALAR leaf (stacked: a `[K]` leaf), never
    treedef aux — the RleColumn.n_rows rule. A padding segment's count is
    0: every row invalid."""

    cascade_kind = "prefix"
    __slots__ = ("n_rows", "padded_rows")

    def __init__(self, n_rows, padded_rows: int):
        _register(PrefixMaskColumn,
                  lambda c: ((c.n_rows,), (c.padded_rows,)),
                  lambda aux, leaves: PrefixMaskColumn(leaves[0], *aux))
        self.n_rows = n_rows
        self.padded_rows = int(padded_rows)

    @property
    def nbytes(self) -> int:
        return int(getattr(self.n_rows, "nbytes", 0))

    @property
    def logical_nbytes(self) -> int:
        return int(self.padded_rows * np.dtype(bool).itemsize)

    def __repr__(self):
        return f"PrefixMaskColumn(rows={self.padded_rows})"


def prefix_mask_decode_device(pm: PrefixMaskColumn):
    """Traced: the dense bool mask, `iota < n_rows`."""
    import jax.numpy as jnp

    record_decode("prefix")
    return jnp.arange(pm.padded_rows, dtype=jnp.int32) < pm.n_rows


# ---------------------------------------------------------------------------
# ForColumn (base-biased range-packing — PackedColumn with cascade identity)
# ---------------------------------------------------------------------------

class ForColumn(packed_mod.PackedColumn):
    """Frame-of-reference rung: exactly PackedColumn mechanics (width/base
    words, tile-planar layout, in-kernel unpack eligibility) but planned by
    the cascade ladder for columns packed.plan_column never claims —
    `__time_offset` and the derived `__key`/`__bucket` columns — and
    counted by the pool's cascade accounting."""

    cascade_kind = "for"

    def __init__(self, words, width: int, base: int, rows: int,
                 dtype_str: str = "int32"):
        _register(ForColumn,
                  lambda pc: ((pc.words,),
                              (pc.width, pc.base, pc.rows, pc.dtype_str)),
                  lambda aux, leaves: ForColumn(leaves[0], *aux))
        super().__init__(words, width, base, rows, dtype_str)


# ---------------------------------------------------------------------------
# DeltaColumn
# ---------------------------------------------------------------------------

class DeltaColumn:
    """Width-packed non-negative consecutive deltas + the first value as a
    device scalar leaf (per-segment bases must not ride the treedef, or
    every segment would compile its own program). Decode = first +
    cumsum(unpacked deltas). Monotone non-decreasing columns only
    (time-ordered `__time_offset`); pad rows repeat the last value, which
    every consumer masks."""

    cascade_kind = "delta"
    __slots__ = ("words", "first", "width", "rows", "dtype_str")

    def __init__(self, words, first, width: int, rows: int,
                 dtype_str: str = "int32"):
        _register(DeltaColumn,
                  lambda c: ((c.words, c.first),
                             (c.width, c.rows, c.dtype_str)),
                  lambda aux, leaves: DeltaColumn(leaves[0], leaves[1],
                                                  *aux))
        self.words = words
        self.first = first
        self.width = int(width)
        self.rows = int(rows)
        self.dtype_str = dtype_str

    @property
    def vpw(self) -> int:
        return _contracts().PACK_WORD_BITS // self.width

    @property
    def nbytes(self) -> int:
        return int(getattr(self.words, "nbytes", 0)
                   + getattr(self.first, "nbytes", 0))

    @property
    def logical_nbytes(self) -> int:
        return int(self.rows * np.dtype(self.dtype_str).itemsize)

    def __repr__(self):
        return f"DeltaColumn(w{self.width}, rows={self.rows})"


def delta_encode(padded: np.ndarray, n_rows: int,
                 width: int) -> Tuple[np.ndarray, np.ndarray]:
    """(packed delta words, first value) for a PADDED monotone column.
    delta[0] = 0 and pad-region deltas are forced to 0, so the decode's
    pad rows repeat the last real value deterministically."""
    v = np.asarray(padded).astype(np.int64)
    d = np.zeros_like(v)
    if v.shape[0] > 1:
        d[1:] = v[1:] - v[:-1]
    if n_rows < v.shape[0]:
        d[n_rows:] = 0
    assert d.min() >= 0 and d.max() < (1 << width), \
        "delta_encode planned on stale stats (delta out of width range)"
    return (packed_mod.pack_padded(d.astype(np.int32), width, 0),
            np.asarray(int(v[0]) if v.shape[0] else 0, dtype=np.int32))


def delta_decode_device(dc: DeltaColumn):
    """Traced: exact inverse of delta_encode (int32 cumsum; prefixes are
    value − first, which fits int32 whenever the values do)."""
    import jax.numpy as jnp

    record_decode("delta")
    width, vpw = dc.width, dc.vpw
    m = jnp.int32((1 << width) - 1)
    w2 = dc.words.reshape(-1, _LANE)
    sh = jnp.int32(width) * jnp.arange(vpw, dtype=jnp.int32)
    d = ((w2[:, None, :] >> sh[None, :, None]) & m).reshape(dc.rows)
    v = dc.first + jnp.cumsum(d, dtype=jnp.int32)
    dt = jnp.dtype(dc.dtype_str)
    return v.astype(dt) if v.dtype != dt else v


# ---------------------------------------------------------------------------
# Lz4Column
# ---------------------------------------------------------------------------

class Lz4Column:
    """An LZ4-block-compressed float column resident in HBM: the literal
    byte stream plus per-sequence token arrays (all pow2-padded). The
    traced decoder reconstructs the raw bytes exactly — literals by
    position arithmetic, matches by a pointer-doubling shift window —
    then bitcasts to the column dtype and zero-pads to the staged row
    count (bit-identical to decoded staging, padding included)."""

    cascade_kind = "lz4"
    __slots__ = ("literals", "lit_lens", "match_lens", "offsets",
                 "n_values", "padded_rows", "dtype_str")

    def __init__(self, literals, lit_lens, match_lens, offsets,
                 n_values: int, padded_rows: int, dtype_str: str):
        _register(Lz4Column,
                  lambda c: ((c.literals, c.lit_lens, c.match_lens,
                              c.offsets),
                             (c.n_values, c.padded_rows, c.dtype_str)),
                  lambda aux, leaves: Lz4Column(*leaves, *aux))
        self.literals = literals
        self.lit_lens = lit_lens
        self.match_lens = match_lens
        self.offsets = offsets
        self.n_values = int(n_values)
        self.padded_rows = int(padded_rows)
        self.dtype_str = dtype_str

    @property
    def out_bytes(self) -> int:
        return self.n_values * np.dtype(self.dtype_str).itemsize

    @property
    def nbytes(self) -> int:
        return int(sum(getattr(a, "nbytes", 0)
                       for a in (self.literals, self.lit_lens,
                                 self.match_lens, self.offsets)))

    @property
    def logical_nbytes(self) -> int:
        return int(self.padded_rows * np.dtype(self.dtype_str).itemsize)

    def __repr__(self):
        return (f"Lz4Column({self.dtype_str}[{self.n_values}], "
                f"{self.nbytes}B compressed)")


def lz4_decode_device(col: Lz4Column):
    """Traced LZ4 block decode. Match back-references resolve by pointer
    doubling: ptr[i] = i for literal bytes, i − offset for match bytes;
    log2(out_bytes) rounds of ptr = ptr[ptr] reach the literal fixpoint
    every chain ends at (overlapping matches included — the chain is the
    sequential copy's data dependency, followed transitively)."""
    import jax
    import jax.numpy as jnp

    record_decode("lz4")
    nb = col.out_bytes
    T = int(col.lit_lens.shape[0])
    ll = col.lit_lens
    tok_total = ll + col.match_lens
    csum = jnp.cumsum(tok_total, dtype=jnp.int32)
    out_start = csum - tok_total
    tok_end = csum
    lit_start = jnp.cumsum(ll, dtype=jnp.int32) - ll
    i = jnp.arange(nb, dtype=jnp.int32)
    t = jnp.clip(jnp.searchsorted(tok_end, i, side="right"), 0, T - 1)
    rel = i - out_start[t]
    is_lit = rel < ll[t]
    litpos = jnp.where(is_lit, lit_start[t] + rel, 0)
    ptr = jnp.where(is_lit, i, i - col.offsets[t])
    ptr = jnp.clip(ptr, 0, nb - 1)
    for _ in range(max(int(nb - 1).bit_length(), 1)):
        ptr = ptr[ptr]
    raw = col.literals[jnp.clip(litpos[ptr], 0,
                                col.literals.shape[0] - 1)]
    itemsize = np.dtype(col.dtype_str).itemsize
    b = raw.astype(jnp.uint32).reshape(-1, itemsize)
    if itemsize == 4:
        word = b[:, 0]
        for s in range(1, 4):
            word = word | (b[:, s] << jnp.uint32(8 * s))
        v = jax.lax.bitcast_convert_type(word, jnp.dtype(col.dtype_str))
    else:
        # float64 needs real uint64 lanes — x64 is globally on
        # (engine/__init__), asserted so a silent 32-bit truncation can
        # never corrupt the reconstruction
        assert jax.config.jax_enable_x64, "lz4 float64 decode needs x64"
        u64 = b[:, 0].astype(jnp.uint64)
        for s in range(1, 8):
            u64 = u64 | (b[:, s].astype(jnp.uint64) << jnp.uint64(8 * s))
        v = jax.lax.bitcast_convert_type(u64, jnp.dtype(col.dtype_str))
    pad = col.padded_rows - col.n_values
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    return v


# ---------------------------------------------------------------------------
# Cached column stats + encodings (host, per segment)
# ---------------------------------------------------------------------------

def column_run_count(segment, name: str) -> int:
    """Cached run count of a column's RAW values (dims: dictionary ids)."""
    def _compute():
        col = segment.dims.get(name)
        v = col.ids if col is not None else segment.metrics[name].values
        if v.shape[0] == 0:
            return 0
        return 1 + int(np.count_nonzero(v[1:] != v[:-1]))
    return segment.aux_cached(("cascade_runs", name), _compute)


def _rle_encoded(segment, name: str) -> Tuple[np.ndarray, np.ndarray]:
    """Cached (values, ends) of a column's raw run encoding."""
    def _compute():
        col = segment.dims.get(name)
        v = col.ids if col is not None else segment.metrics[name].values
        return rle_encode(v)
    return segment.aux_cached(("cascade_rleenc", name), _compute)


def column_run_info(segment, name: str, max_runs: Optional[int] = None
                    ) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
    """(run values, EXCLUSIVE run ends, n_runs) when `name` is run-compressible
    (run count within `max_runs`, default n_rows // 8 capped at
    CASCADE_MAX_RUNS), else None. The RLE-run-aware filter path and the
    run-domain planner both ask this."""
    if name in segment.dims:
        pass
    elif name not in segment.metrics:
        return None
    nr = column_run_count(segment, name)
    if nr == 0:
        return None
    cap = _contracts().CASCADE_MAX_RUNS
    limit = min(max(segment.n_rows // 8, 1), cap) if max_runs is None \
        else min(max_runs, cap)
    if nr > limit:
        return None
    values, ends = _rle_encoded(segment, name)
    return values, ends, nr


def _time_stats(segment) -> Tuple[int, int, int]:
    """(min offset, max offset, max consecutive delta or -1 when not
    monotone/unknown) — all O(1)-amortized cached stats."""
    t0 = segment.interval.start
    lo = segment.min_time - t0
    hi = segment.max_time - t0

    def _compute():
        if not segment.time_ordered or segment.n_rows < 2:
            return 0 if segment.time_ordered else -1
        return int(np.max(np.diff(segment.time_ms)))
    md = segment.aux_cached(("cascade_tdelta",), _compute)
    return int(lo), int(hi), md


def _lz4_stat(segment, name: str) -> Tuple[int, int, int]:
    """Cached (raw bytes, compressed bytes, padded token count) of a float
    column; compressed = 0 marks a failed/unprofitable codec round-trip
    (the rung silently disables for that column)."""
    def _compute():
        from druid_tpu.native import lz4block
        raw = np.ascontiguousarray(segment.metrics[name].values).tobytes()
        try:
            comp = lz4block.compress(raw)
            if lz4block.decompress(comp, len(raw)) != raw:
                return (len(raw), 0, 0)
            lits, ll, ml, off = lz4block.tokenize(comp)
        except (ValueError, IndexError):
            return (len(raw), 0, 0)
        return (len(raw), len(comp), pad_pow2(ll.shape[0]))
    return segment.aux_cached(("cascade_lz4stat", name), _compute)


def _lz4_encoded(segment, name: str):
    """Cached pow2-padded token arrays (literals, lit_lens, match_lens,
    offsets, n_values) for a planned lz4 column."""
    def _compute():
        from druid_tpu.native import lz4block
        vals = np.ascontiguousarray(segment.metrics[name].values)
        comp = lz4block.compress(vals.tobytes())
        lits, ll, ml, off = lz4block.tokenize(comp)
        tp = pad_pow2(ll.shape[0])
        lp = pad_pow2(max(lits.shape[0], 1))

        def padto(a, n, dt):
            out = np.zeros(n, dtype=dt)
            out[: a.shape[0]] = a
            return out
        return (padto(lits, lp, np.uint8), padto(ll, tp, np.int32),
                padto(ml, tp, np.int32), padto(off, tp, np.int32),
                int(vals.shape[0]))
    return segment.aux_cached(("cascade_lz4enc", name), _compute)


# ---------------------------------------------------------------------------
# Planning (pure functions of cached stats; pow2-quantized shapes)
# ---------------------------------------------------------------------------

def _plan_time(segment) -> Optional[Tuple]:
    if segment.n_rows == 0:
        return None
    lo, hi, md = _time_stats(segment)
    base = (1 << (lo.bit_length() - 1)) if lo > 0 else 0
    wf = packed_mod.width_for(hi, base)
    wd = packed_mod.width_for(md, 0) if md >= 0 else 0
    if wf > TIME_MAX_WIDTH:
        wf = 0
    if wd > TIME_MAX_WIDTH:
        wd = 0
    if wd and (not wf or wd < wf):
        return ("delta", wd)
    if wf:
        return ("for", wf, base)
    return None


def _plan_rle(segment, name: str) -> Optional[Tuple]:
    nr = column_run_count(segment, name)
    if nr == 0:
        return None
    padded_runs = pad_pow2(nr)
    if padded_runs > _contracts().CASCADE_MAX_RUNS:
        return None
    rle_bytes = padded_runs * 8           # two int32 arrays
    p = packed_mod.plan_column(segment, name)
    alt_bytes = segment.n_rows * p[0] // 8 if p is not None \
        else segment.n_rows * 4
    if rle_bytes * RLE_MIN_WIN > alt_bytes:
        return None
    return ("rle", padded_runs)


def _plan_lz4(segment, name: str) -> Optional[Tuple]:
    if lz4_mode() not in ("device", "host"):
        return None
    raw, comp, tpad = _lz4_stat(segment, name)
    if not comp or comp * LZ4_MIN_RATIO > raw:
        return None
    if tpad > _contracts().CASCADE_MAX_RUNS:
        return None
    if lz4_mode() == "host":
        return ("lz4host",)
    lits, ll, ml, off, nv = _lz4_encoded(segment, name)
    # n_values joins the descriptor: it is STATIC decode shape (the
    # byte-domain iota/pointer arrays), so two stagings share a program
    # only when it matches — the recompile is visible in the signature
    # instead of a silent treedef retrace
    return ("lz4", int(lits.shape[0]), int(ll.shape[0]), int(nv))


def plan_column(segment, name: str) -> Optional[Tuple]:
    """Cascade descriptor entry tail for one column, or None. Pure in the
    packed.plan_column sense: identical cached stats give identical plans
    on every execution path."""
    if name == "__time_offset":
        return _plan_time(segment)
    if name in segment.dims:
        return _plan_rle(segment, name)
    m = segment.metrics.get(name)
    if m is None:
        return None
    # plan from COLUMN METADATA, not np.asarray(m.values): lazy format-V2
    # columns must be plannable without materializing decoded rows (the
    # zero-host-decode load path)
    from druid_tpu.data.segment import ValueType
    t = getattr(m, "type", None)
    if t is ValueType.LONG:
        if segment.staged_dtype(name) != np.int32:
            return None
        return _plan_rle(segment, name)
    if t in (ValueType.FLOAT, ValueType.DOUBLE):
        return _plan_lz4(segment, name)
    return None                           # complex states: stage as-is


def plan_columns(segment, columns: Sequence[str],
                 permuted: bool = False) -> Tuple:
    """((name, kind, *params), ...) for the cascade-eligible subset of
    `columns` plus `__time_offset` (always staged), sorted by name; ()
    when cascading is disabled or the staging layout is permuted (a row
    permutation destroys run structure). This tuple IS the cascade
    descriptor: it joins the device-pool staging key, the jit-cache
    structure signature, and batching._Plan.digest alongside the pack
    descriptor."""
    if not _ENABLED or permuted:
        return ()
    out = []
    for c in sorted(set(columns) | {"__time_offset"}):
        p = plan_column(segment, c)
        if p is not None:
            out.append((c,) + p)
    return tuple(out)


def plan_pair(segment, columns: Sequence[str],
              permuted: bool = False) -> Tuple[Tuple, Tuple]:
    """(cascade descriptor, pack descriptor) with cascade claims excluded
    from packing — THE one derivation every path (device_block staging,
    per-segment planning, batching digests) shares, so a column is staged
    under exactly one encoding everywhere."""
    cascades = plan_columns(segment, columns, permuted)
    claimed = {e[0] for e in cascades}
    packs = packed_mod.plan_columns(
        segment, [c for c in columns if c not in claimed])
    return cascades, packs


def descriptor_to_json(entries: Tuple) -> list:
    """JSON form of a cascade/pack descriptor tuple (format V2 persists the
    staging plan alongside the parts, so `segment inspect` and the loader
    can show/validate exactly what was encoded)."""
    return [list(e) for e in entries]


def descriptor_from_json(obj) -> Tuple:
    """Exact inverse of descriptor_to_json (tuples restored, so the result
    is hashable and == the original plan_pair output)."""
    return tuple(tuple(e) for e in obj)


# ---------------------------------------------------------------------------
# Staging-time encoding (data/segment._stage_block)
# ---------------------------------------------------------------------------

def encode_column(segment, name: str, entry: Tuple, padded: np.ndarray,
                  put):
    """Encode one planned column for staging. `padded` is the padded host
    array decoded staging would ship; `put` is the caller's device_put."""
    kind = entry[1]
    if kind == "rle":
        values, ends = _rle_encoded(segment, name)
        rpad = entry[2]

        def padto(a, fill):
            out = np.full(rpad, fill, dtype=np.int32)
            out[: a.shape[0]] = a
            return out
        n_rows = int(ends[-1]) if ends.shape[0] else 0
        return RleColumn(put(padto(values, 0)),
                         put(padto(ends, n_rows)),
                         put(np.asarray(n_rows, dtype=np.int32)),
                         int(padded.shape[0]), str(padded.dtype))
    if kind == "for":
        w, base = entry[2], entry[3]
        words = packed_mod.pack_padded(padded, w, base)
        return ForColumn(put(words), w, base, int(padded.shape[0]),
                         str(padded.dtype))
    if kind == "delta":
        w = entry[2]
        words, first = delta_encode(padded, segment.n_rows, w)
        return DeltaColumn(put(words), put(first), w,
                           int(padded.shape[0]), str(padded.dtype))
    if kind == "lz4":
        lits, ll, ml, off, nv = _lz4_encoded(segment, name)
        return Lz4Column(put(lits), put(ll), put(ml), put(off), nv,
                         int(padded.shape[0]), str(padded.dtype))
    if kind == "lz4host":
        # host-staging comparison fallback: round-trip through the codec
        # on host, then stage decoded — the bus/HBM baseline the device
        # decode is measured against
        from druid_tpu.native import lz4block
        vals = np.ascontiguousarray(segment.metrics[name].values)
        raw = lz4block.decompress(lz4block.compress(vals.tobytes()),
                                  vals.nbytes)
        dec = np.frombuffer(raw, dtype=vals.dtype)
        out = np.zeros(padded.shape[0], dtype=vals.dtype)
        out[: dec.shape[0]] = dec
        return put(out)
    raise AssertionError(f"unknown cascade kind {kind!r}")


def for_encode_derived(lo: int, hi: int) -> Optional[Tuple]:
    """(width, base) when a derived int32 column with values in [lo, hi]
    (the `__key`/`__bucket` projection columns — range known exactly at
    plan time) range-packs, else None."""
    if not _ENABLED:
        return None
    base = int(lo)
    w = packed_mod.width_for(int(hi), base)
    return (w, base) if w else None


# ---------------------------------------------------------------------------
# Program-top decode (the one split every execution path calls)
# ---------------------------------------------------------------------------

def split_resident(arrays: Dict) -> Tuple[Dict, Dict]:
    """Superset of packed.split_packed: (packed columns for the pallas
    word path — ForColumn included, its layout IS the packed layout —,
    dense view with every cascade/packed entry decoded). The ONE decode
    entry point, so the decode story cannot diverge across paths."""
    packed_cols: Dict = {}
    out = dict(arrays)
    changed = False
    for k, v in arrays.items():
        if isinstance(v, RleColumn):
            out[k] = rle_decode_device(v)
            changed = True
        elif isinstance(v, PrefixMaskColumn):
            out[k] = prefix_mask_decode_device(v)
            changed = True
        elif isinstance(v, DeltaColumn):
            out[k] = delta_decode_device(v)
            changed = True
        elif isinstance(v, Lz4Column):
            out[k] = lz4_decode_device(v)
            changed = True
        elif isinstance(v, packed_mod.PackedColumn):
            packed_cols[k] = v
            out[k] = packed_mod.unpack_device(v)
            changed = True
    return packed_cols, (out if changed else arrays)


# ---------------------------------------------------------------------------
# Code-domain aggregation stats (query/codeDomain/* metrics)
# ---------------------------------------------------------------------------

class CodeDomainStats:
    """hits = segment executions served fully in run space (no row-width
    array staged or decoded); rows = logical rows those executions
    covered."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.rows = 0

    def record(self, rows: int) -> None:
        with self._lock:
            self.hits += 1
            self.rows += int(rows)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "rows": self.rows}


_CODE_STATS = CodeDomainStats()


def code_domain_stats() -> CodeDomainStats:
    return _CODE_STATS


class CodeDomainMonitor(Monitor):
    """Emits query/codeDomain/{hits,rows} per tick (deltas over the tick
    window, the FilterBitmapMonitor discipline)."""

    def __init__(self, source: Optional[CodeDomainStats] = None):
        self.source = source or _CODE_STATS
        self._last = self.source.snapshot()

    def do_monitor(self, emitter):
        s = self.source.snapshot()
        last, self._last = self._last, s
        emitter.metric("query/codeDomain/hits", s["hits"] - last["hits"])
        emitter.metric("query/codeDomain/rows", s["rows"] - last["rows"])


# ---------------------------------------------------------------------------
# Run-domain (code-domain) aggregation
# ---------------------------------------------------------------------------

@dataclass
class _RunKernel:
    """Run-space execution plan for one kernel: the kernel itself, the
    run columns it reads (empty for count/const-sum/missing-column
    kernels — the latter aggregate to zeros/identity without any run
    table), plus a re-planned (column-domain, whitelisted) filter tree
    for FilteredKernel chains."""
    kernel: object
    cols: frozenset = frozenset()
    fnode: object = None                  # run-space filter node or None
    child: Optional["_RunKernel"] = None

    def sig(self) -> str:
        if self.child is not None:
            f = self.fnode.signature() if self.fnode is not None else "none"
            return f"rfiltered({f},{self.child.sig()})"
        return self.kernel.signature()

    def aux(self) -> List[np.ndarray]:
        out: List[np.ndarray] = []
        if self.child is not None:
            if self.fnode is not None:
                out.extend(self.fnode.aux_arrays())
            out.extend(self.child.aux())
            return out
        k = self.kernel
        if getattr(k, "const_value", None) is not None:
            out.append(np.asarray(k.const_value, dtype=np.int64))
        return out

    def columns(self) -> set:
        if self.child is not None:
            cols = set(self.child.columns())
            if self.fnode is not None:
                cols |= self.fnode.required_device_columns()
            return cols
        return set(self.cols)


_RUN_JIT_CACHE: "collections.OrderedDict[str, object]" = \
    collections.OrderedDict()
_RUN_JIT_CACHE_CAP = 64
_RUN_JIT_CACHE_LOCK = threading.Lock()


def _run_filter_ok(node) -> bool:
    """Whitelist: node kinds whose build() reads only per-run-constant
    columns (LUT/numeric compares over run values) — no expressions
    (absolute __time is row-space) and no word-domain nodes (bitmap words
    are row-space by definition)."""
    from druid_tpu.engine.filters import (AndNode, ConstNode, LutNode,
                                          NotNode, NumericCmpNode,
                                          NumericEqNode, NumericInNode,
                                          OrNode)
    if node is None:
        return True
    if isinstance(node, (AndNode, OrNode)):
        return all(_run_filter_ok(c) for c in node.children)
    if isinstance(node, NotNode):
        return _run_filter_ok(node.child)
    return isinstance(node, (ConstNode, LutNode, NumericEqNode,
                             NumericInNode, NumericCmpNode))


def _plan_run_kernel(k, segment) -> Optional[_RunKernel]:
    from druid_tpu.engine.filters import plan_filter, simplify_node
    from druid_tpu.engine.kernels import (CountKernel, FilteredKernel,
                                          MinMaxKernel, SumKernel)
    from druid_tpu.data.segment import ValueType
    if isinstance(k, FilteredKernel):
        child = _plan_run_kernel(k.child, segment)
        if child is None:
            return None
        # re-plan from the SPEC with device_bitmap off: the kernel's own
        # planned tree may carry word-domain nodes
        fnode = simplify_node(plan_filter(k.spec.filter, segment,
                                          device_bitmap=False))
        if not _run_filter_ok(fnode):
            return None
        return _RunKernel(kernel=k, fnode=fnode, child=child)
    if isinstance(k, CountKernel):
        return _RunKernel(kernel=k)
    if isinstance(k, SumKernel):
        if k.vtype is not ValueType.LONG:
            return None                   # float sums reorder: row path
        if k.const_value is not None:
            return _RunKernel(kernel=k)
        f = k.spec.field
        if f in segment.dims:
            return None
        m = segment.metrics.get(f)
        if m is None:
            return _RunKernel(kernel=k)   # missing column sums to zeros
        if getattr(m, "type", None) is not ValueType.LONG:
            return None                   # metadata check: lazy V2 columns
        return _RunKernel(kernel=k, cols=frozenset({f}))
    if isinstance(k, MinMaxKernel):
        f = k.spec.field
        if f in segment.dims:
            return None
        if f not in segment.metrics:
            return _RunKernel(kernel=k)   # missing column: identity state
        return _RunKernel(kernel=k, cols=frozenset({f}))
    return None


def _run_update(rk: _RunKernel, arrays: Dict, mask, key, lens,
                num: int, it):
    """Traced per-kernel run-space update; state shapes/dtypes are exactly
    the row path's update() shapes, so host_post/combine/merge compose
    unchanged (the bit-parity contract)."""
    import jax
    import jax.numpy as jnp
    from druid_tpu.engine.kernels import (CountKernel, MinMaxKernel,
                                          SumKernel)

    if rk.child is not None:
        fmask = mask
        if rk.fnode is not None:
            fmask = mask & rk.fnode.build(arrays, it)
        return _run_update(rk.child, arrays, fmask, key, lens, num, it)
    k = rk.kernel
    if isinstance(k, CountKernel):
        # counts fit int32 (≤ n_rows < 2^31): same dtype as the row path
        return jax.ops.segment_sum(
            jnp.where(mask, lens, 0), key, num_segments=num)
    if isinstance(k, SumKernel):
        if k.const_value is not None:
            c = next(it)
            cnt = jax.ops.segment_sum(
                jnp.where(mask, lens, 0), key, num_segments=num)
            return cnt.astype(jnp.int64) * c
        f = k.spec.field
        if f not in arrays:
            return jnp.zeros((num,), dtype=jnp.int64)
        # Σ v·len ≡ per-row Σ v (mod 2^64): identical to the row path even
        # at wraparound; x64 is globally on (engine/__init__)
        v = arrays[f].astype(jnp.int64) * lens.astype(jnp.int64)
        return jax.ops.segment_sum(jnp.where(mask, v, 0), key,
                                   num_segments=num)
    assert isinstance(k, MinMaxKernel)
    f = k.spec.field
    if f not in arrays:
        return jnp.asarray(np.broadcast_to(k.empty_state(1), (num,)))
    v = arrays[f]
    if jnp.issubdtype(v.dtype, jnp.integer):
        info = jnp.iinfo(v.dtype)
        ident = jnp.asarray(info.min if k.is_max else info.max,
                            dtype=v.dtype)
    else:
        ident = jnp.asarray(-jnp.inf if k.is_max else jnp.inf,
                            dtype=v.dtype)
    v = jnp.where(mask, v, ident)
    return (jax.ops.segment_max if k.is_max else jax.ops.segment_min)(
        v, key, num_segments=num)


def _build_run_fn(dim_cols: Tuple, has_remap: Tuple, filter_node,
                  rkernels: List[_RunKernel], num_total: int,
                  has_bucket: bool = False):
    import jax
    import jax.numpy as jnp

    def fn(arrays: Dict, aux: Tuple):
        it = iter(aux)
        lens = arrays["__runlen"]
        mask = lens > 0                   # zero-length pad runs drop out
        arrays = dict(arrays)
        arrays["__valid"] = mask          # ConstNode's shape anchor
        if has_bucket:
            # uniform granularity: the bucket id is run-constant by
            # partition construction — it rides as a staged per-run table
            # (pad runs carry -1) and seeds the fused key exactly like the
            # row program's device bucket math
            key = arrays["__runbucket"]
            mask = mask & (key >= 0)
            key = jnp.maximum(key, 0)
        else:
            key = jnp.zeros(lens.shape, dtype=jnp.int32)
        for col, remap in zip(dim_cols, has_remap):
            if col is None:
                continue
            ids = arrays[col]
            if remap:
                r = next(it)
                ids = r[ids]
                mask = mask & (ids >= 0)
            card = next(it)
            key = key * card + jnp.maximum(ids, 0)
        if filter_node is not None:
            mask = mask & filter_node.build(arrays, it)
        key = jnp.clip(key, 0, num_total - 1).astype(jnp.int32)
        counts = jax.ops.segment_sum(jnp.where(mask, lens, 0), key,
                                     num_segments=num_total)
        states = tuple(_run_update(rk, arrays, mask, key, lens,
                                   num_total, it) for rk in rkernels)
        return counts, states

    from druid_tpu.engine.contracts import named_program
    return jax.jit(named_program(fn, "run_domain_agg"))


def run_domain_probe(segment, intervals, granularity, spec, kernels,
                     flt, virtual_columns) -> bool:
    """Cheap eligibility-only check (batching._plan_for routes eligible
    segments to the per-segment path so its enqueue can take the
    code-domain shortcut)."""
    return _plan_run_domain(segment, intervals, granularity, spec,
                            kernels, flt, virtual_columns) is not None


def _plan_run_domain(segment, intervals, granularity, spec, kernels,
                     flt, virtual_columns):
    """None, or (dim structure, run filter node, run kernels, run columns,
    partition key) when the whole grouped aggregate can run over run
    metadata. Memoized on the (single-use — grouping.GroupPlan contract)
    spec: batching's eligibility probe and enqueue_grouped_aggregate's
    execution hook share one planning pass instead of re-planning the
    filter and kernels on the hot path."""
    cached = getattr(spec, "_cascade_run_plan", None)
    if cached is not None:
        return cached[0]
    plan = _plan_run_domain_uncached(segment, intervals, granularity,
                                     spec, kernels, flt, virtual_columns)
    spec._cascade_run_plan = (plan,)
    return plan


def _plan_run_domain_uncached(segment, intervals, granularity, spec,
                              kernels, flt, virtual_columns):
    if not _ENABLED or not _RUN_DOMAIN or segment.n_rows == 0 \
            or virtual_columns:
        return None
    if spec.bucket_mode not in ("all", "uniform") \
            or spec.key_mode != "dense":
        return None
    if not any(iv.start <= segment.min_time and iv.end > segment.max_time
               for iv in intervals):
        return None                       # the time mask must be all-true
    if any(d.host_ids is not None for d in spec.dims):
        return None
    # uniform granularities ride run space too, when their bucket
    # boundaries provably align with run boundaries: the per-row bucket id
    # JOINS the joint run partition, so alignment is exactly the condition
    # that the joint run count stays within the profitability cap — a
    # granularity fine enough to split runs row-by-row prices itself out
    # and falls back to the row program (the ROADMAP item-3 rung)
    bucket = None
    if spec.bucket_mode == "uniform":
        if granularity is None or not granularity.is_uniform \
                or spec.num_buckets < 1:
            return None
        first = int(spec.bucket_starts[0])
        bucket = (first, int(granularity.period_ms), int(spec.num_buckets))
    cols = set()
    for d in spec.dims:
        if d.column is not None:
            if d.column not in segment.dims:
                return None
            cols.add(d.column)
    from druid_tpu.engine.filters import plan_filter, simplify_node
    fnode = simplify_node(plan_filter(flt, segment, device_bitmap=False)) \
        if flt is not None else None
    if not _run_filter_ok(fnode):
        return None
    if fnode is not None:
        cols |= fnode.required_device_columns()
    rkernels = []
    for k in kernels:
        rk = _plan_run_kernel(k, segment)
        if rk is None:
            return None
        rkernels.append(rk)
        cols |= rk.columns()
    for c in cols:
        if c not in segment.dims and c not in segment.metrics:
            return None
    pkey = tuple(sorted(cols))
    # the shared run partition: joint change points of EVERY referenced
    # column — and, for uniform granularities, of the bucket id (cached
    # per column set + bucket signature)
    info = _joint_runs(segment, pkey, bucket)
    if info is None:
        return None
    return (tuple(d.column for d in spec.dims),
            tuple(d.remap is not None for d in spec.dims),
            fnode, rkernels, pkey, bucket, info)


def _joint_runs(segment, pkey: Tuple[str, ...],
                bucket: Optional[Tuple[int, int, int]] = None):
    """Cached (starts, lengths, n_runs) of the joint run partition over
    the named columns (plus, when `bucket` = (first, period, B), the
    uniform-granularity bucket id), or None when too fine-grained to
    pay."""
    def _col_change_starts(c) -> np.ndarray:
        # RLE fast path: a column's change points ARE its run starts, so a
        # column with (cached or format-V2-seeded) run tables contributes
        # them directly — no row scan, no lazy-column materialization
        info = column_run_info(segment, c)
        if info is not None:
            _, ends, nr = info
            return ends[:nr - 1].astype(np.int64) if nr > 1 \
                else np.zeros(0, dtype=np.int64)
        col = segment.dims.get(c)
        v = col.ids if col is not None else segment.metrics[c].values
        return (np.flatnonzero(v[1:] != v[:-1]) + 1).astype(np.int64)

    def _compute():
        n = segment.n_rows
        chunks = [np.zeros(1, dtype=np.int64)]
        chunks.extend(_col_change_starts(c) for c in pkey)
        if bucket is not None:
            first, period, _ = bucket
            bid = (segment.time_ms - first) // period
            chunks.append(
                (np.flatnonzero(bid[1:] != bid[:-1]) + 1).astype(np.int64))
        starts = np.unique(np.concatenate(chunks)).astype(np.int32)
        lengths = np.diff(np.concatenate(
            [starts, [n]])).astype(np.int32)
        return starts, lengths, int(starts.shape[0])
    # cache identity = what the change points actually depend on: bucket
    # BOUNDARIES are (first mod period, period) — a rolling covering
    # window whose start shifts by whole periods reuses the partition
    # instead of re-scanning n_rows and duplicating aux entries
    bkey = None if bucket is None else (bucket[0] % bucket[1], bucket[1])
    starts, lengths, nr = segment.aux_cached(
        ("cascade_runpart", pkey, bkey), _compute)
    cap = _contracts().CASCADE_MAX_RUNS
    if nr > cap or nr * RUN_DOMAIN_MIN_ROWS_PER_RUN > segment.n_rows:
        return None
    return starts, lengths, nr


def _values_at_starts(segment, name: str, starts: np.ndarray, dt):
    """Per-run value of a run-constant column at the joint-partition run
    starts. Columns with run tables (cached, or format-V2-seeded on a lazy
    column) answer via searchsorted over the tables — the mmap-to-HBM path
    never touches decoded rows; everything else gathers from the host
    column. The table path only serves int32-staged columns: rle_encode
    narrows run values to int32, which is exact only there."""
    if dt == np.int32:
        info = column_run_info(segment, name)
        if info is not None:
            rv, ends, nr = info
            idx = np.searchsorted(ends[:nr], starts, side="right")
            return rv[np.minimum(idx, nr - 1)].astype(np.int32)
    col = segment.dims.get(name)
    v = (col.ids if col is not None
         else segment.metrics[name].values)[starts]
    return v.astype(dt) if v.dtype != dt else v


def try_run_domain(segment, intervals, granularity, spec, kernels, flt,
                   virtual_columns):
    """Execute one segment's grouped aggregation fully in run space when
    eligible; returns (counts, device states) or None. Zero decode, zero
    row-width staging — the run tables (a few KB) are the only device
    data, resident in the pool like any derived column."""
    plan = _plan_run_domain(segment, intervals, granularity, spec,
                            kernels, flt, virtual_columns)
    if plan is None:
        return None
    dim_cols, has_remap, fnode, rkernels, pkey, bucket, info = plan
    starts, lengths, nr = info
    rpad = pad_pow2(nr)

    import jax

    # the staging identity must name the PARTITION, not just the column
    # set: a uniform-granularity partition of the same columns has
    # different run tables than the all-granularity one
    part_key = (pkey, bucket)

    def _staged(colname: str, values: np.ndarray, fill=0):
        def _build(v=values):
            out = np.full(rpad, fill, dtype=v.dtype)
            out[: v.shape[0]] = v
            return jax.device_put(out)
        return segment.device_cached(("rundom", part_key, rpad, colname),
                                     _build)

    arrays: Dict[str, object] = {
        "__runlen": _staged("__runlen", lengths)}
    if bucket is not None:
        first, period, _nb = bucket
        bid = ((segment.time_ms[starts] - first) // period).astype(np.int32)
        arrays["__runbucket"] = _staged("__runbucket", bid, fill=-1)
    cols = set(pkey)
    for c in cols:
        dt = np.int32 if c in segment.dims else segment.staged_dtype(c)
        arrays[c] = _staged(c, _values_at_starts(segment, c, starts, dt))

    aux: List[np.ndarray] = []
    for d in spec.dims:
        if d.column is None:
            continue
        if d.remap is not None:
            aux.append(d.remap.astype(np.int32))
        aux.append(np.asarray(d.cardinality, dtype=np.int32))
    if fnode is not None:
        aux.extend(fnode.aux_arrays())
    for rk in rkernels:
        aux.extend(rk.aux())

    sig = "|".join([
        "rundomain",
        f"dims={','.join(f'{c}:{int(r)}' for c, r in zip(dim_cols, has_remap))}",
        f"filt={fnode.signature() if fnode is not None else 'none'}",
        f"aggs={';'.join(rk.sig() for rk in rkernels)}",
        f"total={spec.num_total}", f"R={rpad}",
        f"ub={int(bucket is not None)}",
    ])
    with _RUN_JIT_CACHE_LOCK:
        fn = _RUN_JIT_CACHE.get(sig)
        compiled = fn is None
        if fn is None:
            fn = _build_run_fn(dim_cols, has_remap, fnode, rkernels,
                               spec.num_total,
                               has_bucket=bucket is not None)
            _RUN_JIT_CACHE[sig] = fn
            while len(_RUN_JIT_CACHE) > _RUN_JIT_CACHE_CAP:
                _RUN_JIT_CACHE.popitem(last=False)
        else:
            _RUN_JIT_CACHE.move_to_end(sig)

    from druid_tpu.obs import dispatch as dispatch_mod
    from druid_tpu.obs.trace import span as trace_span
    from druid_tpu.obs.trace import span_when as trace_span_when
    with trace_span("engine/dispatch", strategy="runDomain",
                    rows=segment.n_rows, runs=nr, compile=compiled,
                    program="run_domain_agg"), \
            trace_span_when(compiled, "engine/compile", kind="segment",
                            strategy="runDomain"):
        counts, states = fn(arrays, tuple(aux))
    dispatch_mod.record("runDomain")
    _CODE_STATS.record(segment.n_rows)
    return counts, states
