"""Segments: immutable columnar data blocks, host-resident with device staging.

Capability parity with the reference's QueryableIndex / StorageAdapter surface
(processing/src/main/java/org/apache/druid/segment/QueryableIndex.java:38,
StorageAdapter.java:33) and the V9 column model (segment/column/Column.java:27-52).

TPU-first design, replacing the per-row Cursor pull model:
  * A Segment holds host numpy columns: int32 dictionary ids for string dims
    (sorted dictionary, host-side only), int64/float32/float64 numerics, and
    an int64 `__time` column sorted ascending.
  * `device_block(block_rows)` stages the segment as a DeviceBlock — dense
    jax arrays padded to a static shape (a multiple of the TPU lane tiling)
    plus a validity mask — so XLA compiles exactly one program per
    (query shape, schema, block shape). This replaces Cursor iteration; the
    jit cache plays the role of the reference's ASM monomorphic
    specialization (query/monomorphicprocessing/SpecializationService.java:65).
  * Time on device is an int32 offset from the segment interval start, so no
    64-bit arithmetic is needed in kernels; bucketing for uniform
    granularities is one integer divide on device.
"""
from __future__ import annotations

import enum
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.data.bitmap import BitmapIndex
from druid_tpu.data.dictionary import Dictionary, NULL
from druid_tpu.utils.granularity import Granularity
from druid_tpu.utils.intervals import Interval

# f32 min tile is (8, 128); pad row counts to a multiple of 8*128 so 1-D
# columns reshape cleanly into (sublane, lane) tiles on device.
DEFAULT_ROW_ALIGN = 1024


class ValueType(enum.Enum):
    STRING = "string"
    LONG = "long"
    FLOAT = "float"
    DOUBLE = "double"
    COMPLEX = "complex"

    @property
    def numpy_dtype(self):
        return {
            ValueType.LONG: np.int64,
            ValueType.FLOAT: np.float32,
            ValueType.DOUBLE: np.float64,
        }[self]


@dataclass(frozen=True)
class ColumnCapabilities:
    """Reference analog: segment/column/ColumnCapabilities.java."""
    type: ValueType
    dictionary_encoded: bool = False
    has_bitmap_index: bool = False
    has_multiple_values: bool = False


@dataclass(frozen=True)
class SegmentId:
    """Reference analog: DataSegment identity (api/.../DataSegment)."""
    datasource: str
    interval: Interval
    version: str
    partition: int = 0

    def __str__(self):
        return (f"{self.datasource}_{self.interval}_{self.version}"
                f"_{self.partition}")


@dataclass(frozen=True)
class SegmentSchema:
    """Ordered dim names + metric (name -> type) map."""
    dimensions: Tuple[str, ...]
    metrics: Tuple[Tuple[str, ValueType], ...]

    @property
    def metric_types(self) -> Dict[str, ValueType]:
        return dict(self.metrics)


class StringDimColumn:
    """Dictionary-encoded single-value string dimension."""

    __slots__ = ("ids", "dictionary", "_bitmap_index", "_lock")

    def __init__(self, ids: np.ndarray, dictionary: Dictionary):
        assert ids.dtype == np.int32
        self.ids = ids
        self.dictionary = dictionary
        self._bitmap_index: Optional[BitmapIndex] = None
        self._lock = threading.Lock()

    @property
    def cardinality(self) -> int:
        return self.dictionary.cardinality

    def bitmap_index(self) -> BitmapIndex:
        # built lazily, like the reference mmaps bitmaps on demand
        with self._lock:
            if self._bitmap_index is None:
                self._bitmap_index = BitmapIndex.build(self.ids, self.cardinality)
            return self._bitmap_index

    def set_bitmap_index(self, index: BitmapIndex):
        # same lock as the lazy build: an unlocked store here could be
        # overwritten by a concurrent bitmap_index() builder (or hand a
        # half-published index to it)
        with self._lock:
            self._bitmap_index = index

    def capabilities(self) -> ColumnCapabilities:
        return ColumnCapabilities(ValueType.STRING, dictionary_encoded=True,
                                  has_bitmap_index=True)


class NumericColumn:
    __slots__ = ("values", "type")

    def __init__(self, values: np.ndarray, vtype: ValueType):
        self.values = values
        self.type = vtype

    def capabilities(self) -> ColumnCapabilities:
        return ColumnCapabilities(self.type)


class ComplexColumn:
    """Fixed-width complex metric column: one row = one state vector
    (e.g. HLL registers int8[2^log2m]). Reference analog: ComplexColumn +
    ComplexColumnPartSerde (segment/serde/ComplexColumnPartSerde.java) —
    here states are dense 2-D arrays so device kernels reduce them directly
    (HLL merge = segment_max over rows)."""

    __slots__ = ("values", "type_name")
    type = ValueType.COMPLEX

    def __init__(self, values: np.ndarray, type_name: str):
        assert values.ndim == 2
        self.values = values
        self.type_name = type_name

    def capabilities(self) -> ColumnCapabilities:
        return ColumnCapabilities(ValueType.COMPLEX)


class _ShapeStub:
    """Stands in for a padded host array during staging when the encoder
    needs only its shape/dtype (cascade rle/lz4 columns encode from cached
    run/token tables; persisted format-V2 pack words upload directly).
    Keeps lazy columns lazy: the decoded rows are never built."""

    __slots__ = ("shape", "dtype")

    def __init__(self, n: int, dtype):
        self.shape = (n,)
        self.dtype = np.dtype(dtype)


@dataclass
class DeviceBlock:
    """A segment staged on device as padded dense arrays (all length `padded_rows`).

    arrays:
      "__time_offset": int32 millis from `time0`
      "<dim>":         int32 dictionary ids
      "<metric>":      int64 / float32 / float64 values
      "__valid":       bool row-validity mask (False on padding rows)

    Pack-eligible dim/metric entries may instead be data/packed.py
    PackedColumn values (bit-packed int32 words + descriptor, a jax
    pytree): compressed in HBM, decoded inside the traced program.
    """
    segment_id: SegmentId
    n_rows: int
    padded_rows: int
    time0: int
    arrays: Dict[str, object]
    dictionaries: Dict[str, Dictionary]


class Segment:
    """Immutable columnar segment (host representation)."""

    def __init__(self, segment_id: SegmentId, time_ms: np.ndarray,
                 dims: Dict[str, StringDimColumn],
                 metrics: Dict[str, NumericColumn],
                 sorted_by_time: bool = True,
                 time_ordered: Optional[bool] = None):
        """sorted_by_time=False re-sorts rows by timestamp. sorted_by_time=True
        means "do not re-sort"; pass time_ordered=False alongside it when the
        preserved layout is NOT time-monotonic (e.g. dimension-sorted rollup
        order) so time-pruning optimizations cannot assume monotonicity."""
        self.id = segment_id
        self.time_ms = np.asarray(time_ms, dtype=np.int64)
        self.dims = dims
        self.metrics = metrics
        self.n_rows = int(self.time_ms.shape[0])
        if not sorted_by_time and self.n_rows:
            order = np.argsort(self.time_ms, kind="stable")
            self.time_ms = self.time_ms[order]
            for d in dims.values():
                d.ids = d.ids[order]
            for m in metrics.values():
                m.values = m.values[order]
            time_ordered = True
        #: rows are time-monotonic (safe for searchsorted-style pruning)
        self.time_ordered = True if time_ordered is None else bool(time_ordered)
        self.min_time = int(self.time_ms.min()) if self.n_rows else 0
        self.max_time = int(self.time_ms.max()) if self.n_rows else 0
        # device-resident data (staged blocks, padded device keys) lives in
        # the process-wide byte-budgeted pool: one HBM budget across all
        # segments, LRU by actual bytes, entries purged when this segment
        # is collected (data/devicepool.py)
        from druid_tpu.data.devicepool import device_pool
        self._pool = device_pool()
        self._pool_owner = self._pool.register_owner(self)
        self._aux_cache: Dict[Tuple, object] = {}
        self._lock = threading.Lock()

    # ---- schema/introspection -----------------------------------------
    @property
    def schema(self) -> SegmentSchema:
        return SegmentSchema(tuple(self.dims.keys()),
                             tuple((k, v.type) for k, v in self.metrics.items()))

    @property
    def interval(self) -> Interval:
        return self.id.interval

    def column_capabilities(self, name: str) -> Optional[ColumnCapabilities]:
        if name == "__time":
            return ColumnCapabilities(ValueType.LONG)
        if name in self.dims:
            return self.dims[name].capabilities()
        if name in self.metrics:
            return self.metrics[name].capabilities()
        return None

    def dictionary(self, dim: str) -> Optional[Dictionary]:
        col = self.dims.get(dim)
        return col.dictionary if col else None

    def numeric_values(self, name: str) -> Optional[np.ndarray]:
        col = self.metrics.get(name)
        return col.values if col else None

    # ---- device staging ------------------------------------------------
    def device_block(self, columns: Optional[Sequence[str]] = None,
                     row_align: int = DEFAULT_ROW_ALIGN,
                     device=None, perm: Optional[np.ndarray] = None,
                     perm_key=None) -> DeviceBlock:
        """Stage (a subset of) columns to device, padded to static shape.

        Staging is cached per (columns, row_align, device, perm_key, pack
        descriptor) in the process-wide byte-budgeted device pool; repeated
        queries over the same segment hit HBM-resident arrays — the analog
        of the reference keeping segments mmapped and page-cached
        (server/.../SegmentLoaderLocalCacheManager.java).

        Cascade-eligible columns (data/cascade.py — low-run-count dims and
        int32 metrics as RLE, near-constant `__time_offset` as delta/FOR,
        compressible floats as LZ4 tokens) stage under their cascade
        encoding; pack-eligible columns (data/packed.py — narrow
        dictionary ids, small-range int32-staged longs) stage as
        bit-packed PackedColumn words. Both selections are pure functions
        of column stats (cascade.plan_pair, cascade claims first):
        compressed in HBM, so the pool's byte budget holds ratio more
        segments and a cold miss ships ratio fewer H2D bytes. The traced
        programs decode on-device (cascade.split_resident at the program
        top; the pallas kernel per-tile for packed words). Both
        descriptors join the cache key, so flipping either enable switch
        never serves a mismatched representation.

        `perm` applies a row permutation host-side before staging (the sorted
        projection path); callers must pass a stable hashable `perm_key`
        identifying it so the cache can distinguish layouts.

        `row_align` also serves the batched multi-segment path: staging with
        row_align >= n_rows pads to EXACTLY row_align rows, so batch-mates on
        the same ladder rung stack into one [K, R] program.
        """
        from druid_tpu.data import cascade as cascade_mod
        if perm is not None and perm_key is None:
            raise ValueError("device_block(perm=...) requires perm_key")
        if columns is None:
            columns = list(self.dims.keys()) + list(self.metrics.keys())
        # the shared encode derivation (data/cascade.plan_pair): cascade
        # rungs claim their columns first, bit-packing covers the rest —
        # both descriptors join the pool key, so flipping either switch
        # never serves a mismatched representation
        cascades, packs = cascade_mod.plan_pair(self, columns,
                                                permuted=perm is not None)
        key = ("block", tuple(sorted(set(columns))), row_align,
               getattr(device, "id", None), perm_key, packs, cascades)
        return self._pool.get_or_build(
            self._pool_owner, key,
            lambda: self._stage_block(columns, row_align, device, perm,
                                      packs, cascades))

    def _stage_block(self, columns: Sequence[str], row_align: int,
                     device, perm: Optional[np.ndarray],
                     packs: Tuple = (), cascades: Tuple = ()) -> DeviceBlock:
        import jax

        from druid_tpu.data import cascade as cascade_mod
        from druid_tpu.data import packed as packed_mod
        pack_for = {name: (w, base) for name, w, base in packs}
        cascade_for = {e[0]: e for e in cascades}

        pad_n = max(row_align, ((self.n_rows + row_align - 1) // row_align) * row_align)
        time0 = self.interval.start
        off = (self.time_ms - time0)
        if off.size and (off.min() < 0 or off.max() >= 2**31):
            raise ValueError(
                f"segment rows outside int32 ms-offset range of interval {self.interval}")
        arrays: Dict[str, object] = {}

        def _pad(a: np.ndarray, fill=0):
            if perm is not None:
                a = a[perm]
            out = np.full((pad_n,) + a.shape[1:], fill, dtype=a.dtype)
            out[: a.shape[0]] = a
            return out

        arrays["__time_offset"] = _pad(off.astype(np.int32))
        valid = np.zeros((pad_n,), dtype=bool)
        valid[: self.n_rows] = True
        arrays["__valid"] = valid
        dictionaries: Dict[str, Dictionary] = {}
        packwords: Dict[str, np.ndarray] = {}

        def _cascade_stub(name: str):
            # rle/lz4 encoders read only cached run/token tables plus the
            # padded shape — never the decoded rows, so lazy format-V2
            # columns stage without a host decode
            c = cascade_for.get(name)
            return c is not None and c[1] in ("rle", "lz4")

        def _pack_hint(col_obj, name: str):
            # persisted pack words (format V2) upload as-is when the plan
            # and padded shape match what was written at persist time
            if perm is not None:
                return None
            hint = getattr(col_obj, "_v2_pack", None)
            p = pack_for.get(name)
            if hint is not None and p is not None \
                    and tuple(hint[1:]) == (p[0], p[1], pad_n):
                return hint[0]
            return None

        for name in columns:
            if name in self.dims:
                col = self.dims[name]
                dictionaries[name] = col.dictionary
                if _cascade_stub(name):
                    arrays[name] = _ShapeStub(pad_n, np.int32)
                    continue
                words = _pack_hint(col, name)
                if words is not None:
                    packwords[name] = words
                    arrays[name] = _ShapeStub(pad_n, np.int32)
                    continue
                arrays[name] = _pad(col.ids)
            elif name in self.metrics:
                m = self.metrics[name]
                dt = self.staged_dtype(name)
                if _cascade_stub(name):
                    arrays[name] = _ShapeStub(pad_n, dt)
                    continue
                words = _pack_hint(m, name)
                if words is not None:
                    packwords[name] = words
                    arrays[name] = _ShapeStub(pad_n, dt)
                    continue
                vals = m.values if m.values.dtype == dt \
                    else m.values.astype(dt)
                arrays[name] = _pad(vals)
            elif name in ("__time", "__time_offset", "__valid"):
                continue
            else:
                raise KeyError(f"no such column {name!r} in segment {self.id}")

        put = (lambda a: jax.device_put(a, device)) if device is not None \
            else jax.device_put

        def _stage(name: str, v):
            c = cascade_for.get(name)
            if c is not None:
                return cascade_mod.encode_column(self, name, c, v, put)
            p = pack_for.get(name)
            if p is None:
                return put(v)
            w, base = p
            words = packwords[name] if name in packwords \
                else packed_mod.pack_padded(v, w, base)
            return packed_mod.PackedColumn(put(np.asarray(words)), w, base,
                                           v.shape[0], str(v.dtype))

        return DeviceBlock(
            segment_id=self.id, n_rows=self.n_rows, padded_rows=pad_n,
            time0=time0, arrays={k: _stage(k, v) for k, v in arrays.items()},
            dictionaries=dictionaries,
        )

    def device_cached(self, key: Tuple, fn):
        """Memoize a derived DEVICE array through the same byte-budgeted
        pool as staged blocks (HBM entries must not accumulate per query
        shape)."""
        return self._pool.get_or_build(self._pool_owner, ("aux",) + key, fn)

    def device_contains(self, key: Tuple) -> bool:
        """Residency probe for a device_cached entry (no stats/LRU touch) —
        the filter-bitmap cache's own hit/miss accounting."""
        return self._pool.peek(self._pool_owner, ("aux",) + key)

    def device_take(self, key: Tuple):
        """Pop a device_cached entry (None when absent) — the megakernel's
        donated-carry handoff (the buffers must leave the pool before
        donation invalidates them)."""
        return self._pool.take(self._pool_owner, ("aux",) + key)

    def adopt_carries_from(self, donor: "Segment") -> None:
        """Standing-query carry bridge (engine/standing.py): a live sink's
        snapshot is a FRESH Segment every generation, so the megakernel's
        per-segment donated carries would never be reused across ticks.
        Naming the previous snapshot here lets the per-segment enqueue's
        carry take fall back to the donor's parked grids. ONLY carries may
        bridge — they are content-free HBM allocations the kernel re-inits
        at grid step 0; staged data never transfers between segments.
        This is one of the PARK verbs in donorguard's ownership
        vocabulary (tools/druidlint/donorguard.py): a popped carry handed
        to the bridge counts as discharged, same as put/device_cached."""
        import weakref
        self._carry_donor = weakref.ref(donor)

    def carry_donor(self) -> Optional["Segment"]:
        ref = getattr(self, "_carry_donor", None)
        return ref() if ref is not None else None

    def column_minmax(self, name: str) -> Tuple[int, int]:
        """Cached (min, max) of a numeric column (0, 0 when empty)."""
        def _compute():
            v = self.metrics[name].values
            if v.size == 0:
                return (0, 0)
            return (v.min().item(), v.max().item())
        return self.aux_cached(("minmax", name), _compute)

    def column_finite(self, name: str) -> bool:
        """Cached: True when a float column contains no NaN/Inf. Gates the
        one-hot-matmul float path, where a single non-finite value would
        poison every group (NaN·0 = NaN in the one-hot contraction)."""
        def _compute():
            m = self.metrics.get(name)
            if m is None or not np.issubdtype(m.values.dtype, np.floating):
                return True
            return bool(np.isfinite(m.values).all())
        return self.aux_cached(("finite", name), _compute)

    def staged_dtype(self, name: str):
        """Device dtype a column stages as. LONG columns whose values fit
        int32 stage narrow: 64-bit ops are limb-emulated on TPU (~5x cost),
        and almost all real long metrics fit 32 bits. Aggregation kernels
        restore exact 64-bit semantics at group granularity."""
        if name in self.dims:
            return np.int32
        if name in ("__time_offset",):
            return np.int32
        m = self.metrics.get(name)
        if m is None:
            return None
        if m.type is ValueType.LONG:
            lo, hi = self.column_minmax(name)
            if -(2**31) <= lo and hi < 2**31:
                return np.int32
            return np.int64
        if m.type in (ValueType.FLOAT, ValueType.DOUBLE):
            # from type metadata, not m.values.dtype: lazy format-V2
            # columns answer without materializing
            return np.dtype(m.type.numpy_dtype)
        return m.values.dtype             # complex states

    def aux_cached(self, key: Tuple, fn):
        """Memoize derived host arrays (e.g. calendar bucket ids, fused
        group keys) per segment — the analog of the reference's per-segment
        column caches."""
        with self._lock:
            if key in self._aux_cache:
                return self._aux_cache[key]
        value = fn()
        with self._lock:
            self._aux_cache[key] = value
        return value

    def size_bytes(self) -> int:
        # logical_nbytes hint first: lazy format-V2 columns report decoded
        # size without materializing (it equals .nbytes by construction)
        n = self.time_ms.nbytes
        for d in self.dims.values():
            hint = getattr(d, "logical_nbytes", None)
            n += hint if hint is not None else d.ids.nbytes
        for m in self.metrics.values():
            hint = getattr(m, "logical_nbytes", None)
            n += hint if hint is not None else m.values.nbytes
        return int(n)

    def __repr__(self):
        return f"Segment({self.id}, rows={self.n_rows})"


class SegmentBuilder:
    """Builds an immutable Segment from rows or columns.

    Reference analog: IncrementalIndex + IndexMergerV9.persist for the
    "make a queryable segment" capability (segment/IndexMergerV9.java:729) —
    the streaming-ingest IncrementalIndex analog with rollup lives in
    druid_tpu/ingest/incremental.py.
    """

    def __init__(self, datasource: str, interval: Interval, version: str = "v0",
                 partition: int = 0,
                 shared_dictionaries: Optional[Dict[str, Dictionary]] = None):
        self.segment_id = SegmentId(datasource, interval, version, partition)
        self._time: List[int] = []
        self._dim_values: Dict[str, List[str]] = {}
        self._metric_values: Dict[str, List] = {}
        self._metric_types: Dict[str, ValueType] = {}
        self._shared_dicts = shared_dictionaries or {}
        self._n = 0

    def add_row(self, ts_ms: int, dims: Dict[str, Optional[str]],
                metrics: Dict[str, float]):
        for name in dims:
            if name not in self._dim_values:
                # null backfill for a newly-seen dim: _n is the shared
                # row count, identical for every column by construction
                self._dim_values[name] = [NULL] * self._n  # druidlint: disable=unkeyed-trace-input
        for name in metrics:
            if name not in self._metric_values:
                # same backfill invariant as the dim columns above
                self._metric_values[name] = [0] * self._n  # druidlint: disable=unkeyed-trace-input
                self._metric_types.setdefault(
                    name, ValueType.LONG if isinstance(metrics[name], int)
                    else ValueType.DOUBLE)
            elif (self._metric_types.get(name) == ValueType.LONG
                  and isinstance(metrics.get(name), float)):
                # widen LONG -> DOUBLE when a float arrives later, instead of
                # silently truncating at build time
                self._metric_types[name] = ValueType.DOUBLE
        self._time.append(int(ts_ms))
        for name, vals in self._dim_values.items():
            v = dims.get(name)
            vals.append(NULL if v is None else str(v))
        for name, vals in self._metric_values.items():
            vals.append(metrics.get(name, 0))
        self._n += 1

    def add_columns(self, time_ms: np.ndarray,
                    dims: Dict[str, Sequence[str]],
                    metrics: Dict[str, np.ndarray],
                    metric_types: Optional[Dict[str, ValueType]] = None):
        if self._n:
            raise ValueError("add_columns on non-empty builder unsupported")
        self._time = list(np.asarray(time_ms, dtype=np.int64))
        for k, v in dims.items():
            self._dim_values[k] = [NULL if x is None else str(x) for x in v]
        for k, v in metrics.items():
            arr = np.asarray(v)
            self._metric_values[k] = arr
            if metric_types and k in metric_types:
                self._metric_types[k] = metric_types[k]
            else:
                self._metric_types[k] = (
                    ValueType.LONG if np.issubdtype(arr.dtype, np.integer)
                    else ValueType.DOUBLE if arr.dtype == np.float64
                    else ValueType.FLOAT)
        self._n = len(self._time)

    def build(self) -> Segment:
        time_ms = np.asarray(self._time, dtype=np.int64)
        dims: Dict[str, StringDimColumn] = {}
        for name, values in self._dim_values.items():
            d = self._shared_dicts.get(name) or Dictionary.from_values(values)
            dims[name] = StringDimColumn(d.encode(values), d)
        metrics: Dict[str, NumericColumn] = {}
        for name, values in self._metric_values.items():
            vtype = self._metric_types[name]
            arr = np.asarray(values, dtype=vtype.numpy_dtype)
            metrics[name] = NumericColumn(arr, vtype)
        return Segment(self.segment_id, time_ms, dims, metrics,
                       sorted_by_time=False)
