"""Filter planning: DimFilter trees → device mask programs + host bitmap algebra.

Reference analog: segment/filter/Filters.java:65 (toFilter, CNF,
shouldUseBitmapIndex) and the pre/post-filter split in
QueryableIndexStorageAdapter.makeCursors (:235-282).

TPU-first design:
  * String predicates (selector/in/bound/like/regex/search/javascript) are
    evaluated host-side against the dimension *dictionary* (cardinality-sized,
    tiny) producing a boolean lookup table (LUT). On device the predicate is
    one gather: `lut[ids]`. This one mechanism covers every string matcher the
    reference implements with per-row Predicate objects.
  * Numeric predicates compile to vectorized comparisons on the value column.
  * A FilterNode has a *structural signature* (no embedded constants) so the
    jitted kernel is shared across queries/segments with the same shape;
    constants (LUTs, bounds, remaps) are passed as device arguments. This is
    the XLA analog of the reference's bytecode specialization cache
    (query/monomorphicprocessing/SpecializationService.java:65).
  * `bitmap_of` implements the classic host bitmap-index path (used by the
    search engine, segment pruning, and selectivity estimation), mirroring
    Filter.getBitmapIndex.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import hashlib
import os
import re
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from druid_tpu.data.bitmap import (SPARSE_LEAF_SHARE, AnyBitmap, Bitmap,
                                   SparseBitmap, bitmap_and, bitmap_or,
                                   leaf_rows, sparse_leaf_width)
from druid_tpu.data.dictionary import Dictionary, merge_dictionaries
from druid_tpu.data.devicepool import thread_builds
from druid_tpu.data.segment import Segment, ValueType
from druid_tpu.engine.contracts import named_program
from druid_tpu.obs.trace import span as trace_span
from druid_tpu.obs.trace import span_when as trace_span_when
from druid_tpu.query import filters as F
from druid_tpu.utils.emitter import Monitor
from druid_tpu.utils.expression import parse_expression
from druid_tpu.utils.intervals import Interval

#: process default for the device-bitmap filter path; per-process opt-out
#: via DRUID_TPU_DEVICE_BITMAP=0 or set_device_bitmap_enabled(False).
_DEVICE_BITMAP = os.environ.get("DRUID_TPU_DEVICE_BITMAP", "1").lower() \
    not in ("0", "false", "no")
_DEVICE_BITMAP_LOCK = threading.Lock()


def set_device_bitmap_enabled(on: bool) -> bool:
    """Flip the process-wide device-bitmap default; returns the previous
    value (bench/test toggle, the batching/packed.set_enabled discipline)."""
    global _DEVICE_BITMAP
    with _DEVICE_BITMAP_LOCK:
        prev = _DEVICE_BITMAP
        _DEVICE_BITMAP = bool(on)
        return prev


def device_bitmap_enabled() -> bool:
    return _DEVICE_BITMAP


# ---------------------------------------------------------------------------
# Device-side filter plan nodes
# ---------------------------------------------------------------------------

class FilterNode:
    """A planned filter; structure is segment-independent, aux arrays are not."""

    def signature(self) -> str:
        raise NotImplementedError

    def aux_arrays(self) -> List[np.ndarray]:
        """Constant device inputs, flattened in deterministic order."""
        return []

    def required_device_columns(self) -> Set[str]:
        """Segment columns build() reads from `cols`. Narrower than the
        DimFilter's required_columns: a subtree compiled to a device bitmap
        (DeviceBitmapNode) needs NO staged columns at all — its words ride
        the arrays dict under a synthetic name — so filter-only dimensions
        stop being staged entirely."""
        return set()

    def build(self, cols: Dict[str, object], aux: Iterator):
        """Trace the mask computation. `cols` maps column name -> device array
        (plus "__time_offset"); `aux` yields staged aux arrays in order."""
        raise NotImplementedError


class ConstNode(FilterNode):
    def __init__(self, value: bool):
        self.value = value

    def signature(self):
        return f"const({self.value})"

    def build(self, cols, aux):
        import jax.numpy as jnp
        n = cols["__valid"].shape[0]
        return jnp.full((n,), self.value, dtype=bool)


class LutNode(FilterNode):
    """mask = lut[ids] — all dictionary predicates reduce to this."""

    def __init__(self, dim: str, lut: np.ndarray):
        self.dim = dim
        self.lut = lut.astype(bool)

    def signature(self):
        return f"lut({self.dim})"

    def required_device_columns(self):
        return {self.dim}

    def aux_arrays(self):
        return [self.lut]

    def build(self, cols, aux):
        lut = next(aux)
        return lut[cols[self.dim]]


class NumericCmpNode(FilterNode):
    """lower <= col <= upper with optional strictness; bounds passed as aux."""

    def __init__(self, column: str, lower: Optional[float], upper: Optional[float],
                 lower_strict: bool, upper_strict: bool, dtype):
        self.column = column
        self.lower, self.upper = lower, upper
        self.lower_strict, self.upper_strict = lower_strict, upper_strict
        self.dtype = dtype

    def signature(self):
        return (f"numcmp({self.column},{self.lower is not None},"
                f"{self.upper is not None},{self.lower_strict},{self.upper_strict})")

    def required_device_columns(self):
        return {self.column}

    def aux_arrays(self):
        out = []
        if self.lower is not None:
            out.append(np.asarray(self.lower, dtype=self.dtype))
        if self.upper is not None:
            out.append(np.asarray(self.upper, dtype=self.dtype))
        return out

    def build(self, cols, aux):
        import jax.numpy as jnp
        v = cols[self.column]
        mask = None
        if self.lower is not None:
            lo = next(aux)
            m = (v > lo) if self.lower_strict else (v >= lo)
            mask = m
        if self.upper is not None:
            hi = next(aux)
            m = (v < hi) if self.upper_strict else (v <= hi)
            mask = m if mask is None else (mask & m)
        if mask is None:
            mask = jnp.ones(v.shape, dtype=bool)
        return mask


class NumericEqNode(FilterNode):
    def __init__(self, column: str, value: float, dtype):
        self.column = column
        self.value = value
        self.dtype = dtype

    def signature(self):
        return f"numeq({self.column})"

    def required_device_columns(self):
        return {self.column}

    def aux_arrays(self):
        return [np.asarray(self.value, dtype=self.dtype)]

    def build(self, cols, aux):
        return cols[self.column] == next(aux)


class NumericInNode(FilterNode):
    def __init__(self, column: str, values: np.ndarray):
        self.column = column
        self.values = values

    def signature(self):
        return f"numin({self.column},{len(self.values)})"

    def required_device_columns(self):
        return {self.column}

    def aux_arrays(self):
        return [self.values]

    def build(self, cols, aux):
        import jax.numpy as jnp
        vals = next(aux)
        v = cols[self.column]
        return jnp.any(v[:, None] == vals[None, :], axis=1)


class TimeIntervalsNode(FilterNode):
    """__time within k intervals; offsets relative to block.time0 as aux [k,2]."""

    def __init__(self, offsets: np.ndarray):
        self.offsets = offsets.astype(np.int32)  # shape [k, 2]

    def signature(self):
        return f"timein({self.offsets.shape[0]})"

    def aux_arrays(self):
        return [self.offsets]

    def build(self, cols, aux):
        import jax.numpy as jnp
        iv = next(aux)
        t = cols["__time_offset"]
        m = (t[:, None] >= iv[None, :, 0]) & (t[:, None] < iv[None, :, 1])
        return jnp.any(m, axis=1)


class ColumnCompareNode(FilterNode):
    """dimA == dimB via remap into a merged dictionary id space."""

    def __init__(self, dims: Tuple[str, ...], remaps: List[np.ndarray]):
        self.dims = dims
        self.remaps = remaps

    def signature(self):
        return f"colcmp({','.join(self.dims)})"

    def required_device_columns(self):
        return set(self.dims)

    def aux_arrays(self):
        return list(self.remaps)

    def build(self, cols, aux):
        first = next(aux)[cols[self.dims[0]]]
        mask = None
        for d in self.dims[1:]:
            other = next(aux)[cols[d]]
            m = first == other
            mask = m if mask is None else (mask & m)
        return mask


class ExpressionNode(FilterNode):
    """Expression filter traced to XLA elementwise ops. String-dimension
    comparisons are rewritten at plan time into per-dictionary-id boolean
    LUT gathers (utils.expression.rewrite_string_sites) — the device path
    stays purely numeric."""

    def __init__(self, expression: str, time0: int, segment=None):
        from druid_tpu.utils.expression import (lut_for_site,
                                                rewrite_string_sites)
        self.expression = expression
        self.time0 = time0
        string_dims = frozenset(segment.dims) if segment is not None \
            else frozenset()
        self.expr, sites = rewrite_string_sites(
            parse_expression(expression), string_dims)
        self.luts = [lut_for_site(s, segment.dims[s[0]].dictionary.values)
                     for s in sites] if segment is not None else []

    def signature(self):
        # the REWRITTEN AST must key the jit cache: the same expression
        # string over different schemas (dim vs metric column) rewrites to
        # structurally different programs
        return f"expr({self.expr!r};l{len(self.luts)})"

    def required_device_columns(self):
        return set(self.expr.required_columns())

    def aux_arrays(self):
        return [np.asarray(self.time0, dtype=np.int64)] + list(self.luts)

    def build(self, cols, aux):
        import jax.numpy as jnp
        time0 = next(aux)
        bindings = dict(cols)
        bindings["__time"] = cols["__time_offset"].astype(jnp.int64) + time0
        bindings["__luts"] = [next(aux) for _ in self.luts]
        out = self.expr.evaluate(bindings)
        return jnp.asarray(out, dtype=bool) if hasattr(out, "shape") else (
            jnp.full((cols["__valid"].shape[0],), bool(out)))


class AndNode(FilterNode):
    def __init__(self, children: List[FilterNode]):
        self.children = children

    def signature(self):
        return "and(" + ",".join(c.signature() for c in self.children) + ")"

    def required_device_columns(self):
        out = set()
        for c in self.children:
            out |= c.required_device_columns()
        return out

    def aux_arrays(self):
        return [a for c in self.children for a in c.aux_arrays()]

    def build(self, cols, aux):
        mask = self.children[0].build(cols, aux)
        for c in self.children[1:]:
            mask = mask & c.build(cols, aux)
        return mask


class OrNode(FilterNode):
    def __init__(self, children: List[FilterNode]):
        self.children = children

    def signature(self):
        return "or(" + ",".join(c.signature() for c in self.children) + ")"

    def required_device_columns(self):
        out = set()
        for c in self.children:
            out |= c.required_device_columns()
        return out

    def aux_arrays(self):
        return [a for c in self.children for a in c.aux_arrays()]

    def build(self, cols, aux):
        mask = self.children[0].build(cols, aux)
        for c in self.children[1:]:
            mask = mask | c.build(cols, aux)
        return mask


class NotNode(FilterNode):
    def __init__(self, child: FilterNode):
        self.child = child

    def signature(self):
        return "not(" + self.child.signature() + ")"

    def required_device_columns(self):
        return self.child.required_device_columns()

    def aux_arrays(self):
        return self.child.aux_arrays()

    def build(self, cols, aux):
        return ~self.child.build(cols, aux)


class DeviceBitmapNode(FilterNode):
    """A bitmap-eligible filter subtree compiled to device bitmap algebra.

    The Roaring-informed device path (ROADMAP item 5): per-leaf row bitmaps
    ship density-adaptively (sparse id lists scatter into words ON DEVICE,
    dense leaves ship packed uint32 words) and the subtree's AND/OR/NOT
    combines as word-wise ops in a tiny jitted fill program whose output —
    the combined filter bitmap — lives in the byte-budgeted device pool,
    keyed like the jit caches (structural signature + segment identity +
    aux digest: stage_device_bitmaps). The aggregation program then reads
    the RESIDENT words under `self.col` and derives the row mask by an
    in-program bit test (a broadcast shift, no gather), so:

      * no per-wave host mask upload, no filter-only column staging — the
        words cost 1 bit/row of HBM instead of 32;
      * repeated dashboards hit resident words and skip the bitmap algebra
        entirely (query/filter/* metrics);
      * the program structure is independent of the subtree: ANY two
        bitmap filters share one jitted aggregation program AND can share
        one batched chunk — their words differ per (segment, filter), not
        per program (engine/batching.py fuses across filters).
    """

    def __init__(self, flt: F.DimFilter, segment: Segment):
        self.slot = 0                    # assigned by plan_filter post-walk
        self.leaves: List[Tuple[str, np.ndarray]] = []   # (dim, lut)
        self.structure = self._compile(flt, segment)

    def _compile(self, flt: F.DimFilter, segment: Segment):
        if isinstance(flt, F.TrueFilter):
            return ("const", True)
        if isinstance(flt, F.FalseFilter):
            return ("const", False)
        if isinstance(flt, F.AndFilter):
            return ("and", tuple(self._compile(f, segment)
                                 for f in flt.fields))
        if isinstance(flt, F.OrFilter):
            return ("or", tuple(self._compile(f, segment)
                                for f in flt.fields))
        if isinstance(flt, F.NotFilter):
            return ("not", self._compile(flt.field, segment))
        dim = flt.dimension
        pred = _string_predicate(flt)
        self.leaves.append((dim, _dictionary_lut(segment.dims[dim].dictionary,
                                                 pred)))
        return ("leaf", len(self.leaves) - 1)

    @property
    def col(self) -> str:
        return f"__fbmp{self.slot}"

    def signature(self):
        # deliberately structure-free: the aggregation program sees only
        # resident words + a bit test, so every bitmap subtree in this slot
        # shares one jitted program (the full structure keys the POOL entry
        # via structure_sig/digest instead)
        return f"devbmp({self.slot})"

    def structure_sig(self) -> str:
        def render(node):
            op = node[0]
            if op == "leaf":
                return f"leaf({self.leaves[node[1]][0]})"
            if op == "const":
                return f"const({node[1]})"
            if op == "not":
                return f"not({render(node[1])})"
            return f"{op}(" + ",".join(render(c) for c in node[1]) + ")"
        return render(self.structure)

    def digest(self) -> str:
        """Aux digest: WHICH dictionary ids each leaf matches (the LUT
        bytes). Same structure + same digests + same segment ⇒ same
        resident words — the filter-cache key contract."""
        h = hashlib.sha1(self.structure_sig().encode())
        for dim, lut in self.leaves:
            h.update(dim.encode())
            h.update(lut.tobytes())
        return h.hexdigest()[:20]

    def build(self, cols, aux):
        import jax.numpy as jnp
        w = cols[self.col]                       # uint32 [padded_rows / 32]
        sh = jnp.arange(32, dtype=jnp.uint32)
        bits = (w[:, None] >> sh[None, :]) & jnp.uint32(1)
        return bits.reshape(-1).astype(bool)


def collect_bitmap_nodes(node: Optional[FilterNode]
                         ) -> List[DeviceBitmapNode]:
    """Every DeviceBitmapNode in a planned tree, deterministic DFS order."""
    out: List[DeviceBitmapNode] = []

    def walk(n):
        if isinstance(n, DeviceBitmapNode):
            out.append(n)
        elif isinstance(n, (AndNode, OrNode)):
            for c in n.children:
                walk(c)
        elif isinstance(n, NotNode):
            walk(n.child)
    if node is not None:
        walk(node)
    return out


def assign_bitmap_slots(filter_node: Optional[FilterNode],
                        kernels: Sequence = ()) -> int:
    """Globally unique bitmap slots across ONE execution's trees: the query
    filter first, then every filtered-aggregator tree in kernel order.
    plan_filter slots each tree from 0, so a filtered aggregator's words
    would collide with the query filter's under the same `__fbmpN` name —
    this pass (called once per plan, grouping.plan_grouped_aggregate) makes
    the staged-array namespace collision-free. Returns the slot count."""
    slot = 0
    for node in collect_bitmap_nodes(filter_node):
        node.slot = slot
        slot += 1
    for k in kernels:
        for tree in k.filter_trees():
            for node in collect_bitmap_nodes(tree):
                node.slot = slot
                slot += 1
    return slot


def perm_digest(perm_key) -> Optional[str]:
    """Stable digest of a row-permutation identity (the projection cache
    key) for pool keys; None = original row order."""
    if perm_key is None:
        return None
    return hashlib.sha1(repr(perm_key).encode()).hexdigest()[:16]


def bitmap_pool_key(node: "DeviceBitmapNode", padded_rows: int,
                    perm_dig: Optional[str] = None) -> Tuple:
    """THE pool key for a filter's combined resident words: (structure
    signature, aux digest, padded rows, permutation digest). Shared by the
    staging wave below and the megakernel's residency probe
    (engine/megakernel.megaize), so the two paths cannot key-drift. The
    permutation digest (engine/grouping.py projection layouts) keys
    PERMUTED-row-order words separately from original-order words — the
    permuted path hits its own cache instead of re-planning onto the
    column path."""
    return ("fbmp", node.structure_sig(), node.digest(), padded_rows,
            perm_dig)


# ---------------------------------------------------------------------------
# String predicate → dictionary LUT
# ---------------------------------------------------------------------------

def _dictionary_lut(d: Dictionary, pred) -> np.ndarray:
    return np.fromiter((bool(pred(v)) for v in d.values), dtype=bool,
                       count=d.cardinality)


def _string_predicate(flt: F.DimFilter):
    """Value-level predicate for a single-dim string filter (used for LUTs and
    for row-level evaluation in having specs). An extraction_fn on the
    filter transforms each dictionary value BEFORE the predicate — exactly
    the reference's dimension-extraction filtering, and still one host LUT
    over the dictionary."""
    ex = getattr(flt, "extraction_fn", None)
    if ex is not None:
        import dataclasses
        base = _string_predicate(dataclasses.replace(flt,
                                                     extraction_fn=None))
        if base is None:
            return None

        def extracted(v, _base=base, _ex=ex):
            out = _ex.apply(v)
            return _base("" if out is None else out)
        return extracted
    # extension filters (e.g. bloom) expose a value_predicate() hook
    if hasattr(flt, "value_predicate"):
        return flt.value_predicate()
    if isinstance(flt, F.SelectorFilter):
        target = "" if flt.value is None else flt.value
        return lambda v: v == target
    if isinstance(flt, F.InFilter):
        vals = {("" if v is None else v) for v in flt.values}
        return lambda v: v in vals
    if isinstance(flt, F.BoundFilter):
        lo, up = flt.lower, flt.upper
        ls, us = flt.lower_strict, flt.upper_strict
        if flt.ordering == "numeric":
            def num_pred(v):
                try:
                    x = float(v)
                except (TypeError, ValueError):
                    return False
                if lo is not None:
                    l = float(lo)
                    if x < l or (ls and x == l):
                        return False
                if up is not None:
                    u = float(up)
                    if x > u or (us and x == u):
                        return False
                return True
            return num_pred

        def lex_pred(v):
            if lo is not None and (v < lo or (ls and v == lo)):
                return False
            if up is not None and (v > up or (us and v == up)):
                return False
            return True
        return lex_pred
    if isinstance(flt, F.LikeFilter):
        rx = re.compile(flt.regex())
        return lambda v: rx.match(v) is not None
    if isinstance(flt, F.RegexFilter):
        rx = re.compile(flt.pattern)
        return lambda v: rx.search(v) is not None
    if isinstance(flt, F.SearchFilter):
        if flt.case_sensitive:
            return lambda v: flt.value in v
        needle = flt.value.lower()
        return lambda v: needle in v.lower()
    if isinstance(flt, F.JavaScriptFilter):
        return flt.predicate
    return None


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def plan_filter(flt: Optional[F.DimFilter], segment: Segment,
                virtual_columns: Sequence = (),
                device_bitmap: Optional[bool] = None) -> Optional[FilterNode]:
    """device_bitmap: compile bitmap-eligible subtrees to DeviceBitmapNodes
    (None → the process default). Every execution path — per-segment,
    batched, and the sharded mesh — keeps resident bitmap words: the
    sharded stack carries them as per-segment word slots on the mapped
    axis. Filtered aggregators follow the process default
    (kernels.make_kernel), riding resident words / the fused megakernel
    like the query filter."""
    if flt is None:
        return None
    flt = flt.optimize()
    vc_types = {v.name: v.output_type for v in virtual_columns}
    use_bitmap = device_bitmap_enabled() if device_bitmap is None \
        else device_bitmap
    node = _plan(flt, segment, vc_types, use_bitmap)
    for slot, bn in enumerate(collect_bitmap_nodes(node)):
        bn.slot = slot
    return node


def _bitmap_compilable(flt: F.DimFilter, segment: Segment) -> bool:
    """Whole subtree is bitmap-algebra material AND touches at least one
    real indexed dimension (pure-constant subtrees fold to ConstNodes —
    cheaper than words)."""
    if not can_use_bitmap(flt, segment):
        return False

    def has_leaf(f):
        if isinstance(f, (F.AndFilter, F.OrFilter)):
            return any(has_leaf(x) for x in f.fields)
        if isinstance(f, F.NotFilter):
            return has_leaf(f.field)
        return getattr(f, "dimension", None) in segment.dims
    return has_leaf(flt)


def _plan(flt: F.DimFilter, segment: Segment,
          vc_types: Optional[Dict[str, str]] = None,
          use_bitmap: bool = False) -> FilterNode:
    vc_types = vc_types or {}
    if isinstance(flt, F.TrueFilter):
        return ConstNode(True)
    if isinstance(flt, F.FalseFilter):
        return ConstNode(False)
    if use_bitmap and _bitmap_compilable(flt, segment):
        # maximal eligible subtree → resident device bitmap words; partial
        # trees recurse and wrap their eligible branches below
        return DeviceBitmapNode(flt, segment)
    if isinstance(flt, F.AndFilter):
        return AndNode([_plan(f, segment, vc_types, use_bitmap)
                        for f in flt.fields])
    if isinstance(flt, F.OrFilter):
        return OrNode([_plan(f, segment, vc_types, use_bitmap)
                       for f in flt.fields])
    if isinstance(flt, F.NotFilter):
        return NotNode(_plan(flt.field, segment, vc_types, use_bitmap))
    if isinstance(flt, F.IntervalFilter):
        if flt.dimension != "__time":
            raise ValueError("interval filter supported on __time only")
        t0 = segment.interval.start
        offs = np.asarray(
            [[max(iv.start - t0, -(2**31) + 1), min(iv.end - t0, 2**31 - 1)]
             for iv in flt.intervals], dtype=np.int64).clip(-(2**31) + 1, 2**31 - 1)
        return TimeIntervalsNode(offs.astype(np.int32))
    if isinstance(flt, F.ColumnComparisonFilter):
        dicts = []
        for d in flt.dimensions:
            col = segment.dims.get(d)
            if col is None:
                raise ValueError(f"columnComparison on non-string dim {d!r}")
            dicts.append(col.dictionary)
        _, remaps = merge_dictionaries(dicts)
        return ColumnCompareNode(flt.dimensions, remaps)
    if isinstance(flt, F.ExpressionFilter):
        return ExpressionNode(flt.expression, segment.interval.start, segment)

    # single-column leaf filters
    dim = getattr(flt, "dimension", None)
    if dim is None:
        raise ValueError(f"cannot plan filter {flt!r}")
    if dim in segment.dims:
        d = segment.dims[dim].dictionary
        pred = _string_predicate(flt)
        if pred is None:
            raise ValueError(f"cannot plan string filter {flt!r}")
        # bound filters on sorted dictionaries could use id ranges
        # (Dictionary.id_range); the LUT is equally one gather so we keep
        # the uniform mechanism.
        return LutNode(dim, _dictionary_lut(d, pred))
    if getattr(flt, "extraction_fn", None) is not None:
        # numeric/time columns have no dictionary to transform
        raise ValueError(
            f"extractionFn filter on non-string column [{dim}]")
    # numeric column (metric) or __time
    if dim == "__time":
        dtype, colname = np.int32, "__time_offset"
        # clip to the int32 offset range (bounds far outside the segment's
        # interval still compare correctly after clipping)
        conv = lambda s: min(max(int(s) - segment.interval.start,
                                 -(2**31) + 1), 2**31 - 2)
    elif dim in segment.metrics:
        vt = segment.metrics[dim].type
        # compare in the column's STAGED dtype — an int64 constant against
        # an int32-narrowed column would promote the whole compare to
        # emulated 64-bit ops on device
        dtype, colname = segment.staged_dtype(dim), dim
        conv = (int if vt == ValueType.LONG else float)
        if vt == ValueType.LONG and dtype == np.int32:
            # constants outside int32 have constant outcomes (every value
            # fits int32 — that is why the column staged narrow)
            return _plan_narrow_long(flt, colname)
    elif dim in vc_types:
        t = vc_types[dim]
        dtype = {"long": np.int64, "float": np.float32}.get(t, np.float64)
        colname = dim
        conv = (int if t == "long" else float)
    else:
        # missing column: selector of null matches all rows, else none
        if isinstance(flt, F.SelectorFilter) and (flt.value is None or flt.value == ""):
            return ConstNode(True)
        return ConstNode(False)

    if isinstance(flt, F.SelectorFilter):
        if flt.value is None:
            return ConstNode(False)
        return NumericEqNode(colname, conv(flt.value), dtype)
    if isinstance(flt, F.InFilter):
        vals = np.asarray([conv(v) for v in flt.values if v is not None], dtype=dtype)
        return NumericInNode(colname, vals)
    if isinstance(flt, F.BoundFilter):
        lo = conv(flt.lower) if flt.lower is not None else None
        hi = conv(flt.upper) if flt.upper is not None else None
        return NumericCmpNode(colname, lo, hi, flt.lower_strict, flt.upper_strict,
                              dtype)
    raise ValueError(f"cannot plan filter {type(flt).__name__} on numeric column")


_I32_MIN, _I32_MAX = -(2**31), 2**31 - 1


def _plan_narrow_long(flt: F.DimFilter, colname: str) -> FilterNode:
    """Numeric filters over int32-staged long columns: in-range constants
    compare in int32; out-of-range constants fold to constants."""
    if isinstance(flt, F.SelectorFilter):
        if flt.value is None:
            return ConstNode(False)
        v = int(flt.value)
        if not (_I32_MIN <= v <= _I32_MAX):
            return ConstNode(False)
        return NumericEqNode(colname, v, np.int32)
    if isinstance(flt, F.InFilter):
        vals = [int(v) for v in flt.values if v is not None]
        vals = [v for v in vals if _I32_MIN <= v <= _I32_MAX]
        if not vals:
            return ConstNode(False)
        return NumericInNode(colname, np.asarray(vals, dtype=np.int32))
    if isinstance(flt, F.BoundFilter):
        lo = int(flt.lower) if flt.lower is not None else None
        hi = int(flt.upper) if flt.upper is not None else None
        if lo is not None and lo > _I32_MAX:
            return ConstNode(False)       # nothing is that large
        if hi is not None and hi < _I32_MIN:
            return ConstNode(False)
        if lo is not None and lo < _I32_MIN:
            lo = None                      # everything passes the lower bound
        if hi is not None and hi > _I32_MAX:
            hi = None
        if lo is None and hi is None:
            return ConstNode(True)
        return NumericCmpNode(colname, lo, hi, flt.lower_strict,
                              flt.upper_strict, np.int32)
    raise ValueError(f"cannot plan filter {type(flt).__name__} on numeric column")


# ---------------------------------------------------------------------------
# Host bitmap-index path (reference: Filter.getBitmapIndex)
# ---------------------------------------------------------------------------

def can_use_bitmap(flt: F.DimFilter, segment: Segment) -> bool:
    if isinstance(flt, (F.TrueFilter, F.FalseFilter)):
        return True
    if isinstance(flt, (F.AndFilter, F.OrFilter)):
        return all(can_use_bitmap(f, segment) for f in flt.fields)
    if isinstance(flt, F.NotFilter):
        return can_use_bitmap(flt.field, segment)
    dim = getattr(flt, "dimension", None)
    return dim in segment.dims and _string_predicate(flt) is not None


def bitmap_of(flt: F.DimFilter, segment: Segment) -> AnyBitmap:
    """Evaluate an indexable filter purely via bitmap algebra. Results are
    density-adaptive (data/bitmap.py): low-density operands stay sparse id
    lists through AND/OR/XOR — a SparseBitmap is never densified except by
    complement, whose result is inherently dense."""
    n = segment.n_rows
    if isinstance(flt, F.TrueFilter):
        return Bitmap.full(n)
    if isinstance(flt, F.FalseFilter):
        return SparseBitmap(np.zeros(0, dtype=np.int32), n)
    if isinstance(flt, F.AndFilter):
        parts = [bitmap_of(f, segment) for f in flt.fields]
        return functools.reduce(bitmap_and, parts) if parts \
            else Bitmap.full(n)
    if isinstance(flt, F.OrFilter):
        parts = [bitmap_of(f, segment) for f in flt.fields]
        return functools.reduce(bitmap_or, parts) if parts \
            else SparseBitmap(np.zeros(0, dtype=np.int32), n)
    if isinstance(flt, F.NotFilter):
        return ~bitmap_of(flt.field, segment)
    dim = flt.dimension
    col = segment.dims[dim]
    pred = _string_predicate(flt)
    lut = _dictionary_lut(col.dictionary, pred)
    matching = np.flatnonzero(lut)
    index = col.bitmap_index()
    return index.union_of(matching)


def filter_cardinality(flt: F.DimFilter, segment: Segment) -> int:
    """EXACT matching-row count of a bitmap-eligible filter. NOT computes
    as n - |child| — the complement bitmap is never materialized, so
    NOT-of-sparse costs the sparse child only."""
    n = segment.n_rows
    if isinstance(flt, F.TrueFilter):
        return n
    if isinstance(flt, F.FalseFilter):
        return 0
    if isinstance(flt, F.NotFilter):
        return n - filter_cardinality(flt.field, segment)
    return bitmap_of(flt, segment).cardinality()


def estimate_selectivity(flt: Optional[F.DimFilter], segment: Segment) -> float:
    """Fraction of rows expected to match (reference:
    Filter.estimateSelectivity); exact when bitmap-indexable."""
    if flt is None:
        return 1.0
    if segment.n_rows == 0:
        return 0.0
    if can_use_bitmap(flt, segment):
        return filter_cardinality(flt, segment) / segment.n_rows
    return 1.0


# ---------------------------------------------------------------------------
# Device bitmap staging + the filter-result cache
# ---------------------------------------------------------------------------

class FilterBitmapStats:
    """Filter-cache counters behind query/filter/* (FilterBitmapMonitor).
    hits/misses count RESULT-words pool probes (a hit skips leaf staging
    and the algebra fill entirely); built_bytes are the device bitmap bytes
    materialized on misses."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.built_bytes = 0

    def record(self, hit: bool, nbytes: int = 0) -> None:
        with self._lock:
            if hit:
                self.hits += 1
            else:
                self.misses += 1
                self.built_bytes += nbytes

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "builtBytes": self.built_bytes}

    @property
    def hit_rate(self) -> float:
        with self._lock:
            total = self.hits + self.misses
            return self.hits / total if total else 0.0


_FBMP_STATS = FilterBitmapStats()


def filter_bitmap_stats() -> FilterBitmapStats:
    return _FBMP_STATS


class FilterBitmapMonitor(Monitor):
    """Emits query/filter/{deviceBitmapHits,deviceBitmapMisses,bytes} per
    tick (deltas over the tick window, the DevicePoolMonitor discipline)."""

    def __init__(self, source: Optional[FilterBitmapStats] = None):
        self.source = source or _FBMP_STATS
        self._last = self.source.snapshot()

    def do_monitor(self, emitter):
        s = self.source.snapshot()
        last, self._last = self._last, s
        emitter.metric("query/filter/deviceBitmapHits",
                       s["hits"] - last["hits"])
        emitter.metric("query/filter/deviceBitmapMisses",
                       s["misses"] - last["misses"])
        emitter.metric("query/filter/bytes",
                       s["builtBytes"] - last["builtBytes"])


# Jitted fill programs, keyed on a wave's LAYOUT (per filter structure: its
# slot rung and which leaf blocks ship — `_pack_wave`) and the row count:
# LRU-bounded + locked like grouping._JIT_CACHE (broker thread-pool
# fan-out). The key is a closed set: no leaf's size and no count of cold
# segments is in it, so a dashboard's sliding window meets the programs its
# first cold waves built.
_FBMP_JIT_CACHE: "collections.OrderedDict[Tuple, object]" = \
    collections.OrderedDict()
_FBMP_JIT_CACHE_CAP = 64
_FBMP_JIT_CACHE_LOCK = threading.Lock()

#: what `words_span` reports of the waves staged inside it, per thread:
#: cold pairs, `jax.device_put` calls, bytes of the packed buffers
_WAVE_TALLY = threading.local()


def _wave_tally() -> Tuple[int, int, int]:
    return getattr(_WAVE_TALLY, "n", (0, 0, 0))


def combine_structure_words(structure, leaf_words, const_words):
    """THE word-domain algebra evaluator: AND/OR/NOT/XOR over whatever
    `leaf_words(index)` / `const_words(bool)` return. Shared by the fill
    program below AND the megakernel's inline path
    (engine/megakernel.MegaBitmapNode.words_traced), so the staged and
    fused paths cannot drift — their bit-parity contract is structural."""
    def ev(node):
        op = node[0]
        if op == "leaf":
            return leaf_words(node[1])
        if op == "const":
            return const_words(node[1])
        if op == "not":
            return ~ev(node[1])
        kids = [ev(c) for c in node[1]]
        out = kids[0]
        for k in kids[1:]:
            out = (out & k) if op == "and" else \
                (out | k) if op == "or" else (out ^ k)
        return out

    return ev(structure)


def run_leaf_width(padded_rows: int) -> int:
    """THE run-table length of a `runs` leaf at this row count (the run
    limit `_run_leaf_payload` accepts, plus its sentinel run, on a pow2)."""
    from druid_tpu.data.cascade import pad_pow2
    return pad_pow2(padded_rows // SPARSE_LEAF_SHARE + 1)


def _block_width(kind: str, padded_rows: int) -> int:
    """int32 elements one leaf takes in a block of `kind`."""
    if kind == "sparse":
        return sparse_leaf_width(padded_rows)
    if kind == "dense":
        return padded_rows // 32
    return 2 * run_leaf_width(padded_rows)


def _block_words(kind: str, block, padded_rows: int):
    """Traced: one block of a wave's buffer (int32 [K, width], a leaf a
    row) as uint32 words [K, Rw]. Sparse rows are sorted id lists padded
    with `padded_rows`, scattered into words in ONE scatter for the block —
    distinct ids set distinct bits, so scatter-add IS bitwise-or, and the
    padding drops out of bounds; dense rows are the words themselves; run
    rows are (EXCLUSIVE run end, per-run match) tables with a 2^31-1-end,
    match-0 sentinel run, expanded to rows by a searchsorted over the ends
    (data/cascade.py run tables: 8 bytes a run instead of a bit a row)."""
    import jax
    import jax.numpy as jnp
    K, Rw = block.shape[0], padded_rows // 32
    if kind == "dense":
        return jax.lax.bitcast_convert_type(block, jnp.uint32)
    if kind == "sparse":
        bit = jnp.uint32(1) << (block & 31).astype(jnp.uint32)
        rows = jnp.arange(K, dtype=jnp.int32)[:, None]
        return jnp.zeros((K, Rw), jnp.uint32).at[rows, block >> 5].add(
            bit, mode="drop")
    iota = jnp.arange(padded_rows, dtype=jnp.int32)

    def expand(table):
        ends, match = table[:, 0], table[:, 1]
        idx = jnp.clip(jnp.searchsorted(ends, iota, side="right"),
                       0, ends.shape[0] - 1)
        bits = (match[idx] > 0).astype(jnp.uint32).reshape(-1, 32)
        w = bits[:, 0]
        for s in range(1, 32):
            w = w | (bits[:, s] << jnp.uint32(s))
        return w

    # a slot at a time: batched, the search's temporaries are K times a
    # slot's (3 GB at 64 slots of 262,144 rows, compiled for a v5e)
    return jax.lax.map(expand, block.reshape(K, -1, 2))


def _build_fill_wave(layout: Tuple, padded_rows: int):
    """THE fill program: a wave's packed buffer (`_pack_wave`) in, its
    words out — `K` uint32 [Rw] arrays a `(structure, K, blocks)` entry of
    `layout`, in order, padding slots included (the caller drops them).
    A leaf position's words are the OR of the blocks it ships; AND / OR /
    NOT / XOR then combine word-wise over the [K, Rw] planes. Nothing in it
    is unrolled over K: it costs the same to trace at 64 slots as at 1."""
    import jax
    import jax.numpy as jnp
    Rw = padded_rows // 32

    def fn(buf):
        out, off = [], 0
        for structure, K, blocks in layout:
            leaves = []
            for kinds in blocks:
                words = None
                for kind in kinds:
                    n = K * _block_width(kind, padded_rows)
                    w = _block_words(kind, buf[off:off + n].reshape(K, -1),
                                     padded_rows)
                    words = w if words is None else words | w
                    off += n
                leaves.append(words)

            def const_words(value, K=K):
                fill = np.uint32(0xFFFFFFFF) if value else np.uint32(0)
                return jnp.full((K, Rw), fill, jnp.uint32)

            combined = combine_structure_words(
                structure, leaves.__getitem__, const_words)
            out.extend(combined[i] for i in range(K))
        return tuple(out)

    return jax.jit(named_program(fn, "bitmap_fill_wave"))


def _leaf_digest(lut: np.ndarray) -> str:
    return hashlib.sha1(lut.tobytes()).hexdigest()[:16]


def _permuted_bitmap(segment: Segment, bm: AnyBitmap,
                     perm: np.ndarray, perm_key) -> AnyBitmap:
    """Reorder a row bitmap into a permuted (projection) row layout. Sparse
    bitmaps stay sparse: ids map through the cached inverse permutation."""
    if isinstance(bm, SparseBitmap):
        inv = segment.aux_cached(
            ("perm_inv", perm_digest(perm_key)),
            lambda: np.argsort(perm, kind="stable").astype(np.int32))
        return SparseBitmap(np.sort(inv[bm.ids]), bm.n_rows)
    return Bitmap.from_bool(bm.to_bool()[perm])


def _run_leaf_payload(segment: Segment, dim: str, lut: np.ndarray,
                      padded_rows: int) -> Optional[np.ndarray]:
    """RLE-run-aware leaf payload: int32 [run_leaf_width, 2] of (EXCLUSIVE
    run end — start-of-next-run index — and per-run match) when `dim` is
    run-compressible enough that the run table undercuts both bitmap
    representations (data/cascade.py run info), else None. The match bit
    is decided ONCE PER RUN (one LUT gather over run values) instead of
    once per row; 2^31-1-end sentinel runs cover padding rows with match
    0."""
    from druid_tpu.data import cascade as cascade_mod
    if not cascade_mod.enabled():
        return None
    # beat the dense words (padded_rows/32 uint32) with clear margin
    info = cascade_mod.column_run_info(
        segment, dim, max_runs=padded_rows // SPARSE_LEAF_SHARE)
    if info is None:
        return None
    values, ends, nr = info
    payload = np.zeros((run_leaf_width(padded_rows), 2), dtype=np.int32)
    payload[:, 0] = 2**31 - 1            # sentinel tail (match 0)
    payload[:nr, 0] = ends
    payload[:nr, 1] = lut[values]
    return payload


def _leaf_bitmap(segment: Segment, dim: str, lut: np.ndarray,
                 perm: Optional[np.ndarray], perm_key) -> AnyBitmap:
    """One leaf's row bitmap from the host index; `perm` reorders rows
    into a projection layout."""
    bm = segment.dims[dim].bitmap_index().union_of(np.flatnonzero(lut))
    if perm is not None:
        bm = _permuted_bitmap(segment, bm, perm, perm_key)
    return bm


def _block(K: int, kind: str, padded_rows: int, slots: List[int],
           rows: np.ndarray) -> np.ndarray:
    """One [K, width] block of a wave's buffer, flat: `rows` at `slots`,
    every other slot what fills to zero words."""
    if len(slots) == K:                  # every slot cold: no fill, no copy
        return rows.view(np.int32).reshape(-1)
    block = np.zeros((K, _block_width(kind, padded_rows)), dtype=np.int32)
    if kind == "sparse":
        block[:] = padded_rows
    elif kind == "runs":
        block[:, 0::2] = 2**31 - 1
    block[slots] = rows.view(np.int32).reshape(len(slots), -1)
    return block.reshape(-1)


def _pack_wave(pairs: Sequence[Tuple[Segment, "DeviceBitmapNode"]],
               padded_rows: int, census: Optional[Dict] = None,
               perm: Optional[np.ndarray] = None, perm_key=None
               ) -> Tuple[Tuple, np.ndarray, List[int]]:
    """A wave's cold (segment, node) pairs as ONE host buffer: (layout,
    int32 buffer, output index of every pair). Pairs group by structure;
    a group takes `K` slots — `census[structure]`, how many nodes of that
    structure the WAVE holds, resident or not, on a pow2 — so how many of
    a chunk's segments happen to be cold picks no program. A leaf position
    ships one [K, width] block a kind its leaves take: run tables where the
    dim is run-compressed (original row order only), and for the rest
    sparse ids when every bitmap fits the one width, else words
    (`bitmap.leaf_rows`, which converts the position's bitmaps together)."""
    groups: Dict[Tuple, List[int]] = {}
    for p, (_, node) in enumerate(pairs):
        groups.setdefault(node.structure, []).append(p)
    layout, parts, index = [], [], [0] * len(pairs)
    slot0 = 0
    for structure in sorted(groups, key=repr):
        members = groups[structure]
        K = 1
        while K < max(len(members), (census or {}).get(structure, 0)):
            K <<= 1
        blocks = []
        for j in range(len(pairs[members[0]][1].leaves)):
            tables: Dict[int, np.ndarray] = {}      # by slot
            bitmaps: Dict[int, AnyBitmap] = {}
            for slot, p in enumerate(members):
                segment, node = pairs[p]
                dim, lut = node.leaves[j]
                table = None if perm is not None else \
                    _run_leaf_payload(segment, dim, lut, padded_rows)
                if table is not None:
                    tables[slot] = table
                else:
                    bitmaps[slot] = _leaf_bitmap(segment, dim, lut, perm,
                                                 perm_key)
            kinds = []
            if bitmaps:
                kind, rows = leaf_rows(list(bitmaps.values()), padded_rows)
                kinds.append(kind)
                parts.append(_block(K, kind, padded_rows, list(bitmaps),
                                    rows))
            if tables:
                kinds.append("runs")
                parts.append(_block(K, "runs", padded_rows, list(tables),
                                    np.stack(list(tables.values()))))
            blocks.append(tuple(kinds))
        for slot, p in enumerate(members):
            index[p] = slot0 + slot
        slot0 += K
        layout.append((structure, K, tuple(blocks)))
    buf = parts[0] if len(parts) == 1 else \
        np.concatenate(parts) if parts else np.zeros(0, dtype=np.int32)
    return tuple(layout), buf, index


def _fill_wave(pairs: Sequence[Tuple[Segment, "DeviceBitmapNode"]],
               padded_rows: int, census: Optional[Dict] = None,
               perm: Optional[np.ndarray] = None, perm_key=None) -> List:
    """The words of a wave's cold pairs, in order: ONE packed buffer, ONE
    `jax.device_put`, ONE dispatch, whatever the pairs' count and kinds."""
    import jax

    from druid_tpu.obs import dispatch as dispatch_mod
    layout, buf, index = _pack_wave(pairs, padded_rows, census, perm,
                                    perm_key)
    jkey = (layout, padded_rows)
    with _FBMP_JIT_CACHE_LOCK:
        fn = _FBMP_JIT_CACHE.get(jkey)
        # the miss IS the compile event (jit traces/compiles on the first
        # call below), as at the aggregation programs' cache sites
        compiled = fn is None
        if fn is None:
            fn = _build_fill_wave(layout, padded_rows)
            _FBMP_JIT_CACHE[jkey] = fn
            while len(_FBMP_JIT_CACHE) > _FBMP_JIT_CACHE_CAP:
                _FBMP_JIT_CACHE.popitem(last=False)
        else:
            _FBMP_JIT_CACHE.move_to_end(jkey)
    pending, handovers, leaf_bytes = _wave_tally()
    _WAVE_TALLY.n = (pending + len(pairs), handovers + 1,
                     leaf_bytes + buf.nbytes)
    with trace_span_when(compiled, "engine/compile", kind="filterFill"):
        words = fn(jax.device_put(buf))
    dispatch_mod.record("filterFill")    # successful dispatches only
    return [words[i] for i in index]


def _item_nodes(filter_node: Optional[FilterNode],
                kernels: Sequence) -> List[DeviceBitmapNode]:
    """One item's stageable nodes: the query filter's plus every filtered
    aggregator's (kernels plan bitmap words too — AggKernel.filter_trees)."""
    nodes = collect_bitmap_nodes(filter_node)
    for k in kernels:
        for tree in k.filter_trees():
            nodes.extend(collect_bitmap_nodes(tree))
    return nodes


@contextlib.contextmanager
def words_span(**attrs):
    """The `engine/filter/words` span every execution path opens around
    its staging of filter words (and grouping around the megakernel
    conversion that decides WHICH words stage). `built` = pool entries
    this thread built inside it rather than found resident; `pending` =
    cold (segment, filter) pairs its waves filled, `handovers` = the
    `jax.device_put` calls that took them to the device (one a wave with a
    cold pair, whatever `pending` is), `leafBytes` = bytes those carried."""
    with trace_span("engine/filter/words", **attrs) as sp:
        built0, tally0 = thread_builds(), _wave_tally()
        yield
        if sp is not None:
            sp.attrs["built"] = thread_builds() - built0
            for name, now, was in zip(("pending", "handovers", "leafBytes"),
                                      _wave_tally(), tally0):
                sp.attrs[name] = now - was


def stage_device_bitmaps_multi(items: Sequence[Tuple],
                               padded_rows: int) -> List[Dict[str, object]]:
    """Resident filter-bitmap words for a whole staging wave: one
    {node.col: uint32 words [padded_rows/32]} dict per item, to merge into
    each slot's arrays. Items are (segment, filter_node) or (segment,
    filter_node, kernels) — filtered aggregators' trees stage exactly like
    the query filter's. Results live in the byte-budgeted device pool
    keyed (filter structural signature, aux digest, padded rows,
    permutation digest) per segment — warm probes skip leaf
    materialization AND the algebra (query/filter/deviceBitmapHits); ALL
    of the wave's cold misses cross to the device as one buffer and fill
    in a single batched dispatch (`_fill_wave`)."""
    out: List[Dict[str, object]] = [{} for _ in items]
    pending = []          # (slot, segment, node, pool key)
    # identical (segment, key) pairs within one wave — N fused copies of
    # the same dashboard query — build ONCE and fan out (the duplicates
    # count as hits: they are served without leaf work or algebra)
    wave_dups: Dict[Tuple, List[Tuple[int, str]]] = {}
    census: Dict[Tuple, int] = {}       # the wave's nodes by structure
    for i, item in enumerate(items):
        segment, filter_node = item[0], item[1]
        kernels = item[2] if len(item) > 2 else ()
        for node in _item_nodes(filter_node, kernels):
            census[node.structure] = census.get(node.structure, 0) + 1
            key = bitmap_pool_key(node, padded_rows)
            wkey = (id(segment), key)
            if wkey in wave_dups:
                _FBMP_STATS.record(True)
                wave_dups[wkey].append((i, node.col))
                continue
            hit = segment.device_contains(key)
            _FBMP_STATS.record(hit, 0 if hit else padded_rows // 8)
            if hit:
                # the build lambda never runs on a hit; a racing eviction
                # just lands this entry in the cold wave's semantics
                out[i][node.col] = segment.device_cached(
                    key, lambda s=segment, n=node: _fill_single(
                        s, n, padded_rows))
            else:
                wave_dups[wkey] = []
                pending.append((i, segment, node, key))
    if not pending:
        return out

    words_per = _fill_wave([(segment, node) for _, segment, node, _
                            in pending], padded_rows, census)
    for (i, segment, node, key), words in zip(pending, words_per):
        resident = segment.device_cached(key, lambda w=words: w)
        out[i][node.col] = resident
        for j, col in wave_dups.get((id(segment), key), ()):
            out[j][col] = resident
    return out


def _fill_single(segment: Segment, node: DeviceBitmapNode,
                 padded_rows: int, perm: Optional[np.ndarray] = None,
                 perm_key=None):
    """One (segment, filter) fill — the pool-miss build path when a probe
    said hit but the entry was evicted before device_cached re-read it,
    and the permuted-layout (projection) staging path: the wave of one
    pair."""
    return _fill_wave([(segment, node)], padded_rows, perm=perm,
                      perm_key=perm_key)[0]


def stage_device_bitmaps(segment: Segment,
                         filter_node: Optional[FilterNode],
                         padded_rows: int, kernels: Sequence = (),
                         perm: Optional[np.ndarray] = None,
                         perm_key=None) -> Dict[str, object]:
    """Single-segment staging. Without a permutation this is the wave path
    for one item; with one (the projection layout), every node stages
    PERMUTED words under its own (key, permutation digest) pool entries —
    the projection path hits its cache instead of falling back to the
    column path."""
    if perm is None:
        return stage_device_bitmaps_multi(
            [(segment, filter_node, kernels)], padded_rows)[0]
    pdg = perm_digest(perm_key)
    out: Dict[str, object] = {}
    for node in _item_nodes(filter_node, kernels):
        key = bitmap_pool_key(node, padded_rows, pdg)
        hit = segment.device_contains(key)
        _FBMP_STATS.record(hit, 0 if hit else padded_rows // 8)
        out[node.col] = segment.device_cached(
            key, lambda s=segment, n=node: _fill_single(
                s, n, padded_rows, perm=perm, perm_key=perm_key))
    return out


# ---------------------------------------------------------------------------
# Row-level evaluation (having specs over result rows)
# ---------------------------------------------------------------------------

def evaluate_filter_on_row(flt: F.DimFilter, row: Dict[str, object]) -> bool:
    if isinstance(flt, F.TrueFilter):
        return True
    if isinstance(flt, F.FalseFilter):
        return False
    if isinstance(flt, F.AndFilter):
        return all(evaluate_filter_on_row(f, row) for f in flt.fields)
    if isinstance(flt, F.OrFilter):
        return any(evaluate_filter_on_row(f, row) for f in flt.fields)
    if isinstance(flt, F.NotFilter):
        return not evaluate_filter_on_row(flt.field, row)
    pred = _string_predicate(flt)
    if pred is None:
        raise ValueError(f"cannot row-evaluate {flt!r}")
    v = row.get(flt.dimension)
    return pred("" if v is None else str(v))


# ---------------------------------------------------------------------------
# Host-side full mask evaluation (scan / search / timeBoundary paths)
# ---------------------------------------------------------------------------

def _bind_string_dims(expr, segment: Segment, bindings: Dict) -> None:
    """Bind every string dim an expression references as a DECODED value
    array — host-path numpy string comparison matches the reference's
    lexicographic semantics directly."""
    for c in expr.required_columns():
        if c in segment.dims and c not in bindings:
            col = segment.dims[c]
            vals = np.asarray(list(col.dictionary.values), dtype=object)
            # bindings is a per-call accumulator scoped to ONE segment —
            # the caller builds it fresh for each host_mask evaluation
            bindings[c] = vals[col.ids]  # druidlint: disable=unkeyed-trace-input


def host_mask(flt: Optional[F.DimFilter], segment: Segment,
              virtual_columns: Sequence = ()) -> np.ndarray:
    """Evaluate a filter to a host boolean row mask with vectorized numpy —
    used by the row-export engines (scan/select), search, and timeBoundary,
    where results are host-side anyway."""
    n = segment.n_rows
    if flt is None:
        return np.ones(n, dtype=bool)
    flt = flt.optimize()
    vc_arrays = {}
    if virtual_columns:
        bindings = {"__time": segment.time_ms}
        for name, m in segment.metrics.items():
            bindings[name] = m.values
        for v in virtual_columns:
            expr = parse_expression(v.expression)
            _bind_string_dims(expr, segment, bindings)
            arr = expr.evaluate(bindings)
            vc_arrays[v.name] = np.broadcast_to(np.asarray(arr), (n,))
            bindings[v.name] = vc_arrays[v.name]
    return _host_mask(flt, segment, vc_arrays)


def _host_mask(flt: F.DimFilter, segment: Segment,
               vc_arrays: Optional[Dict[str, np.ndarray]] = None) -> np.ndarray:
    vc_arrays = vc_arrays or {}
    n = segment.n_rows
    if isinstance(flt, F.TrueFilter):
        return np.ones(n, dtype=bool)
    if isinstance(flt, F.FalseFilter):
        return np.zeros(n, dtype=bool)
    if isinstance(flt, F.AndFilter):
        out = np.ones(n, dtype=bool)
        for f in flt.fields:
            out &= _host_mask(f, segment, vc_arrays)
        return out
    if isinstance(flt, F.OrFilter):
        out = np.zeros(n, dtype=bool)
        for f in flt.fields:
            out |= _host_mask(f, segment, vc_arrays)
        return out
    if isinstance(flt, F.NotFilter):
        return ~_host_mask(flt.field, segment, vc_arrays)
    if isinstance(flt, F.IntervalFilter):
        t = segment.time_ms
        out = np.zeros(n, dtype=bool)
        for iv in flt.intervals:
            out |= (t >= iv.start) & (t < iv.end)
        return out
    if isinstance(flt, F.ColumnComparisonFilter):
        dicts = [segment.dims[d].dictionary for d in flt.dimensions]
        _, remaps = merge_dictionaries(dicts)
        first = remaps[0][segment.dims[flt.dimensions[0]].ids]
        out = np.ones(n, dtype=bool)
        for d, remap in zip(flt.dimensions[1:], remaps[1:]):
            out &= first == remap[segment.dims[d].ids]
        return out
    if isinstance(flt, F.ExpressionFilter):
        expr = parse_expression(flt.expression)
        bindings = {"__time": segment.time_ms}
        for name, m in segment.metrics.items():
            bindings[name] = m.values
        _bind_string_dims(expr, segment, bindings)
        bindings.update(vc_arrays)
        out = expr.evaluate(bindings)
        return np.broadcast_to(np.asarray(out, dtype=bool), (n,)).copy()

    dim = getattr(flt, "dimension", None)
    if dim in segment.dims:
        col = segment.dims[dim]
        pred = _string_predicate(flt)
        lut = _dictionary_lut(col.dictionary, pred)
        return lut[col.ids]
    if dim == "__time" or dim in segment.metrics or dim in vc_arrays:
        if dim == "__time":
            vals = segment.time_ms
        elif dim in segment.metrics:
            vals = segment.metrics[dim].values
        else:
            vals = vc_arrays[dim]
        conv = int if (dim == "__time"
                       or (dim in segment.metrics
                           and segment.metrics[dim].type == ValueType.LONG)
                       or (dim in vc_arrays
                           and np.issubdtype(vals.dtype, np.integer))) else float
        if isinstance(flt, F.SelectorFilter):
            if flt.value is None:
                return np.zeros(n, dtype=bool)
            return vals == conv(flt.value)
        if isinstance(flt, F.InFilter):
            targets = np.asarray([conv(v) for v in flt.values if v is not None])
            return np.isin(vals, targets)
        if isinstance(flt, F.BoundFilter):
            out = np.ones(n, dtype=bool)
            if flt.lower is not None:
                lo = conv(flt.lower)
                out &= (vals > lo) if flt.lower_strict else (vals >= lo)
            if flt.upper is not None:
                hi = conv(flt.upper)
                out &= (vals < hi) if flt.upper_strict else (vals <= hi)
            return out
        raise ValueError(f"cannot host-evaluate {type(flt).__name__} on numeric")
    # missing column
    if isinstance(flt, F.SelectorFilter) and (flt.value is None or flt.value == ""):
        return np.ones(n, dtype=bool)
    return np.zeros(n, dtype=bool)


def simplify_node(node: Optional[FilterNode]) -> Optional[FilterNode]:
    """Fold ConstNodes out of a planned tree. Returns None (no filter),
    a ConstNode(False) root (caller short-circuits without a device call —
    constant-mask programs also crash some TPU compiler backends), or a
    const-free tree."""
    if node is None:
        return None
    node = _simplify(node)
    if isinstance(node, ConstNode) and node.value:
        return None
    return node


def _simplify(node: FilterNode) -> FilterNode:
    if isinstance(node, AndNode):
        kids = []
        for c in node.children:
            c = _simplify(c)
            if isinstance(c, ConstNode):
                if not c.value:
                    return ConstNode(False)
                continue
            kids.append(c)
        if not kids:
            return ConstNode(True)
        return kids[0] if len(kids) == 1 else AndNode(kids)
    if isinstance(node, OrNode):
        kids = []
        for c in node.children:
            c = _simplify(c)
            if isinstance(c, ConstNode):
                if c.value:
                    return ConstNode(True)
                continue
            kids.append(c)
        if not kids:
            return ConstNode(False)
        return kids[0] if len(kids) == 1 else OrNode(kids)
    if isinstance(node, NotNode):
        c = _simplify(node.child)
        if isinstance(c, ConstNode):
            return ConstNode(not c.value)
        return NotNode(c)
    return node


# ---------------------------------------------------------------------------
# Row-level evaluation (host): used by ingest-time TransformSpec filters and
# having specs — the analog of the reference's ValueMatcher path
# (query/filter/ValueMatcher.java) for rows that are not yet columnar.
# ---------------------------------------------------------------------------

def make_row_matcher(flt: F.DimFilter):
    """Compile a DimFilter into row(dict)->bool over raw (pre-dictionary)
    values. Dims are strings (None ≡ ""), metrics numeric, __time millis."""
    if isinstance(flt, F.TrueFilter):
        return lambda row: True
    if isinstance(flt, F.FalseFilter):
        return lambda row: False
    if isinstance(flt, F.AndFilter):
        subs = [make_row_matcher(f) for f in flt.fields]
        return lambda row: all(m(row) for m in subs)
    if isinstance(flt, F.OrFilter):
        subs = [make_row_matcher(f) for f in flt.fields]
        return lambda row: any(m(row) for m in subs)
    if isinstance(flt, F.NotFilter):
        sub = make_row_matcher(flt.field)
        return lambda row: not sub(row)
    if isinstance(flt, F.IntervalFilter):
        ivs = flt.intervals
        col = flt.dimension

        def iv_match(row):
            v = row.get(col)
            if v is None:
                return False
            try:
                ms = int(float(v))
            except (TypeError, ValueError):
                return False
            return any(iv.contains(ms) for iv in ivs)
        return iv_match
    if isinstance(flt, F.ColumnComparisonFilter):
        dims = flt.dimensions

        def cc_match(row):
            vals = [("" if row.get(d) is None else str(row.get(d)))
                    for d in dims]
            return all(v == vals[0] for v in vals)
        return cc_match
    if isinstance(flt, F.ExpressionFilter):
        expr = parse_expression(flt.expression)

        def ex_match(row):
            # None ≡ "" — the same null contract as every other row matcher.
            # A numeric expr over a null-bound column raises (e.g. "" > 2);
            # such rows simply don't match, as in the reference.
            try:
                out = expr.evaluate({k: ("" if v is None else v)
                                     for k, v in row.items()})
            except (TypeError, ValueError):
                return False
            try:
                return bool(float(out))
            except (TypeError, ValueError):
                return bool(out)
        return ex_match
    pred = _string_predicate(flt)
    if pred is not None:
        dim = flt.dimension

        def s_match(row):
            v = row.get(dim)
            return pred("" if v is None else str(v))
        return s_match
    raise ValueError(f"cannot row-match filter {type(flt).__name__}")
