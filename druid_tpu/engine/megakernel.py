"""One-dispatch megakernel: fused bitmap filter + packed decode + aggregation.

PRs 9-10 made value columns resident as bit-packed words and filter
bitmaps resident as packed words — but a COLD query still paid up to three
device dispatches: the bitmap-algebra fill wave (engine/filters.py
`_eval_structure`), then the aggregation program (packed decode at the
program top + reduce). This module closes ROADMAP item 4: the whole query
becomes ONE device program, following the decompress-inside-the-operator
design of *GPU Acceleration of SQL Analytics on Compressed Data* and the
accelerator-serving framing of *Tailwind* (PAPERS.md).

The fused path, per bitmap-eligible filter subtree:

  * `megaize` replaces each planned DeviceBitmapNode whose COMBINED words
    are not already pool-resident with a MegaBitmapNode: its per-leaf row
    bitmaps stage as resident words (1 bit/row, the width-1 instance of
    the data/packed.py tile-planar layout) and the AND/OR/NOT/XOR word
    algebra evaluates INLINE in the one traced program — no fill dispatch,
    no combined-words materialization in HBM. Hot dashboards whose
    combined words ARE resident keep the cached bit-test path (also one
    dispatch); the megakernel is the one-shot/cold-query story.
  * On the pallas (sorted-projection) strategy, `mega_reduce` runs the
    fused aggregation kernel: packed value columns arrive AS WORDS and
    unpack per VMEM tile (engine/pallas_agg.py discipline), and the row
    mask arrives AS WORDS too — the interval/validity mask packs to words
    in-program, ANDs with the filter word algebra, and the kernel performs
    a sub-lane unpack per block (one word row out of a resident word tile
    instead of an (R, 128) int32 row mask — ~32x less mask VMEM traffic).
    No decoded column and no row-width mask ever hits HBM.
  * Per-group partial buffers DONATE across executions (`donate_argnums`,
    the pjit plumbing of SNIPPETS.md [1]/[2]): the raw accumulator grids
    of one run park in the device pool and are handed back — donated — to
    the next run of the same (segment, program) pair, so standing/repeated
    queries driven by the scheduler's flush loop (PR 7) update partials in
    place with zero per-tick HBM churn. The kernel re-initializes the
    grids at grid step 0, so donated reuse is bit-identical to fresh
    zero buffers (the donation-aliasing parity contract).

Parity discipline (PR 9): the fused path is bit-identical to the staged
path — the mask BITS are exactly the staged algebra's, and the kernel's
block/accumulation order is pallas_agg's, so counts/int sums match
bitwise and float sums reduce in the same order.

Opt-out: `DRUID_TPU_MEGAKERNEL=0` (or set_enabled(False)) keeps the
staged fill-wave + resident-combined-words path everywhere.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from druid_tpu.engine import pallas_agg
from druid_tpu.engine.contracts import (BLK_SMALL_W, MEGA_MASK_ROW_ALIGN,
                                        MEGA_MASK_TILE_ALIGN, MEGA_MASK_VPW,
                                        MEGA_MASK_WIDTH, donation_supported)
from druid_tpu.engine.filters import (AndNode, DeviceBitmapNode, FilterNode,
                                      NotNode, OrNode, _leaf_digest,
                                      bitmap_pool_key, collect_bitmap_nodes,
                                      perm_digest)
from druid_tpu.utils.emitter import Monitor

#: process default; opt-out via DRUID_TPU_MEGAKERNEL=0 or set_enabled(False)
_ENABLED = os.environ.get("DRUID_TPU_MEGAKERNEL", "1").lower() \
    not in ("0", "false", "no")
#: tests force donation on (CPU ignores donation silently) or off
_FORCE_DONATE: Optional[bool] = None
#: tests force the carry take/park handoff without real donation (CPU)
_FORCE_CARRY: Optional[bool] = None
_STATE_LOCK = threading.Lock()


def set_enabled(on: bool) -> bool:
    """Flip the process-wide megakernel default; returns the previous value
    (bench/test toggle, the batching/packed.set_enabled discipline)."""
    global _ENABLED
    with _STATE_LOCK:
        prev = _ENABLED
        _ENABLED = bool(on)
        return prev


def enabled() -> bool:
    return _ENABLED


def set_force_donate(on: Optional[bool]) -> Optional[bool]:
    """Override donation support detection (None = autodetect). Forcing
    donation ON where the backend does not support it (CPU) is undefined
    behavior — this hook exists for accelerator-run experiments only."""
    global _FORCE_DONATE
    with _STATE_LOCK:
        prev = _FORCE_DONATE
        _FORCE_DONATE = on
        return prev


def donation_enabled() -> bool:
    """Whether the fused program donates its carry buffers. The platform
    decision lives in ONE place — contracts.donation_supported (tri-state
    DRUID_TPU_DONATE, backend autodetect) — so every donation-enable path
    routes through the shared gate donorguard's donate-platform-gate
    rule recognizes; this function only layers the test override on top."""
    if _FORCE_DONATE is not None:
        return _FORCE_DONATE
    return donation_supported()


def set_force_carry(on: Optional[bool]) -> Optional[bool]:
    """Override carry_enabled detection (None = follow donation). Lets CPU
    tests exercise the take/park handoff and its fresh-vs-carried parity
    without real donation."""
    global _FORCE_CARRY
    with _STATE_LOCK:
        prev = _FORCE_CARRY
        _FORCE_CARRY = on
        return prev


def carry_enabled() -> bool:
    """Whether executions pool-park their raw grids and ride them back as
    carries. Without donation the parked grids would only consume pool
    budget (the buffers are never aliased into outputs), so the handoff
    follows donation support by default."""
    if _FORCE_CARRY is not None:
        return _FORCE_CARRY
    return donation_enabled()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Stats (query/megakernel/* metrics)
# ---------------------------------------------------------------------------

class MegaStats:
    """hits = bitmap subtrees fused inline; fallbacks = bitmap subtrees
    that did NOT fuse (megakernel disabled, or resident combined words
    already serve them); donated_bytes = carry-buffer bytes handed back
    donated across executions."""

    def __init__(self):
        self._lock = threading.Lock()
        self.hits = 0
        self.fallbacks = 0
        self.donated_bytes = 0

    def record_hit(self, n: int = 1) -> None:
        with self._lock:
            self.hits += n

    def record_fallback(self, n: int = 1) -> None:
        with self._lock:
            self.fallbacks += n

    def record_donated(self, nbytes: int) -> None:
        with self._lock:
            self.donated_bytes += nbytes

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "fallbacks": self.fallbacks,
                    "donatedBytes": self.donated_bytes}


_STATS = MegaStats()


def stats() -> MegaStats:
    return _STATS


class MegakernelMonitor(Monitor):
    """Emits query/megakernel/{hits,fallbacks,donatedBytes} per tick
    (deltas over the tick window, the FilterBitmapMonitor discipline)."""

    def __init__(self, source: Optional[MegaStats] = None):
        self.source = source or _STATS
        self._last = self.source.snapshot()

    def do_monitor(self, emitter):
        s = self.source.snapshot()
        last, self._last = self._last, s
        emitter.metric("query/megakernel/hits", s["hits"] - last["hits"])
        emitter.metric("query/megakernel/fallbacks",
                       s["fallbacks"] - last["fallbacks"])
        emitter.metric("query/megakernel/donatedBytes",
                       s["donatedBytes"] - last["donatedBytes"])


# ---------------------------------------------------------------------------
# The fused filter node
# ---------------------------------------------------------------------------

class MegaBitmapNode(FilterNode):
    """A bitmap-eligible subtree fused INTO the aggregation program.

    Unlike DeviceBitmapNode (whose combined words are built by a separate
    fill dispatch and cached), this node's LEAVES are the resident device
    data — one word array per leaf, 1 bit/row in the width-1 tile-planar
    packed layout — and the word algebra traces inline. The algebra
    STRUCTURE is therefore program structure and joins the signature
    (exactly like the fill-program jit cache keyed on it)."""

    def __init__(self, structure, leaves: List[Tuple[str, np.ndarray]],
                 slot: int):
        self.structure = structure
        self.leaves = leaves
        self.slot = slot

    @classmethod
    def from_bitmap(cls, node: DeviceBitmapNode) -> "MegaBitmapNode":
        return cls(node.structure, list(node.leaves), node.slot)

    # same rendering/digest as the staged node — the pool-key contract is
    # shared, only the residency story differs
    structure_sig = DeviceBitmapNode.structure_sig
    digest = DeviceBitmapNode.digest

    def leaf_col(self, j: int) -> str:
        return f"__fleaf{self.slot}_{j}"

    def signature(self) -> str:
        return f"mega({self.slot}:{self.structure_sig()})"

    def required_device_columns(self):
        return set()

    def words_traced(self, cols: Dict):
        """Traced: the combined mask words (int32 [staged_rows/32]) — the
        one-shot word algebra, inline in the program instead of the
        separate fill dispatch. The algebra is
        filters.combine_structure_words — the SAME evaluator the staged
        fill program uses, so the two paths cannot drift."""
        import jax.numpy as jnp

        from druid_tpu.engine.filters import combine_structure_words

        def leaf_words(i):
            return cols[self.leaf_col(i)]

        def const_words(value):
            ref = cols[self.leaf_col(0)]
            fill = jnp.int32(-1) if value else jnp.int32(0)
            return jnp.full(ref.shape, fill, jnp.int32)

        return combine_structure_words(self.structure, leaf_words,
                                       const_words)

    def build(self, cols, aux):
        # XLA fallback (non-pallas strategies): combine words, then expand
        # to row bools — still inside the ONE traced program; XLA fuses the
        # expand into the mask consumers
        w = self.words_traced(cols)
        return expand_mask_words(w, cols["__valid"].shape[0])


def collect_mega_nodes(node: Optional[FilterNode]) -> List[MegaBitmapNode]:
    """Every MegaBitmapNode in a planned tree, deterministic DFS order."""
    out: List[MegaBitmapNode] = []

    def walk(n):
        if isinstance(n, MegaBitmapNode):
            out.append(n)
        elif isinstance(n, (AndNode, OrNode)):
            for c in n.children:
                walk(c)
        elif isinstance(n, NotNode):
            walk(n.child)
    if node is not None:
        walk(node)
    return out


def split_for_kernel(node: Optional[FilterNode]
                     ) -> Tuple[List[MegaBitmapNode], Optional[FilterNode]]:
    """(top-level AND-conjunct mega nodes, residual row-domain tree).

    Only mega nodes that are the root or direct AND conjuncts can combine
    in the WORD domain with the program's base mask; any other placement
    (under OR/NOT, or mixed deeper) stays in the residual tree and expands
    to row bools via MegaBitmapNode.build — still one dispatch, just
    without the in-kernel word-mask saving. The residual preserves child
    order, so its aux-consumption order matches the full tree's (mega
    nodes contribute no aux)."""
    if node is None:
        return [], None
    if isinstance(node, MegaBitmapNode):
        return [node], None
    if isinstance(node, AndNode):
        megas = [c for c in node.children if isinstance(c, MegaBitmapNode)]
        rest = [c for c in node.children
                if not isinstance(c, MegaBitmapNode)]
        if not megas:
            return [], node
        residual = None if not rest else \
            rest[0] if len(rest) == 1 else AndNode(rest)
        return megas, residual
    return [], node


# ---------------------------------------------------------------------------
# Planner hooks: megaize a planned tree / a kernel set
# ---------------------------------------------------------------------------

def megaize(filter_node: Optional[FilterNode], segment, padded_rows: int,
            perm_dig: Optional[str] = None) -> Optional[FilterNode]:
    """Rebuild a planned tree with every DeviceBitmapNode whose combined
    words are NOT already pool-resident replaced by a MegaBitmapNode (the
    one-shot inline path). Resident combined words — created by batched
    waves or staged-mode runs; the mega path itself never materializes
    them — keep the cached bit-test path instead of being re-derived.
    A purely per-segment hot query therefore re-runs the inline word
    algebra each time: a few word-wide VPU ops in-program, cheaper than
    the fill dispatch it replaces either way."""
    if filter_node is None or not collect_bitmap_nodes(filter_node):
        return filter_node

    def rebuild(n):
        if isinstance(n, DeviceBitmapNode):
            key = bitmap_pool_key(n, padded_rows, perm_dig)
            if segment.device_contains(key):
                _STATS.record_fallback()
                return n
            _STATS.record_hit()
            return MegaBitmapNode.from_bitmap(n)
        if isinstance(n, AndNode):
            return AndNode([rebuild(c) for c in n.children])
        if isinstance(n, OrNode):
            return OrNode([rebuild(c) for c in n.children])
        if isinstance(n, NotNode):
            return NotNode(rebuild(n.child))
        return n

    return rebuild(filter_node)


def megaize_kernels(kernels: Sequence, segment, padded_rows: int,
                    perm_dig: Optional[str] = None) -> None:
    """In-place megaize of every filtered-aggregator tree (kernels are
    single-use per execution — grouping.GroupPlan contract)."""
    from druid_tpu.engine.kernels import FilteredKernel
    for k in kernels:
        while isinstance(k, FilteredKernel):
            k.filter_node = megaize(k.filter_node, segment, padded_rows,
                                    perm_dig)
            k = k.child


def record_disabled_fallback(filter_node: Optional[FilterNode],
                             kernels: Sequence = ()) -> None:
    """Stats-only: bitmap subtrees that stay on the staged path because the
    megakernel is disabled."""
    n = len(collect_bitmap_nodes(filter_node))
    for k in kernels:
        for tree in k.filter_trees():
            n += len(collect_bitmap_nodes(tree))
    if n:
        _STATS.record_fallback(n)


# ---------------------------------------------------------------------------
# Mask-word packing (host + traced) and leaf staging
# ---------------------------------------------------------------------------

_LANE = 128


def staged_mask_rows(padded_rows: int) -> int:
    """Row count mask/leaf word arrays are sized for: covers every pallas
    row padding (n2 = round_up(max(rows, BLK), BLK) for BLK ≤ BLK_SMALL_W)
    rounded to whole mask-word TILES — exactly mega_reduce's n2m, so the
    resident leaf words AND into the fused mask without a pad."""
    return _round_up(max(padded_rows, BLK_SMALL_W), MEGA_MASK_TILE_ALIGN)


def expand_mask_words(words, rows: int):
    """Traced: width-1 tile-planar words → bool rows (the width-1 instance
    of data/packed.unpack_device; exact, so fused and staged masks carry
    identical bits)."""
    import jax.numpy as jnp
    w2 = words.reshape(-1, _LANE)
    sh = jnp.arange(MEGA_MASK_VPW, dtype=jnp.int32)
    bits = (w2[:, None, :] >> sh[None, :, None]) & jnp.int32(1)
    return bits.reshape(-1)[:rows].astype(bool)


def pack_mask_words_traced(mask):
    """Traced: bool rows (length a multiple of MEGA_MASK_ROW_ALIGN) →
    width-1 tile-planar int32 words. Disjoint bit positions, so the OR
    fold is exact; XLA fuses the row-mask computation into this pack, so
    no row-width mask materializes."""
    import jax.numpy as jnp
    m3 = mask.astype(jnp.int32).reshape(-1, MEGA_MASK_VPW, _LANE)
    words = m3[:, 0, :]
    for s in range(1, MEGA_MASK_VPW):
        words = words | (m3[:, s, :] << jnp.int32(s))
    return words.reshape(-1)


def stage_mega_leaves(segment, filter_node: Optional[FilterNode],
                      kernels: Sequence, padded_rows: int,
                      perm: Optional[np.ndarray] = None,
                      perm_key=None) -> Dict[str, object]:
    """Resident per-leaf mask words for every MegaBitmapNode in the query
    filter and the filtered-aggregator trees: {leaf col: int32 words}.
    Pool-cached per (dim, lut digest, staged rows, permutation digest) —
    the projection (permuted-layout) path stages PERMUTED words under its
    own digest, so original-order and permuted layouts never mix."""
    from druid_tpu.data import packed as packed_mod

    nodes = collect_mega_nodes(filter_node)
    for k in kernels:
        for tree in k.filter_trees():
            nodes.extend(collect_mega_nodes(tree))
    if not nodes:
        return {}
    n_w = staged_mask_rows(padded_rows)
    pdg = perm_digest(perm_key)
    out: Dict[str, object] = {}
    for node in nodes:
        for j, (dim, lut) in enumerate(node.leaves):
            key = ("megaleaf", dim, _leaf_digest(lut), n_w, pdg)

            def _build(dim=dim, lut=lut):
                import jax

                from druid_tpu.data import cascade as cascade_mod
                b = None
                if perm is None and cascade_mod.enabled():
                    # RLE-run-aware build: the match bit is decided once
                    # PER RUN (one LUT gather over run values + a repeat),
                    # not once per row — same output words bit-for-bit, so
                    # the resident cache and kernel paths compose unchanged
                    info = cascade_mod.column_run_info(segment, dim)
                    if info is not None:
                        values, ends, nr = info
                        lengths = np.diff(np.concatenate([[0], ends]))
                        b = np.repeat(lut[values], lengths)
                if b is None:
                    col = segment.dims[dim]
                    bm = col.bitmap_index().union_of(np.flatnonzero(lut))
                    b = bm.to_bool()
                    if perm is not None:
                        b = b[perm]
                padded = np.zeros(n_w, dtype=bool)
                padded[: b.shape[0]] = b
                return jax.device_put(
                    packed_mod.pack_padded(padded, MEGA_MASK_WIDTH, 0))

            out[node.leaf_col(j)] = segment.device_cached(key, _build)
    return out


# ---------------------------------------------------------------------------
# Donated carry buffers
# ---------------------------------------------------------------------------

def carry_defs(kernels: Sequence, col_dtypes: Dict, num_total: int,
               span: int) -> List[Tuple[Tuple[int, int], object]]:
    """[(shape, np dtype)] of the fused program's raw accumulator grids —
    the donated-carry allocation spec. MUST equal mega_reduce's out_shapes
    (both derive from pallas_agg.build_out_defs + plan_window)."""
    ops = [k.pallas_op(col_dtypes) for k in kernels]
    _, W = pallas_agg.plan_window(span)
    G2 = _round_up(num_total, 128) + W
    return [((G2 // 128, 128), dt)
            for _, dt in pallas_agg.build_out_defs(ops)]


def fresh_carries(defs: Sequence[Tuple[Tuple[int, int], object]]) -> Tuple:
    """Zero host carries (the cold-tick donation placeholders). Content is
    never read — the kernel re-initializes every grid at step 0 — so zeros
    vs a prior tick's partials are bit-identical by construction."""
    return tuple(np.zeros(shape, dtype=dt) for shape, dt in defs)


def discard_carries(carries: Optional[Sequence]) -> None:
    """Explicitly release carry grids popped for a dispatch that FAILED:
    donation may have invalidated their buffers mid-flight, so they can be
    neither re-parked nor reused — the exception path must discharge the
    ownership the take popped, or the grids dangle as untracked HBM while
    the pool's byte accounting (already decremented by take) looks clean.
    Host placeholder carries (fresh zeros) have no device buffer and are
    skipped. Both donorguard's take-without-repark rule and the donor
    witness (tools/druidlint/donorwitness.py) recognize this call as the
    exception-path ownership discharge."""
    for a in carries or ():
        delete = getattr(a, "delete", None)
        if delete is None:
            continue
        try:
            delete()
        except Exception:  # druidlint: disable=swallowed-exception
            # an already-invalidated donated buffer raises on delete; the
            # goal (buffer gone, accounting truthful) already holds
            pass


# ---------------------------------------------------------------------------
# The fused pallas program (strategy "megakernel")
# ---------------------------------------------------------------------------

def mega_reduce(arrays: Dict, mask, key, mega_nodes: Sequence[MegaBitmapNode],
                kernels: Sequence, num_total: int, span: int,
                packed_cols: Optional[Dict] = None):
    """Traced: (counts, per-kernel states, raw accumulator grids).

    pallas_agg.pallas_reduce's contract plus the fused-mask inputs: the
    base row mask packs to words in-program, ANDs with each mega node's
    inline word algebra, and the shared kernel (pallas_agg.grouped_reduce)
    reads ONE word row per block out of the resident mask-word tile
    (sub-lane shifts at bit base (block % (32/R))·R) instead of receiving a
    row mask — masked rows read the key sentinel exactly as the staged
    strategy's keyx fold does, so results are bit-identical. The raw grids
    ride back so the caller can park them as donated carries."""
    import jax.numpy as jnp

    # mask words cover every block padding the kernel can choose
    # (staged_mask_rows: whole word tiles past round_up(n, BLK))
    n = mask.shape[0]
    n2m = staged_mask_rows(n)

    # the fused mask: base row mask (validity ∧ intervals ∧ residual
    # filter) packs to words in-program; each top-level mega conjunct ANDs
    # in the word domain. Padding rows pack as 0 bits — masked.
    maskp = mask
    if n2m != n:
        maskp = jnp.concatenate(
            [mask, jnp.zeros((n2m - n,), jnp.bool_)])
    mwords = pack_mask_words_traced(maskp)
    need_w = n2m // MEGA_MASK_VPW
    for node in mega_nodes:
        w = node.words_traced(arrays)
        if w.shape[0] > need_w:
            w = w[:need_w]
        elif w.shape[0] < need_w:
            # staged arrays cover staged_mask_rows(padded) = n2m by
            # construction; zero-fill is the safe (masked) default anyway
            w = jnp.concatenate(
                [w, jnp.zeros((need_w - w.shape[0],), w.dtype)])
        mwords = mwords & w

    # keys stage RAW (no mask fold): the kernel sentinels masked rows from
    # the word bits, reproducing the staged keyx = where(mask, key,
    # SENTINEL) exactly
    return pallas_agg.grouped_reduce(
        arrays, key.astype(jnp.int32),
        mwords.reshape(n2m // MEGA_MASK_ROW_ALIGN, _LANE), kernels,
        num_total, span, packed_cols)
