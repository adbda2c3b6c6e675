"""Engine ⇄ linter shared contracts — the single source of truth tracecheck
(tools/druidlint/tracecheck.py) validates the Pallas + XLA engine layer
against.

Everything here is a plain Python constant: this module MUST stay importable
without jax/numpy so the linter can load it standalone (by file path, no
package import, no x64 side effects). The engine imports the same names, so
a kernel edit that changes a contract changes exactly one place — and the
tier-1 lint gate re-checks every declared invariant against the new value.

Contract families:
  * tile geometry   — lane width, pallas block/window constants
  * capacity        — pallas group/field/slot caps + the VMEM tile budget
  * dtype lattice   — byte widths, 64-bit dtypes, reduce-identity table
  * AggKernel shape — required methods per reduce_kind
  * symbol bounds   — value ranges for names the abstract interpreter
                      cannot derive from the kernel module's own statements
"""

# ---- tile geometry --------------------------------------------------------

LANE = 128            # TPU lane width: the last dim of every VMEM tile
SUBLANE = 8           # float32 sublane count (min tile is (8, 128))

BLK_SMALL_W = 2048    # pallas rows per block when the window is narrow
BLK_WIDE_W = 1024     # pallas rows per block for wide windows
SPAN_BLOCK = 1024     # block size Projection.max_span is measured over
MAX_W = 1024          # widest supported aligned key window

# ---- capacity -------------------------------------------------------------

#: hard cap on num_total for the pallas strategy: the FULL accumulator grid
#: for every output slot stays resident in VMEM across the whole grid, so
#: the group space must be bounded for the vmem-budget contract to hold.
MAX_PALLAS_GROUPS = 1 << 17

#: max distinct value columns streamed into the kernel (one VMEM input tile
#: each, alongside the key tile).
MAX_PALLAS_FIELDS = 8

#: max output slots (out_defs): 1 counts grid + at most 2 slots per op
#: (the int32 lo/hi limb pair) across MAX_PALLAS_FIELDS ops.
MAX_PALLAS_SLOTS = 1 + 2 * MAX_PALLAS_FIELDS

#: Mosaic's DEFAULT scoped-VMEM limit and the budget the declared tiles
#: (counted once each) must fit in. The pipeline double-buffers every
#: blocked operand, the resident output grids included, so at the group ×
#: slot caps the real need passes the default limit: the pallas_call states
#: its own limit (engine/pallas_agg.py grouped_reduce) as twice the declared
#: tiles plus VMEM_SCRATCH_BYTES.
#: Override per-repo via [tool.druidlint] vmem-cap-bytes.
VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

#: headroom on top of the double-buffered tiles for Mosaic's internal
#: scratch: the kernel's [R, 128, 128] one-hot temporaries (1 MiB each at
#: R = 16) do not fit the vector registers and spill there.
VMEM_SCRATCH_BYTES = 16 * 1024 * 1024

#: rows of a word tile (bit-packed value words, megakernel mask words) as
#: the kernel's BlockSpecs declare it: Mosaic refuses a block whose
#: second-minor dim is neither a multiple of the sublane count nor the
#: array's own extent, so sub-sublane word slices (R // vpw ∈ {1, 2, 4}
#: rows, the mask's single row) ride in whole tiles and the kernel picks
#: its rows out of the resident tile.
WORD_TILE_ROWS = SUBLANE

#: widest element the pallas kernel ever tiles: ops accept int32/float32
#: only (pallas_op eligibility) and pallas-accum-dtype bans 64-bit inside
#: the kernel body, so 4 bytes bounds every declared tile.
PALLAS_MAX_TILE_DTYPE_BYTES = 4

# ---- batched multi-segment execution --------------------------------------

#: max segments stacked into ONE batched device dispatch (engine/batching.py).
#: Bounds both the stacked [K, R] working set and the worst-case host-side
#: slice/post loop per dispatch.
BATCH_MAX_SEGMENTS = 64

#: below this many shape-compatible segments a batch never forms: one
#: stacked program would dispatch exactly as many device calls as the
#: per-segment path while paying an extra compile.
BATCH_MIN_SEGMENTS = 2

#: rows per segment above which batching stops paying: per-segment dispatch
#: overhead is amortized by compute alone, and the in-program stack of a
#: huge [K, R] block would double its HBM footprint for no win.
BATCH_MAX_SEGMENT_ROWS = 1 << 21

#: base rung of the padded-row ladder (must equal data.segment's
#: DEFAULT_ROW_ALIGN — asserted by engine/batching.py at import). Rungs are
#: powers of two times this, so at most
#: log2(BATCH_MAX_SEGMENT_ROWS / BATCH_ROW_ALIGN) + 1 row shapes exist per
#: plan structure — the compile-count bound of the batched path.
BATCH_ROW_ALIGN = 1024

# ---- compressed-domain packing (data/packed.py) ---------------------------

#: bits per packed storage word (int32 words: the narrowest element Mosaic
#: tiles natively, and the dtype every unpack shift/mask stays in).
PACK_WORD_BITS = 32

#: supported pack widths, each dividing PACK_WORD_BITS so no value crosses a
#: word boundary and values-per-word (vpw = 32 // width) divides the
#: sublane row count of every pallas block (R = BLK // LANE ∈ {8, 16}).
#: Width 2 (vpw 16) is deliberately absent: vpw must divide R for the
#: in-kernel per-tile unpack, and 16 does not divide the wide-window R=8.
#: Quantizing ceil(log2(cardinality)) up to these widths keeps pack
#: descriptors coarse, so near-identical segments share plan signatures
#: (the same design rule as SumKernel.chunk_rows pow2 quantization).
PACK_WIDTHS = (4, 8, 16)

# ---- cascaded encodings (data/cascade.py) ---------------------------------

#: hard cap on the pow2-padded run count of any cascade run array (RLE run
#: values/ends, the run-domain aggregation tables, LZ4 token streams): run
#: metadata must stay small enough that a (CASCADE_MAX_RUNS // LANE, LANE)
#: run tile fits the pallas VMEM budget with room to spare, and that the
#: host-side run planning stays O(small). A column whose padded run count
#: exceeds this is simply not run-compressible — it falls back to
#: bit-packing or decoded staging (correctness never depends on cascades).
CASCADE_MAX_RUNS = 1 << 16

#: run-value tile rows when a kernel streams run metadata as (RUN_TILE_ROWS,
#: LANE) VMEM tiles — the worst case is every run resident at once.
RUN_TILE_ROWS = CASCADE_MAX_RUNS // LANE

# ---- megakernel mask words (engine/megakernel.py) -------------------------

#: bits per row of the megakernel's fused row-mask words: the width-1
#: instance of the data/packed.py tile-planar layout (word[q, l] packs tile
#: rows q*32+s at lane l, bit s), so the host packer (pack_padded) and the
#: in-kernel sub-lane unpack share one canonical encoding with the packed
#: value columns.
MEGA_MASK_WIDTH = 1

#: mask rows per 32-bit word (PACK_WORD_BITS // MEGA_MASK_WIDTH).
MEGA_MASK_VPW = PACK_WORD_BITS // MEGA_MASK_WIDTH

#: rows covered by ONE 128-lane word row of the mask view. Mask word arrays
#: pad to a multiple of this so (rows/32,) words reshape cleanly into
#: (rows/4096, 128) tiles; every pallas block (BLK ∈ {1024, 2048} rows,
#: R = BLK/128 ∈ {8, 16} tile rows) then sits inside ONE word row because
#: MEGA_MASK_VPW % R == 0 — the in-kernel unpack is a pure sub-lane shift
#: at bit base (block % (MEGA_MASK_VPW / R)) · R, no gather, one word row
#: per block instead of an (R, 128) int32 row mask (the 32x mask VMEM cut).
MEGA_MASK_ROW_ALIGN = MEGA_MASK_VPW * LANE

#: rows covered by one WHOLE (WORD_TILE_ROWS, 128) mask-word tile: staged
#: mask/leaf word arrays pad to a multiple of this, so the kernel's mask
#: operand is always whole tiles.
MEGA_MASK_TILE_ALIGN = WORD_TILE_ROWS * MEGA_MASK_ROW_ALIGN

# ---- device filter bitmaps (engine/filters.py device-bitmap algebra) ------

#: bits per device filter-bitmap word (uint32, LSB-first: row r is bit
#: r % 32 of word r // 32 — data/bitmap.py to_words32). Every padded row
#: count is a multiple of BATCH_ROW_ALIGN = 1024, so word arrays always
#: reshape cleanly into (rows/32,) and the in-program bit-test expansion
#: is a pure broadcast shift, no gather.
FILTER_WORD_BITS = 32

#: worst-case bitmap-word rows per pallas-class block: a BLK_SMALL_W-row
#: block covers BLK_SMALL_W / FILTER_WORD_BITS = 64 word rows. The word
#: expansion runs in XLA before any pallas call today; this bound exists so
#: the vmem-budget rule can size a bitmap-word tile if one is ever declared
#: (tests/test_tracecheck.py pins the worst case).
FILTER_WORDS_PER_BLOCK = BLK_SMALL_W // FILTER_WORD_BITS

# ---- device segment pool --------------------------------------------------

#: default HBM byte budget for the process-wide device segment pool
#: (data/devicepool.py): staged DeviceBlocks + derived padded device arrays
#: LRU-evict by ACTUAL array bytes once the pool passes this. Deliberately
#: far below a v5e/v5p core's HBM so query working sets (stacked batches,
#: accumulator grids) always have headroom. Override via the
#: DRUID_TPU_DEVICE_POOL_BYTES env var or DeviceSegmentPool.configure().
DEVICE_POOL_BUDGET_BYTES = 4 * 1024 ** 3

#: bound on the OUTPUT bytes of programs a request has enqueued and not
#: fetched yet (grouping.run_grouped_aggregates): a request enqueues every
#: program it needs and fetches once, so its un-fetched outputs stay in HBM
#: outside the pool's budget until then. At the bound the pending programs
#: are fetched and the enqueues go on. Twenty 100,000-group partials are
#: ~52 MB and never reach it; 480 of them (1.25 GB) drain in five waves.
PENDING_FETCH_BYTES = 256 * 1024 ** 2

# ---- donation platform gate (donated carry buffers) -----------------------

#: backends whose runtimes honor buffer donation. CPU *accepts*
#: donate_argnums but silently ignores it (with a per-call warning), so
#: only accelerator backends belong here — forcing donation elsewhere is
#: the silent-corruption class donorguard's donate-platform-gate guards.
DONATION_BACKENDS = ("tpu", "gpu")


def donation_supported() -> bool:
    """THE donation platform predicate: every donation-enable decision in
    the engine must route through this one function (donorguard's
    `donate-platform-gate` rule pins the inventory to the configured
    `donorguard-platform-gate` list, which names exactly this).

    Tri-state ``DRUID_TPU_DONATE``: "on"/"1" forces donation (the real-TPU
    bench lever), "off"/"0" disables it, unset/"auto" detects by backend
    (DONATION_BACKENDS). Read LIVE by design — the decision joins the jit
    program signature's mk= field (engine/grouping.py), so a mid-process
    flip keys a fresh program instead of aliasing a cached one. Imports
    stay inside the function: this module must remain loadable standalone,
    without jax, by the linter."""
    import os
    mode = os.environ.get("DRUID_TPU_DONATE", "auto").strip().lower() \
        or "auto"
    if mode in ("on", "1", "force"):
        return True
    if mode in ("off", "0"):
        return False
    try:
        import jax
        return jax.default_backend() in DONATION_BACKENDS
    except Exception:  # druidlint: disable=swallowed-exception
        # availability probe: no backend means no donation, never an error
        return False


# ---- program and kernel names ---------------------------------------------

#: reduction strategies a device program is BUILT for (grouping.
#: select_strategy / _projection_strategy; "projection" is resolved to one of
#: these before any program exists)
STRATEGIES = ("mm", "blocked", "mixed", "windowed", "pallas", "megakernel")

#: aggregation program families: per-segment (engine/grouping.py), batched
#: multi-segment (engine/batching.py), sharded mesh (parallel/distributed.py)
AGG_PROGRAM_FAMILIES = ("seg_agg", "batch_agg", "sharded_agg")

#: THE closed set of names a jitted query-path callable may carry (its
#: `__name__`, hence the `jit_<name>` module the profiler records and the
#: dispatch spans' `program` attribute). Chosen by path and strategy only:
#: never a shape, a segment id or a digest, so kernel time sums by a stable
#: name across segments, queries and processes. Listed in PERF.md §3.
PROGRAM_NAMES = frozenset(
    [f"{family}_{strategy}" for family in AGG_PROGRAM_FAMILIES
     for strategy in STRATEGIES]
    + ["run_domain_agg",        # data/cascade.py code-domain program
       "bitmap_fill_wave"])     # engine/filters.py a wave's fill (of 1 too)

#: `pl.pallas_call` names (engine/pallas_agg.py grouped_reduce): the sorted
#: projection's group reduce, and its megakernel variant that takes the
#: filter mask as words
PALLAS_KERNEL_NAMES = ("proj_group_reduce", "proj_group_reduce_mega")


def program_name(family: str, strategy: str) -> str:
    """`<family>_<strategy>`, refused unless it is in PROGRAM_NAMES."""
    name = f"{family}_{strategy}"
    if name not in PROGRAM_NAMES:
        raise ValueError(f"no program name for {family!r} × {strategy!r}")
    return name


def named_program(fn, name: str):
    """`fn` carrying `name` (one of PROGRAM_NAMES) as its `__name__`, for
    `jax.jit` to name the module after."""
    if name not in PROGRAM_NAMES:
        raise ValueError(f"{name!r} is not a documented program name")
    fn.__name__ = fn.__qualname__ = name
    return fn


#: THE closed set of reasons a mesh node hands a query to the per-segment
#: path (parallel/distributed.py `_plan_sharded`, one per ineligible exit):
#: the `reason` attribute of an `engine/sharded/plan` span whose `fallback`
#: is 1. tests/test_sharded_spans.py holds the source to this list.
SHARDED_FALLBACK_REASONS = (
    "cross_process_mesh",          # the mesh spans processes
    "numeric_dimension",           # per-segment query-time id dictionaries
    "key_dims_differ",             # key dims' columns or cardinalities
    "key_dimension_missing",       # a raw key dim absent from a segment
    "dictionaries_differ",         # a raw key dim's dictionaries disagree
    "key_or_bucket_mode",          # not dense keys over all/uniform buckets
    "filter_plans_differ",
    "filter_constants_differ",
    "kernel_plans_differ",
    "kernel_constants_differ",
    "virtual_columns_differ",
    "complex_metric",              # a 2-D metric column: the stack is [K, R]
    "dimension_presence_differs",
    "metric_presence_differs",
    "metric_types_differ",
)


def sharded_fallback_reason(reason: str) -> str:
    """`reason`, refused unless it is in SHARDED_FALLBACK_REASONS."""
    if reason not in SHARDED_FALLBACK_REASONS:
        raise ValueError(f"{reason!r} is not a documented fall-back reason")
    return reason


#: THE closed set of reasons a segment of a batch-planned request runs
#: alone through the per-segment path (engine/batching.py, one per exit of
#: `_plan_for` and of the chunking): the `reason` attribute of an
#: `engine/batch/plan` span whose `stragglers` is above 0 — the reason that
#: holds most of them. tests/test_batch_served.py holds the source to it.
BATCH_FALLBACK_REASONS = (
    "rows_over_limit",             # more than BATCH_MAX_SEGMENT_ROWS rows
    "code_domain",                 # answered over run metadata, undecoded
    "no_stable_id_column",         # a derived id column the pool cannot key
    "key_or_bucket_mode",          # not dense keys over all/uniform buckets
    "group_space_over_limit",      # more than BLOCKED_GROUP_LIMIT groups
    "constant_false",              # the filter folded to false: no device
    "projection_layout",           # a sorted projection is one segment's own
    "ladder_remainder",            # what the K ladder leaves: a lone segment
    "opted_out",                   # its query's {"batchSegments": false}
)


def batch_fallback_reason(reason: str) -> str:
    """`reason`, refused unless it is in BATCH_FALLBACK_REASONS."""
    if reason not in BATCH_FALLBACK_REASONS:
        raise ValueError(f"{reason!r} is not a documented fall-back reason")
    return reason


# ---- dtype lattice --------------------------------------------------------

DTYPE_BYTES = {
    "bool": 1, "int8": 1, "uint8": 1,
    "int16": 2, "uint16": 2, "float16": 2, "bfloat16": 2,
    "int32": 4, "uint32": 4, "float32": 4,
    "int64": 8, "uint64": 8, "float64": 8,
}

#: dtypes that silently truncate to 32-bit under JAX's default
#: x64-disabled mode (the x64-dtype rule's subject).
X64_DTYPES = ("int64", "uint64", "float64")

#: reduce identity literal → the accumulator dtype it belongs to. A dtype
#: constructor applied to one of these extreme values inside the pallas
#: module must use exactly this dtype (pallas-accum-dtype): the int-min
#: identity / key sentinel is int32 2**31-1, the int-max identity is int32
#: -(2**31), the float min/max identities are float32 ±inf.
REDUCE_IDENTITIES = {
    2 ** 31 - 1: "int32",
    -(2 ** 31): "int32",
    float("inf"): "float32",
    float("-inf"): "float32",
}

# ---- AggKernel shape ------------------------------------------------------

#: every concrete AggKernel subclass must define these (agg-contract).
AGG_REQUIRED_METHODS = ("signature", "update", "combine", "empty_state")

#: additionally required when the class's effective reduce_kind is "fold"
#: (the base-class default): the sharded merge all_gathers states and folds
#: them pairwise on device.
AGG_FOLD_REQUIRED = ("device_combine",)

# ---- symbol bounds for the abstract interpreter ---------------------------

#: name → (lo, hi, multiple_of). Bounds for values tracecheck cannot derive
#: from the scanned module's own assignments: function parameters and
#: results of host-side planning calls. These ARE engine contracts —
#: plan_window returns blk ≤ BLK_SMALL_W and a 128-aligned W ≤ MAX_W,
#: usable() rejects num_total > MAX_PALLAS_GROUPS, and pallas_reduce
#: asserts the field/slot caps — so the static bounds and the runtime
#: checks cannot drift apart.
SYMBOL_BOUNDS = {
    "span": (1, MAX_W, 1),
    "num_total": (1, MAX_PALLAS_GROUPS, 1),
    "n": (1, 1 << 31, 1),
    "BLK": (BLK_WIDE_W, BLK_SMALL_W, LANE),
    "W": (LANE, MAX_W, LANE),
    "len(uniq_fields)": (0, MAX_PALLAS_FIELDS, 1),
    "len(out_defs)": (1, MAX_PALLAS_SLOTS, 1),
    # packed-input variant (pallas_agg packed word tiles): vpw = 32 // width
    # over PACK_WIDTHS, and Rw = R // vpw word rows per block — the worst
    # case (width 16, BLK_SMALL_W) is R // 2 = 8 rows. Enforced at runtime
    # by pallas_reduce's vpw-divides-R assertion.
    "vpw": (2, 8, 2),
    "Rw": (1, 8, 1),
    "len(dense_fields)": (0, MAX_PALLAS_FIELDS, 1),
    "n_dense": (0, MAX_PALLAS_FIELDS, 1),
    "len(packed_rws)": (0, MAX_PALLAS_FIELDS, 1),
    # the megakernel's mask-word operand: present (1) or absent (0)
    "n_mask": (0, 1, 1),
    # device filter-bitmap words (engine/filters.py): word rows per block,
    # bounded by FILTER_WORDS_PER_BLOCK — covers the bitmap words' worst-
    # case tile should a kernel ever stream them in.
    "Rw32": (1, FILTER_WORDS_PER_BLOCK, 1),
    # cascade run metadata (data/cascade.py): run counts are pow2-padded and
    # capped at CASCADE_MAX_RUNS by planning (plan_column / the run-domain
    # eligibility check), run-value tiles declare at most RUN_TILE_ROWS
    # (LANE-wide) rows, and a single run can span at most a whole batched
    # segment. These bounds let vmem-budget / pallas-tile-shape statically
    # cover any kernel that streams run tables as (Rrun, 128) tiles.
    "n_runs": (1, CASCADE_MAX_RUNS, 1),
    "Rrun": (1, RUN_TILE_ROWS, 1),
    "run_len": (1, BATCH_MAX_SEGMENT_ROWS, 1),
}
