"""Canonical mesh layout: the ONE source of PartitionSpecs in the tree.

The sharded execution path used to hand-roll PartitionSpecs at every call
site (stack staging, interval bounds, shard_map in/out specs), so a
resharding edit had to find and agree with every literal. This module is
the single authority instead: a frozen :class:`SpecLayout` names the mesh
axes once and exposes ONE METHOD PER ARRAY ROLE — stacked column words,
resident bitmap word slots, cascade run tables, per-segment time origins,
per-device partial grids — and every sharded producer/consumer asks it.
druidlint's `spec-literal-outside-layout` rule (tools/druidlint/
tracecheck.py) makes the invariant structural: a PartitionSpec or
NamedSharding constructed anywhere else in the tree is a lint failure.

Layout contract (the parallel/distributed.py execution model):

  * every STACKED leaf — decoded rows [K, R], packed/cascade words
    [K, W], run tables [K, runs], bitmap words [K, R/32], per-segment
    scalars [K] — carries the segment axis FIRST and shards over it;
    trailing dimensions are replicated within a shard;
  * plan constants (aux arrays) are replicated everywhere;
  * merged partial grids leave the program replicated — the collective
    merge (psum/pmin/pmax/all_gather+fold) already combined them, so the
    broker-side host merge for the sharded path is gone by construction.

jax imports stay lazy (function-local): the layout must be constructible
and hashable for cache keys without touching a backend.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

from druid_tpu.parallel import context


def _pspec():
    from jax.sharding import PartitionSpec
    return PartitionSpec


def _named_sharding():
    from jax.sharding import NamedSharding
    return NamedSharding


@dataclass(frozen=True)
class SpecLayout:
    """Frozen, canonical sharding layout over a 1-D segment mesh."""

    #: the mesh axis segments shard over (context.make_mesh's one axis)
    seg_axis: str = context.SEGMENT_AXIS

    # ---- one method per array role -----------------------------------
    def column_rows(self):
        """Stacked decoded column rows [K, R]: segment axis leads, rows
        replicated within the shard."""
        return _pspec()(self.seg_axis, None)

    def column_words(self):
        """Stacked packed/FOR/delta word slots [K, W] (data/packed.py
        tile-planar layout) — same story as decoded rows: the word axis
        is intra-segment."""
        return _pspec()(self.seg_axis, None)

    def bitmap_words(self):
        """Stacked resident filter-bitmap words [K, R/32]
        (engine/filters.py DeviceBitmapNode slots)."""
        return _pspec()(self.seg_axis, None)

    def run_tables(self):
        """Stacked RLE run values/ends [K, runs] (data/cascade.py)."""
        return _pspec()(self.seg_axis, None)

    def time0s(self):
        """Per-segment scalars [K]: time origins, delta-column firsts,
        RLE and validity (prefix-mask) row counts."""
        return _pspec()(self.seg_axis)

    def interval_bounds(self):
        """Per-segment relative interval bounds [K, n_intervals, 2]."""
        return _pspec()(self.seg_axis, None, None)

    def bucket_offsets(self):
        """Per-segment uniform-granularity bucket origins [K, 2]: offset
        within a period, whole periods (grouping.stacked_origins)."""
        return _pspec()(self.seg_axis, None)

    def replicated(self):
        """Plan constants (aux arrays): replicated on every device."""
        return _pspec()()

    def partial_grid(self):
        """Merged per-device partial grids: the collective merge already
        combined them, so they leave the program replicated."""
        return self.replicated()

    # ---- generic stacked-pytree mapping ------------------------------
    def stacked_leaf(self, ndim: int):
        """Spec for ONE stacked leaf by rank: axis 0 is always the
        segment axis ([K] scalars, [K, R] rows, [K, W] words alike);
        everything trailing is intra-segment."""
        if ndim < 1:
            raise ValueError("stacked leaves carry a leading segment axis")
        return _pspec()(self.seg_axis, *(None,) * (ndim - 1))

    def stacked_specs(self, tree):
        """The PartitionSpec tree matching a stacked pytree (compressed
        column objects included — their registered leaves map by rank)."""
        import jax
        return jax.tree.map(lambda leaf: self.stacked_leaf(leaf.ndim), tree)

    # ---- device placement (the only NamedSharding factory) -----------
    def sharding(self, mesh, spec):
        return _named_sharding()(mesh, spec)

    def put_stacked(self, mesh, tree):
        """device_put a stacked pytree with per-leaf rank-derived specs."""
        import jax
        shardings = jax.tree.map(
            lambda leaf: self.sharding(mesh, self.stacked_leaf(leaf.ndim)),
            tree)
        return jax.device_put(tree, shardings)

    def put_time0s(self, mesh, value):
        import jax
        return jax.device_put(value, self.sharding(mesh, self.time0s()))

    def put_interval_bounds(self, mesh, value):
        import jax
        return jax.device_put(value,
                              self.sharding(mesh, self.interval_bounds()))

    def put_bucket_offsets(self, mesh, value):
        import jax
        return jax.device_put(value,
                              self.sharding(mesh, self.bucket_offsets()))

    # ---- shard_map plumbing ------------------------------------------
    def in_specs(self, stacked) -> Tuple:
        """shard_map in_specs for the canonical sharded-program calling
        convention: (stacked tree, time0s, interval bounds, bucket
        offsets, replicated aux)."""
        return (self.stacked_specs(stacked), self.time0s(),
                self.interval_bounds(), self.bucket_offsets(),
                self.replicated())

    def out_specs(self) -> Tuple:
        """(counts, states): both pre-merged on device, both replicated."""
        return (self.partial_grid(), self.partial_grid())


def layout_for(mesh) -> "SpecLayout":
    """The layout for a mesh: its first axis is the segment axis (the
    parallel.context.make_mesh contract; user-built meshes keep their own
    leading axis name)."""
    axis = mesh.axis_names[0]
    return SpecLayout(seg_axis=axis)


def layout_sig(layout: "SpecLayout", mesh) -> Tuple:
    """Cache-key witness for everything a sharded program specializes on
    from the (layout, mesh) pair: segment axis, the exact device set in
    mesh order, the axis-name tuple, and the mesh shape. Joins
    distributed._sharded_sig; keyguard's `unkeyed-trace-input` rule
    (pyproject `keyguard-key-fns`) holds every parameter to dataflow into
    the return, so a mesh/layout input silently dropped from the key is a
    lint failure, not an aliased cached program."""
    return (layout.seg_axis,
            tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names),
            tuple(int(n) for n in mesh.devices.shape))
