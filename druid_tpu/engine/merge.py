"""Cross-segment merge of partial aggregation states.

Reference analog: the broker/historical merge step — MergeSequence n-way merge
+ QueryToolChest.mergeResults (e.g. TimeseriesBinaryFn, TopN priority-queue
merge, GroupBy RowBasedGrouperHelper). TPU-first design: partials are
per-key state arrays; merging re-encodes every partial's keys into ONE
merged key space (merged dictionaries play the DimensionMergerV9 role) and
combines with the kernels' elementwise combine — vectorized, no per-row
loop. The merge has two halves. `merge_to_partial` aligns and combines and
returns ONE `SegmentPartial` (host-keyed by the live merged keys, with the
merged per-dimension value lists beside it) that stands for all its inputs
and merges again like any of them: a data node answers with it
(`AggregatePartials.merged`, as the reference's ServerManager merges its
per-segment runners before anything is sent), so what crosses the wire and
what the broker merges is a partial a NODE. `decode_merged` turns such a
partial into the finish steps' arrays; `merge_partials` is the second
applied to the first. The first half picks how the partials are ALIGNED in
the merged space from what they show, and both alignments return the same
groups in the same order with the same bits:

  dense   (`_merge_dense`) when the merged space — buckets × the product of
          the merged per-dimension value lists (the sorted union of the
          partials' lists) — holds at most grouping.DENSE_GROUP_LIMIT keys.
          A key of that space IS an array index: each partial's slots are
          placed by a look-up table per dimension (none at all where the
          partials already share their dictionaries — then a dense-keyed
          partial's slot is its key and a host-keyed one's `host_unique`
          says it), runs of consecutive slots on consecutive keys combine
          as whole slices, and the result compacts to its live groups
          once. No sort, no search.
  sorted  (`_merge_sorted`) otherwise: compact each partial to its live
          keys, np.unique over all merged keys, searchsorted each partial
          into the result. Costs O(live keys · log), whatever the size of
          the space.

The same states merge across chips with psum/max collectives when segments
share dictionaries (see druid_tpu/parallel/).
"""
from __future__ import annotations

from itertools import chain
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

from druid_tpu.data.dictionary import Dictionary, merge_dictionaries
from druid_tpu.engine.grouping import (DENSE_GROUP_LIMIT, GroupSpec, KeyDim,
                                       SegmentPartial)
from druid_tpu.engine.kernels import AggKernel
from druid_tpu.obs.trace import current_span


# ---------------------------------------------------------------------------
# State pytree utilities (states are np arrays or dicts of np arrays)
# ---------------------------------------------------------------------------

def state_select(state, idx: np.ndarray):
    if isinstance(state, dict):
        return {k: state_select(v, idx) for k, v in state.items()}
    return state[idx]


def state_scatter(dest, pos: np.ndarray, src):
    if isinstance(dest, dict):
        for k in dest:
            state_scatter(dest[k], pos, src[k])
        return dest
    dest[pos] = src
    return dest


# ---------------------------------------------------------------------------
# Key decoding
# ---------------------------------------------------------------------------

def partial_nonzero_keys(p: SegmentPartial) -> np.ndarray:
    """Indices into the partial's dense key space that actually have rows."""
    return np.flatnonzero(p.counts > 0)


def decode_keys(p: SegmentPartial, keys: np.ndarray) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Decompose dense/compacted keys into (bucket_ids, [dim_ids...])."""
    spec = p.spec
    if spec.key_mode == "host":
        raw = spec.host_unique[keys].astype(np.int64)
    else:
        raw = keys.astype(np.int64)
    dim_ids: List[np.ndarray] = []
    for d in reversed(spec.dims):
        dim_ids.append((raw % d.cardinality).astype(np.int64))
        raw = raw // d.cardinality
    dim_ids.reverse()
    return raw, dim_ids  # raw is now the bucket id


def merge_partials(partials: Sequence[SegmentPartial],
                   dim_values: Sequence[Sequence[Sequence[str]]]):
    """Merge partial states across segments.

    dim_values[p][d] = list mapping local dim id -> string value for partial p,
    dimension d (from each segment's dictionary, after any extraction remap).

    Returns (buckets, dim_value_arrays, counts, states, kernels):
      buckets: int64 [G] bucket index per merged group
      dim_value_arrays: list of object arrays [G] of string values per dim
      counts: int64 [G]; states: merged state pytrees; kernels: from partial 0.

    Groups come out live (count > 0) and in merged-key order: this is
    `decode_merged` of `merge_to_partial`. A partial that is itself a merge
    (a data node's answer) goes through the same two steps; merging one
    partial combines nothing, so its bits come out as they went in.
    """
    return decode_merged(*merge_to_partial(partials, dim_values))


def merge_to_partial(partials: Sequence[SegmentPartial],
                     dim_values: Sequence[Sequence[Sequence[str]]]
                     ) -> Tuple[SegmentPartial, List[list]]:
    """The first half of `merge_partials`: align in the merged key space and
    combine. Returns (merged partial, merged per-dimension value lists) — a
    `SegmentPartial` that stands for all of `partials` and merges with
    others like any of them: `key_mode` "host" with `host_unique` the live
    merged keys in order, `dims` at the merged cardinalities, `counts` and
    `states` compacted to the live groups in fresh arrays (nothing of the
    inputs is written to), the kernels and segment of partial 0.

    The innermost open trace span (`datanode/merge` on a data node,
    `broker/merge` under a broker) learns which alignment ran (`mergePath`)
    and how many groups came out (`groups`)."""
    assert partials
    space = _dense_space(partials, dim_values)
    if space is None:
        keys, values, counts, states = _merge_sorted(partials, dim_values)
    else:
        keys, values, counts, states = _merge_dense(partials, space)
    sp = current_span()
    if sp is not None:
        sp.attrs["mergePath"] = "sorted" if space is None else "dense"
        sp.attrs["groups"] = int(len(keys))
    # the widest bucket axis: every merged key decodes inside it
    spec0 = max((p.spec for p in partials), key=lambda s: s.num_buckets)
    values = [v or [""] for v in values]
    spec = GroupSpec(
        bucket_starts=spec0.bucket_starts, bucket_mode=spec0.bucket_mode,
        uniform_period=spec0.uniform_period,
        uniform_first_offset=spec0.uniform_first_offset,
        key_mode="host",
        dims=tuple(KeyDim(d.column, len(v), None)
                   for d, v in zip(partials[0].spec.dims, values)),
        host_unique=keys, num_total=len(keys))
    return SegmentPartial(partials[0].segment, spec, counts, states,
                          partials[0].kernels), values


def decode_merged(p: SegmentPartial, values: Sequence[Sequence]):
    """The second half of `merge_partials`: a partial of `merge_to_partial`
    and its value lists as (buckets, dim_value_arrays, counts, states,
    kernels)."""
    buckets, dim_ids = decode_keys(p, np.arange(len(p.spec.host_unique)))
    dim_value_arrays = [np.asarray(v, dtype=object)[ids]
                        for v, ids in zip(values, dim_ids)]
    return buckets, dim_value_arrays, p.counts, p.states, p.kernels


def _merge_sorted(partials: Sequence[SegmentPartial],
                  dim_values: Sequence[Sequence[Sequence[str]]]):
    """The alignment that needs no bound on the key space: live keys only,
    np.unique + searchsorted. Returns (live merged keys, merged value lists
    — the values live somewhere, in merged order —, counts, states)."""
    kernels = partials[0].kernels
    n_dims = len(partials[0].spec.dims)

    # 1. compact each partial + decode
    compacted = []
    for p_i, p in enumerate(partials):
        nz = partial_nonzero_keys(p)
        buckets, dim_ids = decode_keys(p, nz)
        compacted.append((p, nz, buckets, dim_ids))

    # 2. build merged per-dim value spaces
    merged_values: List[List[str]] = []
    value_to_merged: List[Dict[str, int]] = []
    for d in range(n_dims):
        vals = set()
        for p_i, (p, nz, buckets, dim_ids) in enumerate(compacted):
            local_vals = dim_values[p_i][d]
            vals.update(local_vals[int(i)] for i in np.unique(dim_ids[d]))
        # numbers (numeric dims) sort before strings so mixed schemas
        # (column numeric in one segment, absent -> "" in another) never
        # compare across types
        ordered = sorted(vals, key=lambda v: (isinstance(v, str), v))
        merged_values.append(ordered)
        value_to_merged.append({v: i for i, v in enumerate(ordered)})

    # 3. merged key per group entry
    cards = [max(len(v), 1) for v in merged_values]
    merged_keys_per_partial = []
    for p_i, (p, nz, buckets, dim_ids) in enumerate(compacted):
        key = buckets.copy()
        for d in range(n_dims):
            local_vals = dim_values[p_i][d]
            # local id -> merged id remap (vectorized via lookup table)
            # values with no live group in any partial map to -1 (never
            # referenced by dim_ids, which only cover live groups)
            lut = np.fromiter((value_to_merged[d].get(v, -1) for v in local_vals),
                              dtype=np.int64, count=len(local_vals))
            key = key * cards[d] + lut[dim_ids[d]]
        merged_keys_per_partial.append(key)

    all_keys = (np.concatenate(merged_keys_per_partial)
                if merged_keys_per_partial else np.zeros(0, dtype=np.int64))
    uniq = np.unique(all_keys)
    G = len(uniq)

    # 4. align + combine
    counts = np.zeros(G, dtype=np.int64)
    states: Optional[Dict[str, object]] = None
    for (p, nz, buckets, dim_ids), mkeys in zip(compacted, merged_keys_per_partial):
        pos = np.searchsorted(uniq, mkeys)
        np.add.at(counts, pos, p.counts[nz])
        aligned = {}
        for k in kernels:
            dest = k.empty_state(G)
            src = state_select(p.states[k.name], nz)
            aligned[k.name] = state_scatter(dest, pos, src)
        if states is None:
            states = aligned
        else:
            states = {k.name: k.combine(states[k.name], aligned[k.name])
                      for k in kernels}

    if states is None:
        states = {k.name: k.empty_state(G) for k in kernels}
    return uniq, merged_values, counts, states


# ---------------------------------------------------------------------------
# Dense alignment
# ---------------------------------------------------------------------------

class _DenseSpace(NamedTuple):
    """The merged key space, held densely: key = bucket · strides[0] +
    Σ merged id_d · strides[d + 1]. `luts[p][d]` maps partial p's local ids
    of dimension d to merged ids; None where they already are merged ids."""
    num_buckets: int
    values: List[list]
    cards: List[int]
    strides: List[int]
    luts: List[List[Optional[np.ndarray]]]

    @property
    def size(self) -> int:
        return self.num_buckets * self.strides[0]


def _value_order(v):
    # numbers sort before strings, as in _merge_sorted
    return (isinstance(v, str), v)


def _dense_space(partials: Sequence[SegmentPartial],
                 dim_values) -> Optional[_DenseSpace]:
    """The merged space if it can be held densely, else None. Costs
    O(partials · Σ cardinality): it reads specs and value lists, never a
    group."""
    spec0 = partials[0].spec
    num_buckets = max(spec0.num_buckets, 1)
    n_dims = len(spec0.dims)
    floor = num_buckets      # the merged space is at least the widest partial's
    for d in range(n_dims):
        floor *= max(max(p.spec.dims[d].cardinality for p in partials), 1)
    if floor > DENSE_GROUP_LIMIT:
        return None
    for p, vals in zip(partials, dim_values):
        if max(p.spec.num_buckets, 1) != num_buckets \
                or (p.spec.key_mode == "host" and p.spec.host_unique is None) \
                or any(len(vals[d]) != p.spec.dims[d].cardinality
                       for d in range(n_dims)):
            return None

    values, cards = [], []
    luts = [[None] * n_dims for _ in partials]
    size = num_buckets
    for d in range(n_dims):
        lists = [vals[d] for vals in dim_values]
        first = lists[0]
        # equal lists spell the same values only if the types agree too
        # (1 == 1.0 == True); a str equals nothing but a str
        types = None if all(type(v) is str for v in first) \
            else list(map(type, first))
        shared = all(l is first or (l == first and (
            types is None or list(map(type, l)) == types))
            for l in lists[1:])
        try:
            merged = sorted(set(first if shared else chain(*lists)),
                            key=_value_order)
        except TypeError:
            return None       # unorderable values: not this path's to judge
        if shared:
            if len(merged) != len(first):
                return None   # a value twice in one list: ids are not groups
            if merged != list(first):
                # one list, not in merged order (an extraction's or a
                # query-time dictionary's): one permutation serves all
                index = {v: i for i, v in enumerate(merged)}
                lut = np.fromiter((index[v] for v in first), dtype=np.int64,
                                  count=len(first))
                for row in luts:
                    row[d] = lut
        else:
            # values that are equal across types (1 and 1.0) would be ONE
            # merged value whose spelling depends on who is live
            if any(len(set(l)) != len(l) for l in lists) \
                    or len({(type(v), v) for v in chain(*lists)}) != len(merged):
                return None
            index = {v: i for i, v in enumerate(merged)}
            for row, l in zip(luts, lists):
                row[d] = np.fromiter((index[v] for v in l), dtype=np.int64,
                                     count=len(l))
        values.append(merged)
        cards.append(max(len(merged), 1))
        size *= cards[-1]
        if size > DENSE_GROUP_LIMIT:
            return None
    strides = [1]             # of the bucket, then of every dimension
    for c in reversed(cards):
        strides.insert(0, strides[0] * c)
    return _DenseSpace(num_buckets, values, cards, strides, luts)


#: slots a run of consecutive slots on consecutive keys has to average
#: before slices beat one gather and one scatter (a slice pair costs about
#: what ~500 gathered elements do, whatever its length)
_RUN_MIN_SLOTS = 512


def _partial_placement(p: SegmentPartial, space: _DenseSpace, luts):
    """[(sel, pos), ...]: the slots of `p` that take part and where each
    lands in the merged arrays. Only LIVE slots take part, so nothing is ever
    read from a slot that no row reached. Consecutive slots that land on
    consecutive merged keys move as a pair of slices — a partial that is live
    everywhere and shares its dictionaries is one such pair, one that lacks
    a few keys a few of them —, the rest as a pair of index arrays."""
    spec = p.spec
    identity = all(l is None for l in luts)
    own_cards = [d.cardinality for d in spec.dims]
    host = spec.key_mode == "host"
    if host:
        n = len(spec.host_unique)
    else:
        n = space.num_buckets
        for c in own_cards:
            n *= c
    live = p.counts[:n] > 0
    if live.all():
        src, sel = np.arange(n), slice(0, n)
    else:
        src = sel = np.flatnonzero(live)

    strides = space.strides
    if identity:
        pos = np.asarray(spec.host_unique, dtype=np.int64)[sel] if host \
            else src
    elif host:
        buckets, dim_ids = decode_keys(p, src)
        pos = buckets * strides[0]
        for d, ids in enumerate(dim_ids):
            pos += (ids if luts[d] is None else luts[d][ids]) * strides[d + 1]
    else:
        # the partial's own (bucket × dims) grid, each cell holding its
        # merged key: an outer sum of the look-up tables, no division
        grid = np.arange(space.num_buckets, dtype=np.int64) * strides[0]
        for d, c in enumerate(own_cards):
            ids = np.arange(c, dtype=np.int64) if luts[d] is None else luts[d]
            grid = grid[..., None] + ids * strides[d + 1]
        pos = grid.ravel()[sel]

    m = len(src)
    if m == 0:
        return []
    broken = np.diff(pos) != 1
    if sel is src and pos is not src:
        broken |= np.diff(src) != 1
    cuts = (np.flatnonzero(broken) + 1).tolist()
    if len(cuts) * _RUN_MIN_SLOTS > m:
        return [(sel, pos)]
    return [(slice(int(src[a]), int(src[a]) + b - a),
             slice(int(pos[a]), int(pos[a]) + b - a))
            for a, b in zip([0] + cuts, cuts + [m])]


def _state_like(state, ref):
    """`state` in the dtypes of `ref` — what scattering into an
    `empty_state` does to it on the sorted path."""
    if isinstance(ref, dict):
        return {k: _state_like(state[k], ref[k]) for k in ref}
    state = np.asarray(state)
    return state if state.dtype == ref.dtype else state.astype(ref.dtype)


def _merge_dense(partials: Sequence[SegmentPartial], space: _DenseSpace):
    """Place partial 0 in the merged arrays, combine the others into them in
    partial order (so float sums round as on the sorted path), compact
    once. Returns (live merged keys, the space's value lists, counts,
    states); no array of a partial is written to."""
    kernels = partials[0].kernels
    size = space.size
    counts = np.zeros(size, dtype=np.int64)
    held = np.zeros(size, dtype=np.int32)   # partials that hold the group
    states = {k.name: k.empty_state(size) for k in kernels}
    for p_i, (p, luts) in enumerate(zip(partials, space.luts)):
        for sel, pos in _partial_placement(p, space, luts):
            counts[pos] += p.counts[sel]
            held[pos] += 1
            for k in kernels:
                src = _state_like(state_select(p.states[k.name], sel),
                                  states[k.name])
                if p_i:
                    src = k.combine(state_select(states[k.name], pos), src)
                state_scatter(states[k.name], pos, src)

    live = np.flatnonzero(counts > 0)
    counts = counts[live]
    states = {k.name: state_select(states[k.name], live) for k in kernels}
    # a group that some partial lacks meets the identity there on the sorted
    # path; once is as good as many, and only a float sum can tell at all
    # (-0.0 + 0.0 is 0.0)
    lacked = np.flatnonzero(held[live] < len(partials))
    if len(lacked):
        for k in kernels:
            state_scatter(states[k.name], lacked, k.combine(
                state_select(states[k.name], lacked),
                k.empty_state(len(lacked))))
    return live, space.values, counts, states


def finalize_states(kernels: Sequence[AggKernel], states: Dict[str, object],
                    finalize: bool = True) -> Dict[str, np.ndarray]:
    """Per-group finalized (or raw combined) value arrays keyed by agg name."""
    out = {}
    for k in kernels:
        arr = k.finalize_array(states[k.name])
        out[k.name] = arr
    return out
