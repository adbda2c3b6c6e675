"""The comparison that decides `correct`: served answer against reference.

Exact equality, value strings as the generator made them, row order as the
query defines it. One freedom: among topN rows of EQUAL metric the order is
free, and where the cut at `threshold` falls inside a run of equal metrics
either of the tied values may be returned — so a topN is compared as: every
returned row is the reference's row for that dimension value, and the
returned metrics are the reference's top metrics in order.
"""
from __future__ import annotations

from typing import Optional

from benchmark.reference import engine as reference


def _first_difference(got: list, want: list) -> str:
    if len(got) != len(want):
        return f"{len(got)} rows, the reference has {len(want)}"
    for i, (g, w) in enumerate(zip(got, want)):
        if g != w:
            return f"row {i}: got {g!r}, the reference has {w!r}"
    return "no difference"


def _check_top_n(data, query: dict, got: list) -> Optional[str]:
    # the reference with no cut: every value of the dimension, in order
    full = reference.answer(data, dict(query, threshold=2 ** 31))
    if not full:
        return None if got == [] else f"got {got!r}, the reference is empty"
    if len(got) != 1:
        return f"{len(got)} result objects, the reference has 1"
    if got[0].get("timestamp") != full[0]["timestamp"]:
        return (f"timestamp {got[0].get('timestamp')!r}, the reference has "
                f"{full[0]['timestamp']!r}")
    dim = query["dimension"] if isinstance(query["dimension"], str) \
        else query["dimension"]["dimension"]
    rows = got[0]["result"]
    by_value = {r[dim]: r for r in full[0]["result"]}
    for r in rows:
        if by_value.get(r.get(dim)) != r:
            return (f"row {r!r} differs from the reference's "
                    f"{by_value.get(r.get(dim))!r}")
    if len({r[dim] for r in rows}) != len(rows):
        return "a dimension value is returned twice"
    metric = query["metric"]
    want = [r[metric] for r in full[0]["result"][: int(query["threshold"])]]
    if [r[metric] for r in rows] != want:
        return "the returned metrics are not the reference's top metrics in order"
    return None


def check(data, query: dict, got) -> Optional[str]:
    """None when `got` is the reference's answer to `query`, else what
    differs."""
    if not isinstance(got, list):
        return f"the answer is no list: {str(got)[:200]!r}"
    if query["queryType"] == "topN":
        return _check_top_n(data, query, got)
    want = reference.answer(data, query)
    return None if got == want else _first_difference(got, want)
