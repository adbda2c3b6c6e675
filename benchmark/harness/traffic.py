"""The one general traffic generator: a workload file + a seed → a plan.

A plan is the list of requests of one run, each with its due time (open
loop), its native query and its queryId. Everything a mix is — templates
and weights, each literal slot's generator, the loop — is data in
`workloads/<name>.json` and `queries/<name>.json`; this module implements
the vocabulary once. numpy only: no jax, no druid_tpu.

Every seed gets the SAME amount of work in another order: the number of
requests, the count of each template and of each stratified choice (an
interval's length) are fixed by the file and the window; the seed draws the
order, the arrival instants and the literals.
"""
from __future__ import annotations

import copy
import json
import os
from datetime import datetime, timezone
from typing import Dict, List

import numpy as np

DAY_MS = 86_400_000
SLOT = "$slot"


def iso(ms: int) -> str:
    return datetime.fromtimestamp(ms / 1000.0, tz=timezone.utc) \
        .strftime("%Y-%m-%dT%H:%M:%S.") + f"{ms % 1000:03d}Z"


def fill(template, values: Dict[str, object]):
    """A copy of `template` with every {"$slot": name} replaced."""
    if isinstance(template, dict):
        if set(template) == {SLOT}:
            return copy.deepcopy(values[template[SLOT]])
        return {k: fill(v, values) for k, v in template.items()}
    if isinstance(template, list):
        return [fill(v, values) for v in template]
    return template


def slots_of(template) -> List[str]:
    if isinstance(template, dict):
        if set(template) == {SLOT}:
            return [template[SLOT]]
        return [s for v in template.values() for s in slots_of(v)]
    if isinstance(template, list):
        return [s for v in template for s in slots_of(v)]
    return []


def _stratified(rng, choices: List, weights: List[float], n: int) -> List:
    """`n` picks whose counts are the weights' shares (largest remainders),
    in a seeded order."""
    w = np.asarray(weights, dtype=np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    short = n - int(counts.sum())
    for j in np.argsort(-(exact - counts), kind="stable")[:short]:
        counts[j] += 1
    picks = [c for c, k in zip(choices, counts) for _ in range(int(k))]
    return [picks[j] for j in rng.permutation(n)]


def _zipf_ranks(rng, n: int, size: int, exponent: float) -> np.ndarray:
    """`n` ranks in [0, size), rank r with weight (r+1)**-exponent."""
    cdf = np.cumsum(np.arange(1, size + 1, dtype=np.float64) ** -exponent)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      size - 1)


def _gen_slot(spec: dict, rng, n: int, config: dict) -> List:
    """`n` values of one literal slot."""
    first = int(config["first_day_ms"])
    days = int(config["segments"])
    gen = spec["gen"]
    if gen == "all_days":
        return [[f"{iso(first)}/{iso(first + days * DAY_MS)}"]] * n
    if gen == "uniform_int":
        return rng.integers(int(spec["low"]), int(spec["high"]) + 1,
                            size=n).tolist()
    if gen == "zipf_string":
        # Zipf over the integers [low, high), rank 0 = low, as decimal strings
        low, high = int(spec["low"]), int(spec["high"])
        ranks = _zipf_ranks(rng, n, high - low, float(spec["exponent"]))
        return [str(low + int(r)) for r in ranks]
    if gen == "recent_days":
        # an interval of whole days ending at a day drawn with recency skew:
        # Zipf over days back from the newest; lengths stratified
        lengths = _stratified(rng, spec["lengths"], spec["weights"], n)
        back = _zipf_ranks(rng, n, days, float(spec["exponent"]))
        out = []
        for length, b in zip(lengths, back.tolist()):
            length = min(int(length), days)           # keep it inside the data
            end_day = max(days - b, length)
            out.append([f"{iso(first + (end_day - length) * DAY_MS)}/"
                        f"{iso(first + end_day * DAY_MS)}"])
        return out
    raise ValueError(f"unknown slot generator {gen!r}")


def load_query(root: str, name: str) -> dict:
    with open(os.path.join(root, "queries", f"{name}.json")) as f:
        return json.load(f)


def request_count(workload: dict, seconds: float) -> int:
    loop = workload["loop"]
    rate = loop["rate_qps"] if loop["kind"] == "open" else loop["plan_qps"]
    return max(1, int(round(float(rate) * seconds)))


def make_plan(root: str, workload: dict, config: dict, seed: int,
              seconds: float) -> List[dict]:
    """The run's requests in sending order: {i, due_s, template, query}.
    Open loop: a Poisson process of the file's rate conditioned on its count
    (sorted uniform instants in the window). Closed loop: `plan_qps` ×
    seconds requests, more than the clients can send, all due at once."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    n = request_count(workload, seconds)
    entries = workload["templates"]
    which = _stratified(rng, list(range(len(entries))),
                        [e["weight"] for e in entries], n)
    if workload["loop"]["kind"] == "open":
        due = np.sort(rng.random(n) * float(seconds)).tolist()
    else:
        due = [0.0] * n
    values: Dict[int, Dict[str, List]] = {}
    for t, entry in enumerate(entries):
        count = which.count(t)
        slots = {name: _gen_slot(spec, rng, count, config)
                 for name, spec in sorted(entry.get("slots", {}).items())}
        distinct = entry.get("distinct")
        if distinct:
            # no combination of these slots twice in a run: redraw repeats
            seen = set()
            for j in range(count):
                for _try in range(1000):
                    key = tuple(json.dumps(slots[s][j]) for s in distinct)
                    if key not in seen:
                        seen.add(key)
                        break
                    for s in distinct:
                        slots[s][j] = _gen_slot(entry["slots"][s], rng, 1,
                                                config)[0]
                else:
                    raise ValueError(f"{entry['query']}: cannot draw {count} "
                                     f"distinct {distinct}")
        values[t] = slots
    templates = [load_query(root, e["query"]) for e in entries]
    cursor = [0] * len(entries)
    plan = []
    for i, t in enumerate(which):
        j = cursor[t]
        cursor[t] += 1
        query = fill(templates[t]["query"],
                     {name: vals[j] for name, vals in values[t].items()})
        query["context"] = dict(query.get("context", {}),
                                queryId=f"bench-{int(seed)}-{i}")
        plan.append({"i": i, "due_s": due[i],
                     "template": entries[t]["query"], "query": query})
    return plan


def warm_plan(root: str, workload: dict, config: dict, seed: int) -> List[dict]:
    """One request per distinct compiled shape of every template: the
    template's `warm` list names, per warm-up, the slot values that pick the
    shape (an interval's length); other slots are drawn from the seed by a
    stream of its own, so no warm-up literal is one of the window's by
    construction of the `distinct` slots' ranges alone — it may be, and then
    a cache answers that request as it would in a deployment."""
    rng = np.random.default_rng([int(seed), 0x3A2B])
    out = []
    for entry in workload["templates"]:
        template = load_query(root, entry["query"])
        for k, fixed in enumerate(entry.get("warm", [{}])):
            vals = {}
            for name, spec in sorted(entry.get("slots", {}).items()):
                spec = dict(spec, **fixed.get(name, {}))
                vals[name] = _gen_slot(spec, rng, 1, config)[0]
            query = fill(template["query"], vals)
            query["context"] = dict(
                query.get("context", {}),
                queryId=f"bench-{int(seed)}-warm-{entry['query']}-{k}")
            out.append({"template": entry["query"], "query": query,
                        "device": bool(template.get("device", True))})
    return out
