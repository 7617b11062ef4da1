"""Deployment structures as plain data, and the candidate placements for them.

A structure is one streaming query on one heterogeneous cluster::

    {"name": str,
     "ops":   [{"type": "source" | "filter" | "join" | "aggregate" | "sink", ...}],
     "edges": [[u, v], ...],                      # data flow, op indices
     "hosts": [{"cpu", "ram_mb", "bandwidth_mbps", "latency_ms"}, ...]}

The configuration's file names the generator that makes them:

* ``synthetic``: COSTREAM's corpus generator (paper Sec. VI, Table II),
  re-written from the program's ``dsps/generator.py``: linear chains and
  left-deep 2-/3-way joins with optional filters and aggregations.
* ``catalog``: fixed query templates listed in the configuration's file, each
  with the event rates it may take (paper Table VI(B)).

Two generators of randomness keep runs comparable: ``shape_seed`` (fixed in
the traffic file) decides every size -- which template or query kind, how many
filters, whether an aggregation follows, how many hosts -- and ``seed`` (the
run's) decides every value: rates, widths, selectivities, windows, hardware.
Every seed therefore serves the same set of graph shapes with other features.

``to_program`` converts a structure into the program's ``Query``/``Cluster``
input types; nothing else of the program is used here.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

from chipbench.loadgen import largest_remainder

STATEFUL = ("join", "aggregate")


def _window(q: Dict, rng: np.random.Generator) -> Dict:
    policy = str(rng.choice(q["window_policies"]))
    wtype = str(rng.choice(q["window_types"]))
    sizes = q["window_size_count"] if policy == "count" else q["window_size_time"]
    lo, hi = q["slide_ratio"]
    return {
        "wtype": wtype,
        "policy": policy,
        "size": float(rng.choice(sizes)),
        "slide_ratio": float(rng.uniform(lo, hi)),
    }


def _loguniform(rng: np.random.Generator, lo_hi: Sequence[float]) -> float:
    return float(10.0 ** rng.uniform(*lo_hi))


def _synthetic_ops(kind: str, q: Dict, shape: np.random.Generator, rng: np.random.Generator):
    """One synthetic query: (ops, edges).  Sizes from ``shape``, values from ``rng``."""
    rates = q["event_rate"][kind]

    def source():
        width = int(rng.choice(q["tuple_widths"]))
        n_int, n_double, n_string = (int(x) for x in rng.multinomial(width, [1 / 3] * 3))
        return {"type": "source", "event_rate": float(rng.choice(rates)),
                "n_int": n_int, "n_double": n_double, "n_string": n_string}

    def filt():
        fn = str(rng.choice(q["filter_fns"]))
        lit = "string" if fn in ("startswith", "endswith") else str(rng.choice(["int", "double"]))
        return {"type": "filter", "filter_fn": fn, "literal_dtype": lit,
                "selectivity": _loguniform(rng, q["filter_sel_log10"])}

    def agg():
        gb = str(rng.choice(["int", "double", "string", "none"]))
        return {"type": "aggregate", "agg_fn": str(rng.choice(q["agg_fns"])),
                "group_by_dtype": gb, "agg_dtype": str(rng.choice(["int", "double"])),
                "window": _window(q, rng),
                "selectivity": _loguniform(rng, q["agg_sel_log10"]) if gb != "none" else 1.0}

    def join():
        return {"type": "join", "join_key_dtype": str(rng.choice(["int", "double", "string"])),
                "window": _window(q, rng), "selectivity": _loguniform(rng, q["join_sel_log10"])}

    ops: List[Dict] = []
    edges: List[List[int]] = []

    def add(op, parents=()):
        ops.append(op)
        for p in parents:
            edges.append([p, len(ops) - 1])
        return len(ops) - 1

    if kind == "linear":
        prev = add(source())
        for _ in range(int(q["linear_filters"])):
            prev = add(filt(), [prev])
        if shape.random() < q["agg_probability"]:
            prev = add(agg(), [prev])
        add({"type": "sink"}, [prev])
        return ops, edges
    n_streams = {"two_way": 2, "three_way": 3}[kind]
    counts = [int(k) for k in q["filter_count_p"]]
    probs = np.asarray([q["filter_count_p"][str(k)] for k in counts], dtype=np.float64)
    budget = int(shape.choice(counts, p=probs / probs.sum()))
    heads = []
    for _ in range(n_streams):
        head = add(source())
        if budget > 0 and shape.random() < 0.6:
            head = add(filt(), [head])
            budget -= 1
        heads.append(head)
    left = heads[0]
    for s in range(1, n_streams):
        left = add(join(), [left, heads[s]])
    if budget > 0 and shape.random() < 0.5:
        left = add(filt(), [left])
    if shape.random() < q["agg_probability"]:
        left = add(agg(), [left])
    add({"type": "sink"}, [left])
    return ops, edges


def _catalog_ops(template: Dict, rng: np.random.Generator):
    rates = {k: float(rng.choice(v)) for k, v in template["rates"].items()}
    ops = []
    for op in template["ops"]:
        op = dict(op)
        if isinstance(op.get("event_rate"), str):
            op["event_rate"] = rates[op["event_rate"]]
        ops.append(op)
    return ops, [list(e) for e in template["edges"]]


def _hosts(hw: Dict, n: int, rng: np.random.Generator) -> List[Dict]:
    return [
        {k: float(rng.choice(hw[k])) for k in ("cpu", "ram_mb", "bandwidth_mbps", "latency_ms")}
        for _ in range(n)
    ]


def make_pool(config: Dict, n: int, shape_seed: int, seed: int) -> List[Dict]:
    """``n`` structures of the configuration's deployment space, in
    popularity order (index 0 is the most popular under a skewed mix)."""
    shape = np.random.default_rng(shape_seed)
    rng = np.random.default_rng(seed)
    q, hw = config["queries"], config["hardware"]
    if config["generator"] == "synthetic":
        names = list(q["mix"])
        kinds = [k for k, c in zip(names, largest_remainder(list(q["mix"].values()), n)) for _ in range(c)]
    elif config["generator"] == "catalog":
        names = list(q)
        kinds = [k for k, c in zip(names, largest_remainder([1.0] * len(names), n)) for _ in range(c)]
    else:
        raise ValueError(f"unknown structure generator {config['generator']!r}")
    kinds = [kinds[i] for i in shape.permutation(n)]
    lo, hi = hw["n_hosts"]
    pool = []
    for i, kind in enumerate(kinds):
        n_hosts = int(shape.integers(lo, hi + 1))
        if config["generator"] == "synthetic":
            ops, edges = _synthetic_ops(kind, q, shape, rng)
        else:
            ops, edges = _catalog_ops(q[kind], rng)
        pool.append({"name": f"{kind}{i}", "ops": ops, "edges": edges,
                     "hosts": _hosts(hw, n_hosts, rng)})
    return pool


# -- graph helpers ------------------------------------------------------------------


def parents(struct: Dict) -> List[List[int]]:
    out: List[List[int]] = [[] for _ in struct["ops"]]
    for u, v in struct["edges"]:
        out[v].append(u)
    return out


def topological_order(struct: Dict) -> List[int]:
    par = parents(struct)
    done: List[int] = []
    seen = set()
    while len(done) < len(par):
        for v, ps in enumerate(par):
            if v not in seen and all(p in seen for p in ps):
                seen.add(v)
                done.append(v)
    return done


def source_to_sink_paths(struct: Dict) -> List[List[int]]:
    children: List[List[int]] = [[] for _ in struct["ops"]]
    for u, v in struct["edges"]:
        children[u].append(v)

    def walk(u):
        if not children[u]:
            return [[u]]
        return [[u] + p for v in children[u] for p in walk(v)]

    return [p for s, ps in enumerate(parents(struct)) if not ps for p in walk(s)]


# -- candidate placements ---------------------------------------------------------


def host_bins(struct: Dict, hw: Dict) -> np.ndarray:
    """Capability bin per host (paper Fig. 5: edge, workstation, cloud):
    thirds of the log cpu + ram + bandwidth score over the hardware grid."""
    lo = sum(math.log(min(hw[k])) for k in ("cpu", "ram_mb", "bandwidth_mbps"))
    hi = sum(math.log(max(hw[k])) for k in ("cpu", "ram_mb", "bandwidth_mbps"))
    score = np.asarray(
        [sum(math.log(h[k]) for k in ("cpu", "ram_mb", "bandwidth_mbps")) for h in struct["hosts"]]
    )
    t = (score - lo) / max(hi - lo, 1e-9)
    return np.minimum((t * 3).astype(np.int64), 2)


def valid_rows(struct: Dict, bins: np.ndarray, a: np.ndarray) -> np.ndarray:
    """Paper Fig. 5's rules per row: bins never decrease along the data flow,
    and on every source-to-sink path a host once left is never returned to."""
    ok = np.ones(len(a), dtype=bool)
    for u, v in struct["edges"]:
        ok &= bins[a[:, u]] <= bins[a[:, v]]
    for path in source_to_sink_paths(struct):
        hosts = a[:, path]
        for j in range(2, len(path)):
            # a move at j onto the host of an earlier, already left position
            moved = hosts[:, j] != hosts[:, j - 1]
            for i in range(j - 1):
                ok &= ~(moved & (hosts[:, j] == hosts[:, i]))
    return ok


def sample_candidates(
    struct: Dict, hw: Dict, k: int, rng: np.random.Generator, colocation: float = 0.4
) -> np.ndarray:
    """``k`` valid placements, ``(k, n_ops)`` host indices, drawn with
    replacement: each operator in topological order goes to a random host of
    at least its parents' bin, or with probability ``colocation`` to a
    parent's host; rows that break Fig. 5's rules are drawn again."""
    bins = host_bins(struct, hw)
    par = parents(struct)
    order = topological_order(struct)
    n_hosts = len(struct["hosts"])
    out = np.zeros((0, len(par)), dtype=np.int64)
    while len(out) < k:
        n = 2 * (k - len(out)) + 16
        a = np.zeros((n, len(par)), dtype=np.int64)
        for v in order:
            min_bin = bins[a[:, par[v]]].max(axis=1) if par[v] else np.zeros(n, np.int64)
            pick = np.empty(n, dtype=np.int64)
            for b in np.unique(min_bin):
                allowed = np.flatnonzero(bins >= b)
                rows = min_bin == b
                pick[rows] = allowed[rng.integers(0, len(allowed), rows.sum())]
            if par[v]:
                via = np.asarray(par[v])[rng.integers(0, len(par[v]), n)]
                coloc = rng.random(n) < colocation
                pick = np.where(coloc, a[np.arange(n), via], pick)
            a[:, v] = pick
        assert a.max() < n_hosts
        out = np.concatenate([out, a[valid_rows(struct, bins, a)]])
    return out[:k]


# -- the program's input types ------------------------------------------------------


def to_program(struct: Dict):
    """(Query, Cluster) of the program for one structure."""
    from repro.dsps.hardware import Cluster, HardwareNode
    from repro.dsps.query import AggFn, DType, FilterFn, Operator, OpType, Query, WindowSpec

    ops = []
    for i, op in enumerate(struct["ops"]):
        kw = {"op_id": i, "op_type": OpType(op["type"])}
        for key in ("event_rate", "selectivity"):
            if key in op:
                kw[key] = float(op[key])
        for key in ("n_int", "n_double", "n_string"):
            if key in op:
                kw[key] = int(op[key])
        if "filter_fn" in op:
            kw["filter_fn"] = FilterFn(op["filter_fn"])
        if "agg_fn" in op:
            kw["agg_fn"] = AggFn(op["agg_fn"])
        for key in ("literal_dtype", "join_key_dtype", "group_by_dtype", "agg_dtype"):
            if key in op:
                kw[key] = DType(op[key])
        if "window" in op:
            kw["window"] = WindowSpec(**op["window"])
        ops.append(Operator(**kw))
    query = Query(operators=ops, edges=[tuple(e) for e in struct["edges"]], name=struct["name"])
    cluster = Cluster(nodes=[HardwareNode(node_id=i, **h) for i, h in enumerate(struct["hosts"])])
    return query.infer_widths(), cluster
