"""The benchmark's weights, its plain reference of COSTREAM's cost model, and
the comparison that decides ``correct``.

Nothing here imports the program.  The reference follows the paper's
Algorithm 1 (Sec. IV) on one structure at a time, with its real operators and
hosts and no padding, banding, merging or kernels:

  stage 0  h_v  = MLP_type(v)(x_v)           type-specific encoders
  stage 1  hosts absorb the sum of the operator states placed on them
  stage 2  operators absorb the state of their host
  stage 3  operators absorb the sum of their parents' states, in topological order
  readout  MLP_out(sum of every operator and host state)

where each update is ``h' = MLP(concat(h, message))``, operators updating
through their type's MLP of the ``op_upd`` bank in stages 2 and 3 alike.  The
featurization (paper Sec. IV-B, Tables I and II) is re-written here from the
paper's feature list, with its own table of log-scale bounds.

``forward`` runs in float32 in numpy on the host for the reference, and in
bfloat16 in JAX on the chip for the control (the precision below the float32
that the configuration states).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import structures as S

OP_TYPES = {"source": 0, "filter": 1, "aggregate": 2, "join": 3, "sink": 4}
N_OP_TYPES = 5
OP_FEATURES = 39
HW_FEATURES = 4
REGRESSION = ("throughput", "latency_p", "latency_e")

FILTER_FNS = ["<", ">", "<=", ">=", "!=", "startswith", "endswith"]
AGG_FNS = ["min", "max", "mean", "sum"]
DTYPES3 = ["int", "double", "string"]
DTYPES4 = ["int", "double", "string", "none"]

#: Log-scale feature bounds around the Table II ranges (min, max).
LOG_BOUNDS = {
    "cpu": (10.0, 3200.0),
    "ram_mb": (250.0, 128000.0),
    "bandwidth_mbps": (5.0, 40000.0),
    "latency_ms": (0.25, 640.0),
    "event_rate": (5.0, 102400.0),
    "tuple_width": (1.0, 40.0),
    "selectivity": (1e-4, 1.0),
    "window_count": (1.0, 2560.0),
    "window_time_s": (0.05, 64.0),
}


# -- featurization ------------------------------------------------------------------


def _lognorm(x: float, key: str) -> float:
    lo, hi = LOG_BOUNDS[key]
    return (math.log(max(float(x), 1e-12)) - math.log(lo)) / (math.log(hi) - math.log(lo))


def tuple_widths(struct: Dict) -> List[tuple]:
    """(width in, width out) per operator, derived along the data flow."""
    par = S.parents(struct)
    out: List[tuple] = [None] * len(par)
    for v in S.topological_order(struct):
        op = struct["ops"][v]
        pw = [out[p][1] for p in par[v]]
        if op["type"] == "source":
            w = float(op.get("n_int", 0) + op.get("n_double", 0) + op.get("n_string", 0))
            out[v] = (w, w)
        elif op["type"] == "join":
            out[v] = (sum(pw), sum(pw))
        elif op["type"] == "aggregate":
            out[v] = (pw[0], 2.0 if op.get("group_by_dtype", "none") != "none" else 1.0)
        else:  # filter, sink
            out[v] = (pw[0], pw[0])
    return out


def featurize(struct: Dict):
    """(operator features (n, 39), operator type ids (n,), host features (m, 4))."""
    widths = tuple_widths(struct)
    x = np.zeros((len(struct["ops"]), OP_FEATURES), dtype=np.float32)
    for i, op in enumerate(struct["ops"]):
        v = x[i]
        v[0] = _lognorm(max(widths[i][0], 1.0), "tuple_width")
        v[1] = _lognorm(max(widths[i][1], 1.0), "tuple_width")
        kind = op["type"]
        if kind == "source":
            v[2] = _lognorm(op["event_rate"], "event_rate")
            n = [op.get("n_int", 0), op.get("n_double", 0), op.get("n_string", 0)]
            v[3:6] = np.asarray(n) / max(sum(n), 1)
        if kind == "filter":
            v[6 + FILTER_FNS.index(op["filter_fn"])] = 1.0
            v[13 + DTYPES3.index(op["literal_dtype"])] = 1.0
            v[16] = _lognorm(op["selectivity"], "selectivity")
        if kind == "join":
            v[17 + DTYPES3.index(op["join_key_dtype"])] = 1.0
            v[16] = _lognorm(op["selectivity"], "selectivity")
        if kind == "aggregate":
            v[20 + AGG_FNS.index(op["agg_fn"])] = 1.0
            v[24 + DTYPES4.index(op["group_by_dtype"])] = 1.0
            v[28 + DTYPES3.index(op["agg_dtype"])] = 1.0
            v[16] = _lognorm(op["selectivity"], "selectivity")
        w = op.get("window")
        if w is not None:
            v[31 + (0 if w["wtype"] == "sliding" else 1)] = 1.0
            v[33 + (0 if w["policy"] == "count" else 1)] = 1.0
            if w["policy"] == "count":
                v[35] = _lognorm(w["size"], "window_count")
            else:
                v[36] = _lognorm(w["size"], "window_time_s")
            v[37] = w["slide_ratio"]
        v[38] = 1.0 if kind in S.STATEFUL else 0.0
    types = np.asarray([OP_TYPES[op["type"]] for op in struct["ops"]], dtype=np.int32)
    hw = np.asarray(
        [[_lognorm(h[k], k) for k in ("cpu", "ram_mb", "bandwidth_mbps", "latency_ms")]
         for h in struct["hosts"]],
        dtype=np.float32,
    )
    return x, types, hw


# -- weights --------------------------------------------------------------------------


def _mlp_sizes(d_in: int, n_layers: int, hidden: int, d_out: int) -> List[int]:
    return [d_in] + [hidden] * (n_layers - 1) + [d_out]


@partial(jax.jit, static_argnames=("model",))
def _init(key, model):
    """Every metric's ensemble in one call: metric -> params whose leaves
    carry a leading member axis."""
    model = dict(model)
    h, e = model["hidden"], model["members"]
    lo, hi = model["output_bias"]

    def glorot(k, shape):  # shape (..., fan_in, fan_out)
        return math.sqrt(2.0 / (shape[-2] + shape[-1])) * jax.random.normal(k, shape, jnp.float32)

    def mlp(k, sizes, lead):
        ks = jax.random.split(k, len(sizes) - 1)
        return {"layers": [
            {"w": glorot(kk, lead + (sizes[i], sizes[i + 1])),
             "b": jnp.zeros(lead + (sizes[i + 1],), jnp.float32)}
            for i, kk in enumerate(ks)
        ]}

    out = {}
    for m, k in zip(model["metrics"], jax.random.split(key, len(model["metrics"]))):
        ks = jax.random.split(k, 6)
        p = {
            "op_enc": mlp(ks[0], _mlp_sizes(OP_FEATURES, model["enc_layers"], h, h), (e, N_OP_TYPES)),
            "hw_enc": mlp(ks[1], _mlp_sizes(HW_FEATURES, model["enc_layers"], h, h), (e,)),
            "op_upd": mlp(ks[2], _mlp_sizes(2 * h, model["update_layers"], h, h), (e, N_OP_TYPES)),
            "hw_upd": mlp(ks[3], _mlp_sizes(2 * h, model["update_layers"], h, h), (e,)),
            "out": mlp(ks[4], _mlp_sizes(h, model["readout_layers"], h, 1), (e,)),
        }
        if m in REGRESSION:  # a cost of e^2..e^6 units, so answers are not clipped at 0
            p["out"]["layers"][-1]["b"] = jax.random.uniform(ks[5], (e, 1), jnp.float32, lo, hi)
        out[m] = p
    return out


def make_weights(model: Dict, seed: int) -> Dict[str, object]:
    """metric -> member-stacked params, made on the default device from ``seed``."""
    frozen = tuple((k, tuple(v) if isinstance(v, list) else v) for k, v in sorted(model.items()))
    key = jax.random.PRNGKey(np.uint32(seed % (1 << 32)))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) % (1 << 32)))
    return _init(key, frozen)


# -- forward ------------------------------------------------------------------------


def _layers(p, xp, dtype):
    return [(xp.asarray(l["w"], dtype), xp.asarray(l["b"], dtype)) for l in p["layers"]]


def _algorithm1(p, x_op, types, x_hw, place, par, order, xp, mm):
    """Raw outputs (members, B) of one member-stacked ensemble.

    ``x_op`` (n, 39), ``x_hw`` (m, 4), ``place`` (B, n, m) one-hot placements;
    ``types``, ``par`` and ``order`` describe the query.  ``xp`` is numpy or
    jax.numpy and ``mm`` their matrix product."""
    relu = lambda x: xp.maximum(x, 0)  # noqa: E731
    t = np.asarray(types)

    def shared(layers, x):  # x (E, B, k, d)
        for i, (w, b) in enumerate(layers):
            x = mm(x, w[:, None]) + b[:, None, None, :]
            x = relu(x) if i < len(layers) - 1 else x
        return x

    def bank(layers, x, ts):  # x (E, B, k, d), one type per row k
        x = xp.swapaxes(x, 1, 2)  # (E, k, B, d)
        for i, (w, b) in enumerate(layers):
            x = mm(x, w[:, ts]) + b[:, ts][:, :, None, :]
            x = relu(x) if i < len(layers) - 1 else x
        return xp.swapaxes(x, 1, 2)

    e = p["hw_enc"][0][0].shape[0]
    h_op0 = bank(p["op_enc"], xp.broadcast_to(x_op, (e, 1) + x_op.shape), t)  # (E, 1, n, h)
    h_hw0 = shared(p["hw_enc"], xp.broadcast_to(x_hw, (e, 1) + x_hw.shape))  # (E, 1, m, h)
    # stage 1: each host absorbs the states of the operators placed on it
    msg_hw = mm(xp.swapaxes(place, 1, 2), h_op0)  # (E, B, m, h)
    h_hw = shared(p["hw_upd"], xp.concatenate([xp.broadcast_to(h_hw0, msg_hw.shape), msg_hw], -1))
    # stage 2: each operator absorbs the state of its host
    msg_op = mm(place, h_hw)  # (E, B, n, h)
    h = bank(p["op_upd"], xp.concatenate([xp.broadcast_to(h_op0, msg_op.shape), msg_op], -1), t)
    # stage 3: each operator absorbs the sum of its parents' states, in topological order
    cols = [h[:, :, v] for v in range(h.shape[2])]
    for v in order:
        msg = cols[par[v][0]]
        for u in par[v][1:]:
            msg = msg + cols[u]
        x = xp.concatenate([cols[v], msg], -1)[:, :, None]
        cols[v] = bank(p["op_upd"], x, t[v : v + 1])[:, :, 0]
    pooled = sum(cols[1:], cols[0]) + xp.sum(h_hw, axis=2)  # readout: (E, B, h)
    out = pooled[:, :, None, :]
    for i, (w, b) in enumerate(p["out"]):
        out = mm(out, w[:, None]) + b[:, None, None, :]
        out = relu(out) if i < len(p["out"]) - 1 else out
    return out[:, :, 0, 0]


@partial(jax.jit, static_argnames=("types", "par", "order"))
def _jax_forward(params, x_op, x_hw, place, types, par, order):
    dtype = x_op.dtype
    p = {k: _layers(v, jnp, dtype) for k, v in params.items()}
    return _algorithm1(p, x_op, types, x_hw, place, par, order, jnp, jnp.matmul)


def forward(params: Dict, struct: Dict, assignments: np.ndarray, dtype=np.float32) -> Dict[str, np.ndarray]:
    """Raw outputs, metric -> (members, B): log1p(cost) for regression,
    logits for classification.  ``params``: metric -> member-stacked weights.
    In float32 the reference runs in numpy on the host; in any other
    ``dtype`` (the control) every weight, input and intermediate is held in
    it and the forward runs in JAX on the default device."""
    x_op, types, x_hw = featurize(struct)
    par = tuple(tuple(ps) for ps in S.parents(struct))
    order = tuple(v for v in S.topological_order(struct) if par[v])
    place = np.eye(len(struct["hosts"]), dtype=np.float32)[np.asarray(assignments)]  # (B, n, m)
    out = {}
    for metric, p in params.items():
        if dtype == np.float32:
            p = {k: _layers(v, np, np.float32) for k, v in p.items()}
            raw = _algorithm1(p, x_op, types, x_hw, place, par, order, np, np.matmul)
        else:
            raw = _jax_forward(p, jnp.asarray(x_op, dtype), jnp.asarray(x_hw, dtype),
                               jnp.asarray(place, dtype), tuple(int(t) for t in types), par, order)
        out[metric] = np.asarray(raw, dtype=np.float32)
    return out


# -- the comparison -------------------------------------------------------------------


def log1p_cost(raw: np.ndarray) -> np.ndarray:
    """An ensemble's regression answer from its raw outputs, in log1p space:
    the mean member cost, clipped at zero (paper Sec. IV-A)."""
    return np.log1p(np.clip(np.mean(np.expm1(raw.astype(np.float64)), axis=0), 0.0, None))


def votes(raw: np.ndarray) -> np.ndarray:
    """An ensemble's classification answer: the majority of member votes."""
    return (np.sum(raw > 0, axis=0) * 2 > raw.shape[0]).astype(np.int64)


class Comparison:
    """Served answers against the reference.

    ``answer_gap``: the widest gap, in log1p(cost), between a served
    regression answer and the reference's.  ``vote_mismatch``: classification
    answers that differ from the reference's majority, counted where no
    member's reference logit lies within ``margin`` of zero (a logit that
    close may flip on the rounding the limit allows)."""

    def __init__(self, margin: float):
        self.margin = margin
        self.answer_gap = 0.0
        self.vote_mismatch = 0
        self.n_regression = 0
        self.n_votes = 0
        self.n_votes_near_zero = 0
        self.worst = ""
        self._gaps: List[np.ndarray] = []

    def add(self, what: str, served: Dict[str, np.ndarray], raw: Dict[str, np.ndarray]) -> None:
        for m, ref in raw.items():
            have = np.asarray(served[m])
            if have.shape != ref.shape[1:]:
                raise ValueError(f"{what}/{m}: served shape {have.shape}, reference {ref.shape[1:]}")
            if m in REGRESSION:
                gap = np.abs(np.log1p(np.clip(have.astype(np.float64), 0.0, None)) - log1p_cost(ref))
                gap = np.where(np.isfinite(gap), gap, np.inf)
                self.n_regression += gap.size
                self._gaps.append(gap)
                i = int(np.argmax(gap))
                if gap[i] > self.answer_gap:
                    self.answer_gap, self.worst = float(gap[i]), f"{what}/{m}[{i}]"
            else:
                clear = np.all(np.abs(ref) > self.margin, axis=0)
                self.n_votes += int(clear.sum())
                self.n_votes_near_zero += int((~clear).sum())
                self.vote_mismatch += int(np.sum(clear & (have != votes(ref))))

    def summary(self) -> Dict[str, object]:
        gaps = np.concatenate(self._gaps) if self._gaps else np.zeros(1)
        return {
            "answer_gap": self.answer_gap,
            "worst": self.worst,
            "gap_p50": float(np.percentile(gaps, 50)),
            "gap_p99": float(np.percentile(gaps, 99)),
            "gap_mean": float(np.mean(gaps)),
            "vote_mismatch": self.vote_mismatch,
            "n_regression": self.n_regression,
            "n_votes": self.n_votes,
            "n_votes_near_zero": self.n_votes_near_zero,
        }


#: The control's precision: the one below the float32 the configuration states.
CONTROL_DTYPE = jnp.bfloat16


def _groups(items: Sequence) -> List[list]:
    by_struct: Dict[int, list] = {}
    for item in items:
        by_struct.setdefault(id(item[0]), []).append(item)
    return list(by_struct.values())


def control_items(params, items: Sequence) -> List[tuple]:
    """``items`` with the control's answers in place of the served ones: the
    reference computed in ``CONTROL_DTYPE`` on the default device, its
    answers formed as the program forms them, one per item."""
    out = []
    for group in _groups(items):
        struct = group[0][0]
        low = forward(params, struct, np.concatenate([a for _, a, _ in group]), CONTROL_DTYPE)
        off = 0
        for _, a, _ in group:
            part = {m: r[:, off : off + len(a)] for m, r in low.items()}
            out.append((struct, a, [{m: (np.expm1(log1p_cost(r)) if m in REGRESSION else votes(r))
                                     for m, r in part.items()}]))
            off += len(a)
    return out


def compare_all(params_host: Dict, items: Sequence, margin: float) -> Comparison:
    """Reference over ``items`` = [(struct, assignments, [served answers])]:
    one batched forward per structure, every answer of an item compared with
    the reference's answer for its assignments."""
    cmp = Comparison(margin)
    for group in _groups(items):
        struct = group[0][0]
        raw = forward(params_host, struct, np.concatenate([a for _, a, _ in group]))
        off = 0
        for _, a, answers in group:
            part = {m: r[:, off : off + len(a)] for m, r in raw.items()}
            for served in answers:
                cmp.add(struct["name"], served, part)
            off += len(a)
    return cmp
