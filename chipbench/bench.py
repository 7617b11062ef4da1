"""One run of one cell: set up, drive the window, check the answers, reduce
the metrics.  ``run.py`` is the command line; the CPU tests call ``run_cell``.

Everything that belongs to a cell is found by name from ``BENCHMARK.json``:
the configuration's file (sizes, structure generator, limits), the traffic
mix's file ``chipbench/traffic/<traffic>.json`` and, for each metric, its
reader ``chipbench/metrics/<name>.py`` (or ``<name up to the first dot>.py``).
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from chipbench import flops as F
from chipbench import loadgen as L
from chipbench import reference as R
from chipbench import structures as S
from chipbench import trace as T

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent

#: How long past the window's close the drivers wait for answers.
ANSWER_DEADLINE_S = 60.0
#: Requests per closed-loop caller that the check can draw from.
MAX_CLOSED_REQUESTS = 1 << 17


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (unknown cell, device or file)."""


class Spec:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise BenchError(f"no configuration named {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return json.loads((self.root / "chipbench" / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
        return [m for m in self.data[kind] if cell in m.get("workloads", [cell])]


def reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` of a metric: ``metrics/<name>.py``, else the file of
    the name up to its first dot (one quantity split by what it moves)."""
    base = root / "chipbench" / "metrics"
    for path in (base / f"{name}.py", base / f"{name.split('.')[0]}.py"):
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"chipbench_metric_{path.stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError(f"no reader for metric {name!r} under {base}")


def load_peaks(kind: str, root: Path = ROOT) -> Dict:
    table = json.loads((root / "chipbench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table["devices"][kind]


class Meter:
    """Compiles (backend compiles and persistent-cache loads) since ``mark``.

    JAX's monitoring listeners are process-wide: registered on entry,
    removed on exit."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self._lock = threading.Lock()

    def __enter__(self) -> "Meter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self._COMPILE:
            with self._lock:
                self.compiles += 1

    def mark(self) -> None:
        with self._lock:
            self.compiles = 0


def build_models(model: Dict, weights: Dict):
    """metric -> (params, CostModelConfig), the program's serving input."""
    from repro.core.gnn import GNNConfig
    from repro.core.model import CostModelConfig

    gnn = GNNConfig(hidden=model["hidden"], enc_layers=model["enc_layers"],
                    update_layers=model["update_layers"], readout_layers=model["readout_layers"])
    return {
        m: (weights[m], CostModelConfig(metric=m, n_ensemble=model["members"], gnn=gnn))
        for m in model["metrics"]
    }


def popularity(traffic: Dict, n: int) -> np.ndarray:
    pop = traffic["popularity"]
    if pop["kind"] == "zipf":
        return 1.0 / np.arange(1, n + 1) ** float(pop["s"])
    if pop["kind"] == "uniform":
        return np.ones(n)
    raise BenchError(f"unknown popularity {pop}")


class Workload:
    """The structures of one run and the requests it sends, all from the seed."""

    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.config, self.traffic = config, traffic
        self.hw = config["hardware"]
        self.pool = S.make_pool(config, traffic["pool"], traffic["shape_seed"], seed)
        self.programs = [S.to_program(s) for s in self.pool]
        self.metrics = tuple(traffic["metrics"])

    def open_requests(self, rate: float, seconds: float, rng: np.random.Generator):
        """(schedule, structure index per request, candidates per request)."""
        sched = L.arrivals(self.traffic["arrivals"], rate, seconds, rng)
        n = len(sched)
        which = L.fixed_multiset(popularity(self.traffic, len(self.pool)), n, rng)
        lo, hi = self.traffic["cands"]
        sizes = lo + L.fixed_multiset(np.ones(hi - lo + 1), n, rng)
        cands: List[Optional[np.ndarray]] = [None] * n
        for i in np.unique(which):
            idx = np.flatnonzero(which == i)
            block = S.sample_candidates(self.pool[i], self.hw, int(sizes[idx].sum()), rng)
            off = np.concatenate([[0], np.cumsum(sizes[idx])])
            for j, r in enumerate(idx):
                cands[r] = block[off[j] : off[j + 1]]
        return sched, which, cands

    def closed_requests(self, rng: np.random.Generator):
        """A closed loop's requests: caller c's k-th request is structure
        ``orders[c][k % pool]`` with ``cands`` rows drawn afresh, from the
        caller's own stream, out of that structure's bank of ``cand_bank``
        valid placements, so no two requests are alike."""
        t = self.traffic
        banks = [S.sample_candidates(s, self.hw, t["cand_bank"], rng) for s in self.pool]
        orders = [rng.permutation(len(self.pool)) for _ in range(t["callers"])]
        streams = [np.random.default_rng(int(rng.integers(1 << 63))) for _ in range(t["callers"])]
        n = t["cands"][0]

        def request(c: int, k: int):
            i = int(orders[c][k % len(self.pool)])
            return i, banks[i][streams[c].integers(0, len(banks[i]), n)]

        return SimpleNamespace(orders=orders, request=request, kept={})

    def closed_structure(self, sent, c: int, k: int) -> int:
        return int(sent.orders[c][k % len(self.pool)])


def _snapshot(svc) -> Dict:
    return dict(vars(svc.stats))


def _delta(a: Dict, b: Dict) -> Dict:
    return {k: b[k] - a[k] for k in a if isinstance(a[k], (int, float)) and not isinstance(a[k], bool)}


class Session:
    """A warmed service for one cell and one seed: the weights, the
    structures, the program's estimator and ``PlacementService``."""

    def __init__(self, workload: str, seed: int, root: Path = ROOT, overrides: Optional[Dict] = None):
        from repro.serve import CostEstimator, DispatchPolicy, PlacementService

        spec = Spec(root)
        self.name, self.seed = workload, seed
        self.cell = spec.cell(workload)
        self.config = spec.config(self.cell["config"])
        self.traffic = spec.traffic(self.cell["traffic"])
        overrides = dict(overrides or {})
        self.config["model"].update(overrides.pop("model", {}))
        self.traffic.update(overrides)
        self.model = self.config["model"]
        self.closed = self.traffic["loop"] == "closed"
        self.rate = float(self.traffic.get("rate_rps", 0.0))
        self.policy = DispatchPolicy()  # pinned: no host profile on the machine changes a run
        self.weights = R.make_weights(self.model, seed)
        self.wl = Workload(self.config, self.traffic, seed)
        self.est = CostEstimator(build_models(self.model, self.weights), policy=self.policy)
        self.svc = PlacementService(self.est, auto_start=False, policy=self.policy)
        if self.traffic["warm_cands"]:
            # the deployment's warm-up, every candidate bucket up to max_batch: a drain
            # that grows behind any stall must not compile inside the window
            self.svc.warm(self.wl.programs, max_cands=self.traffic["warm_cands"], metrics=self.wl.metrics)
        self.svc.start()

    def submit(self, i: int, a: np.ndarray):
        q, c = self.wl.programs[i]
        return self.svc.submit_score(q, c, a, self.wl.metrics)

    def _closed_fire(self, sent, keep):
        def fire(c: int, k: int):
            i, a = sent.request(c, k)
            if keep(c, k):
                sent.kept[(c, k)] = (i, a)
            return self.submit(i, a)

        return fire

    def warm_in(self, rng: np.random.Generator, rate: Optional[float] = None) -> None:
        """One request per structure, then ``warm_in_s`` of the cell's own traffic."""
        t = self.traffic
        for i, s in enumerate(self.wl.pool):
            self.submit(i, S.sample_candidates(s, self.wl.hw, t["cands"][-1], rng)).result(ANSWER_DEADLINE_S)
        if self.closed:
            sent = self.wl.closed_requests(rng)
            L.run_closed_loop(self._closed_fire(sent, lambda c, k: False), t["callers"], t["warm_in_s"],
                              ANSWER_DEADLINE_S, keep=lambda c, k: False)
        else:
            sched, which, cands = self.wl.open_requests(rate or self.rate, t["warm_in_s"], rng)
            L.run_open_loop(lambda j: self.submit(which[j], cands[j]), sched, ANSWER_DEADLINE_S)

    def drive(self, seconds: float, rng: np.random.Generator, rate: Optional[float] = None,
              spans: bool = False, on_start=None,
              check_rng: Optional[np.random.Generator] = None) -> SimpleNamespace:
        """The window: what was sent, what came back, and the service's
        counters over it.  A closed loop keeps the requests and answers
        that ``check_rng`` draws, each request with chance ``check_share``."""
        stats0 = _snapshot(self.svc)
        if self.closed:
            sent = self.wl.closed_requests(rng)
            keep = lambda c, k: False  # noqa: E731
            if check_rng is not None:
                marks = [check_rng.random(MAX_CLOSED_REQUESTS) < self.traffic["check_share"]
                         for _ in range(self.traffic["callers"])]
                keep = lambda c, k: k < MAX_CLOSED_REQUESTS and bool(marks[c][k])  # noqa: E731
            outs = L.run_closed_loop(self._closed_fire(sent, keep), self.traffic["callers"], seconds,
                                     ANSWER_DEADLINE_S, spans=spans, on_start=on_start, keep=keep)
        else:
            sched, which, cands = self.wl.open_requests(rate or self.rate, seconds, rng)
            outs = L.run_open_loop(lambda j: self.submit(which[j], cands[j]), sched,
                                   ANSWER_DEADLINE_S, spans=spans, on_start=on_start)
            sent = SimpleNamespace(sched=sched, which=which, cands=cands)
        return SimpleNamespace(sent=sent, outs=outs, stats=_delta(stats0, _snapshot(self.svc)))

    def close(self) -> None:
        self.svc.close()
        self.svc = self.est = None

    def record(self, w: SimpleNamespace, seconds: float, check_rng: np.random.Generator):
        """What the window did: latencies, driver lag, requests attempted and
        failed, candidates and operations answered inside the window, and the
        items to compare with the reference: of a closed loop, the requests
        kept in the window, ``check_rows`` rows of each drawn from
        ``check_rng``; of an open loop, a sample drawn from ``check_rng``)."""
        wl, model = self.wl, self.model
        n_members = model["members"] * len(wl.metrics)
        rec = SimpleNamespace(flops=0, cands=0, lag=None, items=[])
        if self.closed:
            n = self.traffic["cands"][0]
            per_request = [F.request_flops(s, model, n, n_members) for s in wl.pool]
            for c, o in enumerate(w.outs):
                for k in range(len(o.scheduled_s)):
                    if o.failed[k]:
                        continue
                    if o.done_s[k] <= seconds:
                        rec.cands += n
                        rec.flops += per_request[wl.closed_structure(w.sent, c, k)]
                    if (c, k) in w.sent.kept:
                        i, a = w.sent.kept[(c, k)]
                        rows = np.sort(check_rng.choice(n, size=min(n, self.traffic["check_rows"]),
                                                        replace=False))
                        answer = {m: np.asarray(v)[rows] for m, v in o.answers[k].items()}
                        rec.items.append((wl.pool[i], a[rows], [answer]))
            rec.lat = np.concatenate([o.latencies_s() for o in w.outs])
            rec.failed = int(sum(o.failed.sum() for o in w.outs))
            rec.attempted = int(sum(len(o.scheduled_s) for o in w.outs))
        else:
            out, s = w.outs, w.sent
            ok = np.flatnonzero(~out.failed)
            in_window = ok[out.done_s[ok] <= seconds]
            rec.cands = int(sum(len(s.cands[r]) for r in in_window))
            rec.flops = sum(F.request_flops(wl.pool[s.which[r]], model, len(s.cands[r]), n_members)
                            for r in in_window)
            k = min(len(ok), self.traffic["check_requests"])
            pick = sorted(check_rng.choice(ok, size=k, replace=False)) if k else []
            rec.items = [(wl.pool[s.which[r]], s.cands[r], [out.answers[r]]) for r in pick]
            rec.lat, rec.lag = out.latencies_s(), out.lag_s()
            rec.failed, rec.attempted = int(out.failed.sum()), len(s.sched)
        # a request that never came is beyond every limit: as late as the wait
        rec.lat = np.where(np.isfinite(rec.lat), rec.lat, seconds + ANSWER_DEADLINE_S)
        return rec


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool = False,
    t_start: Optional[float] = None,
    root: Path = ROOT,
    overrides: Optional[Dict] = None,
    peaks: Optional[Dict] = None,
    control: bool = False,
    log=print,
) -> Dict:
    """One run; returns the result line (as a dict).  ``overrides`` replaces
    keys of the traffic mix and of the configuration's ``model`` (the CPU
    tests shrink them); ``peaks`` replaces the device's row of the peak
    table.  ``control`` puts the lower-precision control in the program's
    place: the answers compared are the control's, on the same requests, and
    ``correct`` is the control's; the result gains ``calibration``, the
    program's and the control's readings (the limits' calibration, never the
    benchmark's own runs)."""
    import jax

    from repro.serve.policy import use_policy

    t_start = time.perf_counter() if t_start is None else t_start
    spec = Spec(root)
    dev = jax.devices()[0]
    peaks = peaks if peaks is not None else load_peaks(dev.device_kind, root)
    wanted = spec.metrics(workload, "per_layer" if trace else "end_to_end")
    readers = {m["name"]: reader(m["name"], root) for m in wanted}
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    warm_rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    check_rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    traced = SimpleNamespace(t0=None, dir=None, stats0=None, stats1=None)

    with Meter() as meter:
        sess = Session(workload, seed, root, overrides)
        with use_policy(sess.policy):
            sess.warm_in(warm_rng)

            def on_start(t0: float) -> None:
                traced.t0 = t0  # the first timed request: set-up ends here
                if not trace:
                    return
                time.sleep(max(0.0, t0 + 0.3 * seconds - time.perf_counter()))
                traced.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(traced.dir, profiler_options=opts)
                traced.stats0 = _snapshot(sess.svc)
                with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
                    time.sleep(min(4.0, 0.4 * seconds))
                traced.stats1 = _snapshot(sess.svc)
                jax.profiler.stop_trace()

            meter.mark()
            w = sess.drive(seconds, rng, spans=trace, on_start=on_start, check_rng=check_rng)
            setup_s = traced.t0 - t_start
            compiles = meter.compiles
            mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            sess.close()

    rec = sess.record(w, seconds, check_rng)
    log(json.dumps({"info": "window", "workload": workload, "seed": seed, "seconds": seconds,
                    "attempted": rec.attempted, "failed": rec.failed, "candidates_answered": rec.cands,
                    "compiles_in_window": compiles, "compared_requests": sum(len(x[2]) for x in rec.items),
                    "setup_s": setup_s, "stats": w.stats}), flush=True)

    # -- correctness: the served answers against the reference, after the window ------
    limits = sess.config["limits"]
    served = {m: sess.weights[m] for m in sess.wl.metrics}
    host_weights = jax.device_get(served)
    t_ref = time.perf_counter()
    cmp = R.compare_all(host_weights, rec.items, margin=limits["answer_gap"])
    log(json.dumps({"info": "reference", "seconds": time.perf_counter() - t_ref, **cmp.summary()}),
        flush=True)
    calibration = None
    if control:
        ctl = R.compare_all(host_weights, R.control_items(served, rec.items), margin=limits["answer_gap"])
        calibration = {"program": cmp.summary(), "control": ctl.summary()}
        cmp = ctl
    compared = judge(cmp, rec.failed, w.stats, limits)
    correct = all(v <= lim for v, lim in compared.values()) and cmp.n_regression > 0

    # -- metrics ------------------------------------------------------------------------
    tr = None
    if traced.dir is not None:
        path = T.find(traced.dir)
        tr = T.read(path, host_ops=dev.platform == "cpu") if path else None
        shutil.rmtree(traced.dir, ignore_errors=True)
    ctx = SimpleNamespace(
        seconds=seconds, setup_s=setup_s, latencies_s=rec.lat, lag_s=rec.lag, closed=sess.closed,
        stats=w.stats, traced_stats=(_delta(traced.stats0, traced.stats1) if traced.stats1 else None),
        trace=tr, compiles_in_window=compiles, candidates_answered=rec.cands,
        flops_answered=rec.flops, peaks=peaks, model=sess.model,
    )
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": rec.attempted, "failed": rec.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_s() if tr else 0.0
        device["window_s"] = tr.window_s() if tr else 0.0
        if tr is not None:
            mods = sorted(tr.module_seconds().items(), key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {"device_ops": [[k, v] for k, v in mods],
                                   "idle_gaps": [[k, v] for k, v in tr.idle_gaps(10)]}
    if control:
        result["calibration"] = {**calibration, "trace": tr}
    result["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    return result


def judge(cmp: "R.Comparison", failed: int, stats: Dict, limits: Dict) -> Dict[str, tuple]:
    """The numbers that decide ``correct``: name -> (value, limit)."""
    summary = cmp.summary()
    return {
        "answer_gap": (cmp.answer_gap, limits["answer_gap"]),
        "answer_gap_p50": (summary["gap_p50"], limits["answer_gap_p50"]),
        "vote_mismatch": (cmp.vote_mismatch, 0),
        "unanswered": (failed, 0),
        "degraded": (int(stats.get("n_degraded", 0)) + int(stats.get("n_nonfinite", 0)), 0),
    }


def compared_lines(result: Dict) -> List[str]:
    """The numbers that decided ``correct``, each beside its limit."""
    return [f"compared {k} = {v['value']} (limit {v['limit']})" for k, v in result["compared"].items()]


def main_check_device(chips: int) -> Optional[str]:
    """Why this machine cannot run a cell on ``chips`` chips, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: the default device is {devs[0].platform}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX finds {len(devs)}"
    return None
