"""A configuration, a traffic mix and a per-layer metric are added by new
files and new entries alone: no file the benchmark has is edited.  The new
cell is an open-loop refinement cell, so the readers kept for the refine
metrics run here too."""

import json
import shutil

from _chipbench_tiny import CPU_PEAKS, ROOT, tiny

from chipbench import bench

READER = '''"""Drains the service popped per second of the window."""


def read(ctx):
    return ctx.stats["n_batches"] / ctx.seconds
'''


def test_new_files_and_entries_make_a_new_cell(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes() for p in (tmp_path / "chipbench").rglob("*") if p.is_file()}

    cb = tmp_path / "chipbench"
    config = json.loads((cb / "configs" / "costream-synth.json").read_text())
    config["name"] = "costream-synth-copy"
    (cb / "configs" / "costream-synth-copy.json").write_text(json.dumps(config))
    mix = json.loads((cb / "traffic" / "refine-poisson-zipf.json").read_text())
    mix["popularity"] = {"kind": "uniform"}
    (cb / "traffic" / "refine-poisson-uniform.json").write_text(json.dumps(mix))
    (cb / "metrics" / "drains_per_s.py").write_text(READER)

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "costream-synth-copy", "source": "https://arxiv.org/abs/2403.08444",
                            "file": "chipbench/configs/costream-synth-copy.json", "reduced": [],
                            "why": "a copy"})
    spec["workloads"].append({"name": "copy-refine", "config": "costream-synth-copy",
                              "traffic": "refine-poisson-uniform", "chips": 1, "why": "a copy"})
    spec["end_to_end"].append({"name": "req_p50_ms", "unit": "ms", "better": "lower", "bound": 0.25,
                               "source": "host_clock", "workloads": ["copy-refine"]})
    layer = {"gen_lag_p99_ms": ("load generator", "req_p99_ms"),
             "queue_wait_ms.refine": ("service queue", "req_p99_ms"),
             "merged_share.refine": ("service routing", "req_p50_ms"),
             "forwards_per_drain.refine": ("estimator dispatch", "req_p50_ms"),
             "fwd_device_ms.refine": ("GNN forwards on the device", "req_p50_ms"),
             "device_idle_share.refine": ("device", "req_p50_ms"),
             "drains_per_s": ("service queue", "req_p99_ms")}
    for name, (where, moves) in layer.items():
        spec["per_layer"].append({"name": name, "unit": "1", "better": "lower", "source": "program_counter",
                                  "layer": where, "moves": moves, "workloads": ["copy-refine"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    r = bench.run_cell("copy-refine", 5, 1.5, True, root=tmp_path, overrides=tiny("synth-refine"),
                       peaks=CPU_PEAKS, log=lambda *a, **k: None)
    assert r["correct"], r["compared"]
    assert r["metrics"]["drains_per_s"]["value"] > 0
    assert set(r["metrics"]) == set(layer)
    after = {p.relative_to(tmp_path): p.read_bytes() for p in (tmp_path / "chipbench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items() if "__pycache__" not in k.parts)
