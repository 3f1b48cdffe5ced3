"""The harness finds every cell, configuration and per-layer metric by
name, and a new one needs only new files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BM = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_has_its_files(cell):
    bm, wl, cfg = bench_run.load_cell(cell)
    entry = {w["name"]: w for w in bm["workloads"]}[cell]
    assert wl["config"] == entry["config"] == cfg["name"]
    assert wl["chips"] == entry["chips"]
    assert wl["limits"] and set(wl["limits"]) <= {"loss", "first_update",
                                                  "change"}
    assert bench_run.metrics_of(bm, cell, "end_to_end")
    assert bench_run.metrics_of(bm, cell, "per_layer")


@pytest.mark.parametrize("spec", BM["configs"], ids=lambda s: s["name"])
def test_every_config_file_states_its_cut(spec):
    cfg = json.load(open(os.path.join(ROOT, spec["file"])))
    assert cfg["name"] == spec["name"] and cfg["source"] == spec["source"]
    assert cfg["reduced"] == spec["reduced"]
    assert all(k in cfg for k in bench_run.MODEL_KEYS)


@pytest.mark.parametrize("spec", BM["per_layer"], ids=lambda s: s["name"])
def test_every_per_layer_metric_has_a_reader(spec):
    mod = bench_run.load_reader(spec["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (spec["layer"], spec["unit"],
                                                spec["moves"])
    assert callable(mod.read)


WORKLOAD_FILES = sorted(os.listdir(os.path.join(ROOT, "bench", "workloads")))
READER_FILES = sorted(f for f in os.listdir(os.path.join(ROOT, "bench",
                                                          "metrics"))
                      if f.endswith(".py"))


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_every_workload_file_is_complete(name):
    """Also the traffic kept for cells not in ``BENCHMARK.json`` yet."""
    wl = bench_run.load_json(bench_run.HERE, "workloads", name)
    cfg = bench_run.load_json(bench_run.HERE, "configs",
                              f"{wl['config']}.json")
    assert name.startswith(wl["config"] + ".") and name.endswith(".json")
    assert cfg["name"] == wl["config"] and wl["chips"] in (1, 4)
    assert wl["limits"] and set(wl["limits"]) <= {"loss", "first_update",
                                                  "change"}
    assert wl["grad_accum_steps"] >= 1 and wl["batch"] % 2 == 0


@pytest.mark.parametrize("name", READER_FILES)
def test_every_reader_file_declares_its_metric(name):
    """Also the readers of metrics whose cells are not in
    ``BENCHMARK.json`` yet."""
    mod = bench_run.load_reader(name[:-3])
    assert mod.UNIT and mod.LAYER and mod.MOVES == "tokens_per_s"
    assert callable(mod.read)


def test_new_cell_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads(json.dumps(BM))
    bm["workloads"].append({"name": "qwen2-0.5b.new", "config": "qwen2-0.5b",
                            "traffic": "new", "chips": 1, "why": "a test"})
    bm["per_layer"].append({"name": "new_metric", "unit": "ms",
                            "better": "lower", "source": "device_trace",
                            "layer": "device", "moves": "tokens_per_s",
                            "workloads": ["qwen2-0.5b.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    wl = json.load(open(os.path.join(ROOT, "bench", "workloads",
                                     "qwen2-0.5b.rbd.json")))
    wl["seq"] = 1024
    (tmp_path / "bench" / "workloads" / "qwen2-0.5b.new.json").write_text(
        json.dumps(wl))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        'LAYER = "device"\nUNIT = "ms"\nMOVES = "tokens_per_s"\n\n\n'
        "def read(ctx):\n    return 1e3 * ctx.window_s / ctx.steps\n")
    probe = (
        "import sys; sys.path.insert(0, '.')\n"
        "from bench import run\n"
        "bm, wl, cfg = run.load_cell('qwen2-0.5b.new')\n"
        "names = [m['name'] for m in run.metrics_of(bm, 'qwen2-0.5b.new', "
        "'per_layer')]\n"
        "ctx = run.TraceContext(None, 4, 2.0, 1, 1, {}, 0.0, (), ())\n"
        "print(wl['seq'], cfg['name'], names[-1], "
        "run.load_reader(names[-1]).read(ctx))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["1024", "qwen2-0.5b", "new_metric", "500.0"]
