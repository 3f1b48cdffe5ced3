"""The comparison that decides ``correct``, at a size a CPU test holds:
the reference restates the program's arithmetic; the control (the
reference in float8) and broken programs come out not correct under the
limits of ``qwen2-0.5b.rbd``."""

import json

import numpy as np
import pytest

from bench import faults, program, reference, traffic
from bench import run as bench_run

CELL = "qwen2-0.5b.rbd"
TINY = dict(name="tiny", registry="qwen2-0.5b", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
            qkv_bias=True, act="silu", tie_embeddings=True, rope_theta=1e6,
            norm_eps=1e-6)
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def cell():
    bm, wl, _ = bench_run.load_cell(CELL)
    wl = dict(wl, batch=2, seq=32, rbd_dim=64, reference_rows=1)
    return bm, wl


def _run(cell, build=program.build):
    bm, wl = cell
    return bench_run.run(CELL, wl, TINY, bm, seed=SEED, seconds=0.5,
                         trace=False, require_chip=False, build=build)


def test_state_is_the_programs_packing_of_the_weights(cell):
    """The packed buffer, written one leaf at a time from the seed, is
    bit for bit the program's own packing of the reference's weights,
    and its per-segment distance from them, with each leaf made again
    inside the call that reads it, is round-off before any step."""
    import functools

    import jax

    from repro.configs.base import RBDConfig, TrainConfig
    from repro.models import get_model
    from repro.train import step as steplib

    _, wl = cell
    m = bench_run.model_numbers(TINY)
    prog = program.build(TINY["registry"], m, wl,
                         functools.partial(reference.init_leaf, m), SEED,
                         workers=1)
    mc = program.model_config(TINY["registry"], m)
    model = get_model(mc)
    rbd = RBDConfig(enabled=True, total_dim=wl["rbd_dim"], backend="pallas",
                    packed="on")
    transform = steplib.make_transform(model, rbd)
    _, _, sub_opt = steplib.make_train_step(
        model, TrainConfig(model=mc, rbd=rbd), transform,
        axis_name="data", return_optimizer=True)
    flat = reference.init_params(m, SEED)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes),
        [flat[n] for n in program.leaf_names(shapes)])
    want = np.asarray(sub_opt.prepare_params(tree))
    np.testing.assert_array_equal(np.asarray(prog.state.params), want)
    base = prog.norms(np.zeros_like(want), SEED)   # ||theta_0||
    gap = program.segment_norms(prog, SEED)
    assert np.all(gap <= 1e-6 * np.maximum(base, np.median(base))), gap


def test_reference_restates_the_program_in_float32(cell):
    def build(reg, m, wl, *a, **k):
        return program.build(reg, dict(m, compute_dtype="float32"), wl, *a,
                             **k)

    res = _run(cell, build)
    assert all(c["value"] < 1e-4 for c in res["checks"].values()), res


def test_program_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(cell, fault):
    res = _run(cell, faults.build_with(fault))
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(cell):
    """The reference computed in float8 in the program's place."""
    _, wl = cell
    m = bench_run.model_numbers(TINY)
    table = traffic.markov_table(SEED, m["vocab"], wl["markov_branch"])
    batches = [traffic.batch(SEED, i, table, accum=1, rows=wl["batch"],
                             seq=wl["seq"]) for i in range(3)]
    kw = dict(total_dim=wl["rbd_dim"], base_seed=program.BASE_SEED,
              lr=wl["lr"], rows=wl["reference_rows"],
              pass_rows=wl["reference_pass_rows"])
    ref = reference.train(m, SEED, batches, **kw)
    ctl = reference.train(m, SEED, batches, control=True, **kw)
    nums = bench_run.compare(ctl, ref, bench_run.leaf_of(m, wl["rbd_dim"]))
    assert any(v > wl["limits"][k] for k, v in nums.items()), nums
    assert np.all(np.isfinite(ctl["loss"]))


FOUR = """
import json, sys
sys.path.insert(0, ".")
from bench import faults, program
from bench import run as bench_run
from bench.test_bench_correct import TINY, SEED
bm = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
wl = bench_run.load_json(bench_run.HERE, "workloads", "qwen2-0.5b.indep4.json")
wl = dict(wl, batch=2, seq=32, rbd_dim=64, reference_rows=1)
out = {}
for name, build in [("sound", program.build),
                    ("no_exchange", faults.build_with("no_exchange")),
                    ("half_batch", faults.build_with("half_batch"))]:
    res = bench_run.run("qwen2-0.5b.indep4", wl, TINY, bm, seed=SEED,
                        seconds=0.5, trace=False, require_chip=False,
                        build=build)
    out[name] = res["correct"]
print(json.dumps(out))
"""


def test_four_workers_faults_are_caught():
    """K=4 independent bases on four virtual CPU devices, with the
    traffic of the four-chip cell kept for a later benchmark
    (``bench/workloads/qwen2-0.5b.indep4.json``): the sound program is
    correct; leaving out the exchange or half the batch is not."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", FOUR], cwd=root, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False,
                   "half_batch": False}, out
