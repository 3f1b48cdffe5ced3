"""Work counts, the reference's compartments against the program's plan,
and the harness's refusal to run without a chip."""

import os
import subprocess
import sys

import jax
import pytest

from bench import program, reference, work
from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = {"qwen2-0.5b": (494_032_768, 290), "granite-34b-2l":
           (1_362_130_944, 19)}


def _numbers(name):
    return bench_run.model_numbers(
        bench_run.load_json(bench_run.HERE, "configs", f"{name}.json"))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counted_parameters(name):
    assert reference.n_params(_numbers(name)) == CONFIGS[name][0]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_compartments_match_program_plan(name):
    """Leaf order, seed tags, sizes and allocated dims: the reference
    derives them on its own, the program from its plan."""
    from repro.configs.base import RBDConfig
    from repro.models import get_model
    from repro.train import step as steplib

    cfg = bench_run.load_json(bench_run.HERE, "configs", f"{name}.json")
    m = bench_run.model_numbers(cfg)
    model = get_model(program.model_config(cfg["registry"], m))
    plan = steplib.make_plan(model, RBDConfig(total_dim=256))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    names = program.leaf_names(shapes)
    assert names == list(reference.param_shapes(m))
    want = [(names[lp.leaf_idx], layer if lp.stacked else None, lp.seed_tag,
             lp.dim, lp.size)
            for lp in plan.leaves for layer in range(lp.n_stack)]
    comps = reference.compartments(m, 256)
    assert comps == want
    assert len(comps) == CONFIGS[name][1]


def test_sum_dq_and_kernel_work():
    m = _numbers("qwen2-0.5b")
    comps = reference.compartments(m, 256)
    dims, sizes = [c[3] for c in comps], [c[4] for c in comps]
    assert work.sum_dq(dims, sizes) == sum(d * q for d, q in zip(dims,
                                                                  sizes))
    ops, nbytes = work.project_work(dims, sizes, 10)
    assert ops == 2 * work.sum_dq(dims, sizes) and nbytes == 40
    ops4, nbytes4 = work.reconstruct_work(dims, sizes, 10, workers=4)
    assert ops4 == 4 * ops and nbytes4 == 80


def test_flops_per_token():
    m = _numbers("qwen2-0.5b")
    f = work.forward_flops_per_token(m, 512)
    # 2 * params of the matmuls, plus attention over the causal context
    dense = 2 * reference.n_params(m)
    assert 0.9 * dense < f < 1.3 * dense
    assert work.train_flops_per_token(m, 512) == 3 * f


def test_least_time_and_peaks():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_time(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_off_chip_run_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.rbd",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout
