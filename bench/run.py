"""Benchmark of random-bases training on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell and its metrics; ``bench/workloads/<cell>.json`` holds the traffic
and the limits of the correctness check; ``bench/configs/<config>.json``
the model's numbers; ``bench/metrics/<metric>.py`` one reader per
per-layer metric.

A run builds the launcher's training step (``bench/program.py``) on the
benchmark's seeded weights, drives it through its first steps (the first
compiles), measures whole steps until ``--seconds`` have passed, then
frees the program and runs the plain reference (``bench/reference.py``)
over the same first steps.  The last line of standard output is one JSON
object; the numbers compared with the reference, each beside its limit,
are the last lines of standard error and the result's last key.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program, reference, traffic, work  # noqa: E402
from bench import trace as tracelib  # noqa: E402

CHECK_STEPS = 3


class NoChip(RuntimeError):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict]:
    """(benchmark entry of the cell, workload file, config file)."""
    bm = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    wl = load_json(HERE, "workloads", f"{name}.json")
    cfg = load_json(HERE, "configs", f"{cells[name]['config']}.json")
    return bm, wl, cfg


MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
              "vocab", "qkv_bias", "act", "tie_embeddings", "rope_theta",
              "norm_eps")


def model_numbers(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS}


def metrics_of(bm: dict, cell: str, kind: str) -> list:
    return [m for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(wl: dict, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < wl["chips"]):
        raise NoChip(f"need {wl['chips']} TPU chip(s), JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if len(devices) < wl["chips"]:
        raise NoChip(f"need {wl['chips']} devices, found {len(devices)}")
    return devices[: wl["chips"]]


@dataclasses.dataclass
class TraceContext:
    """What a per-layer reader reads."""
    trace: object
    steps: int
    window_s: float
    tokens_per_step: int
    chips: int
    peak: dict
    flops_per_token: float
    project_work: tuple
    reconstruct_work: tuple

    def mean_over_devices(self, fn) -> float:
        vals = [fn(ops) for ops in self.trace.devices.values()]
        return float(np.mean(vals)) if vals else 0.0


def peak_bytes(device) -> int:
    """The device's peak memory: the buffers it held at their peak and
    the scratch its programs reserved at theirs.  The TPU runtime books
    a program's temporaries as reserved, apart from the buffers in use,
    so the first alone would leave out the step's own working memory."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def leaf_of(m: dict, total_dim: int) -> np.ndarray:
    """Pytree leaf index of each compartment, in packed order."""
    names = [c[0] for c in reference.compartments(m, total_dim)]
    order = list(dict.fromkeys(names))
    return np.asarray([order.index(n) for n in names])


def gap(prog, ref, keep=None) -> float:
    """Worst leaf's |prog - ref| over the larger of its own and the
    median leaf's reference norm."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    den = np.maximum(ref, np.median(ref))
    g = np.abs(prog - ref) / np.maximum(den, 1e-30)
    if keep is not None:
        g = g[keep]
    return float(np.max(g))


def compare(readings: dict, ref: dict, leaf: np.ndarray) -> dict:
    """The numbers that decide ``correct``: the worst step's relative
    loss gap, and by the worst pytree leaf (a stacked leaf's layers
    together) the gap of the first update's norm and of the change
    after the last step.  Leaves whose first reference update is under a
    thousandth of the median leaf's move by round-off alone and are
    left out of the change."""
    def per_leaf(x):
        return np.sqrt(np.bincount(leaf, weights=np.square(x)))

    first, change = per_leaf(ref["first"]), per_leaf(ref["change"])
    keep = first >= 1e-3 * np.median(first)
    loss = np.abs(readings["loss"] - ref["loss"]) / np.abs(ref["loss"])
    return {"loss": float(np.max(loss)),
            "first_update": gap(per_leaf(readings["first"]), first),
            "change": gap(per_leaf(readings["change"]), change, keep)}


def feed(prog, wl: dict, b):
    """Host batch -> the step's device batch."""
    import jax

    tok, lab = b
    if wl["grad_accum_steps"] == 1:
        tok, lab = tok[0], lab[0]
    return jax.device_put({"tokens": tok, "labels": lab},
                          prog.batch_sharding)


def first_steps(prog, seed: int, host_batch, device_batch) -> dict:
    """Drive the program from its seeded state through its first
    ``CHECK_STEPS`` steps, through the window's own call and feed, and
    read what the check compares: each step's loss, the per-compartment
    norm of the first update and of the change after the last."""
    readings = {"loss": []}
    for i in range(CHECK_STEPS):
        prog.state, met = prog.step(prog.state, device_batch(host_batch(i)))
        readings["loss"].append(float(met["loss"]))
        if i in (0, CHECK_STEPS - 1):
            key = "first" if i == 0 else "change"
            readings[key] = program.segment_norms(prog, seed)
    readings["loss"] = np.asarray(readings["loss"])
    return readings


def run(cell: str, wl: dict, cfg: dict, bm: dict, *, seed: int,
        seconds: float, trace: bool, require_chip: bool = True,
        t_start: float = T_START, build=program.build) -> dict:
    import jax

    devices = chips_for(wl, require_chip)
    m = model_numbers(cfg)
    workers = wl["chips"]
    rows = wl["batch"] * workers
    tokens_per_step = wl["grad_accum_steps"] * rows * wl["seq"]

    table = traffic.markov_table(seed, m["vocab"], wl["markov_branch"])

    def host_batch(i):
        return traffic.batch(seed, i, table, accum=wl["grad_accum_steps"],
                             rows=rows, seq=wl["seq"])

    prog = build(cfg["registry"], m, wl,
                 functools.partial(reference.init_leaf, m), seed,
                 workers=workers)
    if (prog.plan_strategy, prog.prng_impl) != ("fused_packed",
                                                 wl["prng_impl"]):
        raise RuntimeError(f"plan {prog.plan_strategy}/{prog.prng_impl}, "
                           f"not fused_packed/{wl['prng_impl']}")

    def device_batch(b):
        return feed(prog, wl, b)

    readings = first_steps(prog, seed, host_batch, device_batch)
    state = prog.state
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    n, i = 0, CHECK_STEPS
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        # one step outside the window, so that the profiler's start-up
        # on the device does not fall into it
        state, met = prog.step(state, device_batch(host_batch(i)))
        jax.block_until_ready(met["loss"])
        i += 1
    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with ann("bench.window"):
        with ann("bench.data"):
            b = device_batch(host_batch(i))
        with ann("bench.dispatch"):
            state, met = prog.step(state, b)
        while True:
            i += 1
            with ann("bench.data"):
                b = device_batch(host_batch(i))
            with ann("bench.wait"):
                jax.block_until_ready(met["loss"])
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
            with ann("bench.dispatch"):
                state, met = prog.step(state, b)
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()

    peak = max(peak_bytes(d) for d in devices)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"attempted": i, "failed": 0}
    if trace:
        tr = tracelib.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = TraceContext(
            trace=tr, steps=n, window_s=tr.window[1] - tr.window[0],
            tokens_per_step=tokens_per_step, chips=len(devices),
            peak=work.peaks(kind),
            flops_per_token=work.train_flops_per_token(m, wl["seq"]),
            project_work=work.project_work(prog.seg_dims, prog.seg_sizes,
                                           prog.q_packed),
            reconstruct_work=work.reconstruct_work(
                prog.seg_dims, prog.seg_sizes, prog.q_packed,
                workers if wl["rbd_mode"] == "independent_bases" else 1))
        metrics = {}
        for spec in metrics_of(bm, cell, "per_layer"):
            v = load_reader(spec["name"]).read(ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": float(v),
                                         "unit": spec["unit"]}
        busy = ctx.mean_over_devices(tracelib.busy_s)
        device.update(busy_s=busy, window_s=ctx.window_s)
        ops0 = tr.devices[min(tr.devices)] if tr.devices else []
        result["breakdown"] = {
            "device_ops": tracelib.top_ops(ops0),
            "idle_gaps": tracelib.idle_gaps(ops0, tr.spans, tr.window)}
    else:
        e2e = {"tokens_per_s": n * tokens_per_step / window_s,
               "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        metrics = {spec["name"]: {"value": float(e2e[spec["name"]]),
                                  "unit": spec["unit"]}
                   for spec in metrics_of(bm, cell, "end_to_end")}

    # free the program before the reference runs on the same chip
    prog.state = state = met = b = None
    del prog
    gc.collect()
    ref = reference.train(
        m, seed, [host_batch(j) for j in range(CHECK_STEPS)],
        total_dim=wl["rbd_dim"], base_seed=program.BASE_SEED, lr=wl["lr"],
        rows=wl["reference_rows"], workers=workers,
        pass_rows=wl["reference_pass_rows"])
    nums = compare(readings, ref, leaf_of(m, wl["rbd_dim"]))
    # a cell compares the numbers its workload file gives a limit for
    checks = {k: {"value": nums[k], "limit": lim}
              for k, lim in wl["limits"].items()}
    result.update(correct=all(c["value"] <= c["limit"]
                              for c in checks.values()),
                  metrics=metrics, device=device, checks=checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bm, wl, cfg = load_cell(args.workload)
    enable_compile_cache()
    try:
        res = run(args.workload, wl, cfg, bm, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    print(json.dumps({k: res[k] for k in order if k in res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
