"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 3] [--fault-seeds 3] [--faults half_batch] \
        [--out readings.jsonl]

For every seed, in one process with one compiled program: the
program's first steps, then the plain reference, and the numbers that
``bench/run.py`` compares.  On the first ``--control-seeds`` seeds also
the control (the reference computed in float8, in the program's place)
and, on the first ``--fault-seeds``, the program with each fault of
``--faults`` planted (``bench/faults.py``).  A step that returns its
state unchanged reads 1 by construction and needs no run.  The
benchmark's own runs never run this.
"""

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench import faults, program, reference, traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bm, wl, cfg = bench_run.load_cell(args.workload)
    bench_run.enable_compile_cache()
    bench_run.chips_for(wl, require_chip=True)
    m = bench_run.model_numbers(cfg)
    workers = wl["chips"]
    rows = wl["batch"] * workers
    ref_kw = dict(total_dim=wl["rbd_dim"], base_seed=program.BASE_SEED,
                  lr=wl["lr"], rows=wl["reference_rows"], workers=workers,
        pass_rows=wl["reference_pass_rows"])
    leaf = bench_run.leaf_of(m, wl["rbd_dim"])
    progs = {}
    out = open(args.out, "a") if args.out else None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        table = traffic.markov_table(seed, m["vocab"], wl["markov_branch"])

        def host_batch(i, seed=seed, table=table):
            return traffic.batch(seed, i, table,
                                 accum=wl["grad_accum_steps"], rows=rows,
                                 seq=wl["seq"])

        def program_readings(fault=None):
            prog = progs.get(fault)
            if prog is None:
                build = (faults.build_with(fault) if fault
                         else program.build)
                prog = progs[fault] = build(
                    cfg["registry"], m, wl,
                    functools.partial(reference.init_leaf, m), seed,
                    workers=workers)
            else:
                prog.state = prog.make_state(seed)
            r = bench_run.first_steps(
                prog, seed, host_batch,
                lambda b: bench_run.feed(prog, wl, b))
            prog.state = None
            gc.collect()
            return r

        rec = {"seed": seed}
        t0 = time.perf_counter()
        readings = program_readings()
        rec["program_s"] = time.perf_counter() - t0
        planted = {f: program_readings(f) for f in args.faults.split(",")
                   if f and n < args.fault_seeds}
        batches = [host_batch(j) for j in range(bench_run.CHECK_STEPS)]
        t0 = time.perf_counter()
        ref = reference.train(m, seed, batches, **ref_kw)
        rec["reference_s"] = time.perf_counter() - t0
        rec["program"] = bench_run.compare(readings, ref, leaf)
        for f, r in planted.items():
            rec[f] = bench_run.compare(r, ref, leaf)
        if n < args.control_seeds:
            ctl = reference.train(m, seed, batches, control=True, **ref_kw)
            rec["control"] = bench_run.compare(ctl, ref, leaf)
        first = np.sqrt(np.bincount(leaf, weights=ref["first"] ** 2))
        rec["leaves_left_out"] = int(np.sum(first < 1e-3 * np.median(first)))
        rec["ref_loss"] = ref["loss"].tolist()
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
