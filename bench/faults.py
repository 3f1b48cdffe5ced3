"""Faults planted in the program, for the tests and the calibration that
show the check catches them.  Each is a drop-in for ``program.build``
whose step is broken underneath; the benchmark's runs never use them.

* ``unchanged``: the step returns its input state (the loss is still
  computed);
* ``half_batch``: half of every batch is left out, the mean taken over
  the rest;
* ``no_exchange``: the coordinate all-gather is left out, each worker
  applies only its own coordinates (``independent_bases`` cells).
"""

from __future__ import annotations

from bench import program


def _unchanged(step, workers):
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        return keep, step(state, batch)[1]

    return broken


def _half_batch(step, workers):
    import jax.numpy as jnp

    def broken(state, batch):
        # keep every worker's first half of its rows (the batch axis is
        # the data-sharded one, last but one)
        def cut(x):
            ax = x.ndim - 2
            n = x.shape[ax] // workers
            keep = [slice(None)] * x.ndim
            parts = []
            for k in range(workers):
                keep[ax] = slice(k * n, k * n + n // 2)
                parts.append(x[tuple(keep)])
            return jnp.concatenate(parts, axis=ax)

        return step(state, {k: cut(v) for k, v in batch.items()})

    return broken


def _no_exchange(step, workers):
    import jax
    import jax.numpy as jnp

    def local_only(x, axis_name, **_):
        k = jax.lax.axis_index(axis_name)
        return jnp.zeros((workers,) + x.shape, x.dtype).at[k].set(x)

    def broken(state, batch):
        orig = jax.lax.all_gather
        jax.lax.all_gather = local_only     # seen while the step traces
        try:
            return step(state, batch)
        finally:
            jax.lax.all_gather = orig

    return broken


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "no_exchange": _no_exchange}


def build_with(fault: str):
    def build(*a, workers: int, **k):
        prog = program.build(*a, workers=workers, **k)
        prog.step = FAULTS[fault](prog.step, workers)
        return prog

    return build
