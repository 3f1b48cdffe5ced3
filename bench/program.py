"""The system under test: the launcher's training step, built as
``repro.launch.train.run_training`` builds it for ``--mode sharedseed
--rbd-backend pallas --packed on`` on a (data, 1) mesh, but handed back
to the benchmark instead of run for a fixed number of steps.  One
departure: the step donates its input state (``run_training`` keeps the
old and the new parameter buffer alive together, which at
granite-34b's widths does not fit a 16 GB chip).  The weights are made
from the seed one leaf at a time inside the call that writes it into the
packed buffer in place, so the set-up holds no buffer but the state's.

This mirrors ``run_training``'s set-up, limited to that one path; it is
the only file of the benchmark that calls into the program's internals.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))


# the launcher's RBDConfig default: the basis schedule is the same in
# every run (a seed-dependent one would be a different program to compile)
BASE_SEED = 0


@dataclasses.dataclass
class Program:
    step: object          # jitted (state, batch) -> (state, metrics)
    state: object         # TrainState with the packed parameter buffer
    make_state: object    # seed -> TrainState, weights packed leaf by leaf
    norms: object         # (packed buffer, seed) -> per-segment norms
    batch_sharding: object
    leaves: tuple         # ((name, n_stack, size, packed offset, psize),)
    plan_strategy: str
    prng_impl: str
    q_packed: int
    seg_dims: np.ndarray  # d of each segment, packed order
    seg_sizes: np.ndarray  # Q of each segment, packed order


def model_config(registry: str, numbers: dict):
    """The program's ModelConfig: the registry entry with the
    configuration file's numbers written over it."""
    from repro.configs import get_config

    cfg = get_config(registry)
    fields = {f.name for f in dataclasses.fields(cfg)}
    return dataclasses.replace(
        cfg, **{k: v for k, v in numbers.items() if k in fields})


def leaf_names(tree) -> list:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(p, "key", p)) for p in path)
            for path, _ in flat]


def build(registry: str, numbers: dict, wl: dict, init_leaf, seed: int,
          *, workers: int) -> Program:
    """Build the step and its state from the benchmark's weights:
    ``init_leaf(key, name)`` makes one leaf and is traced into the calls
    that need it, one leaf at a time, so that no call holds the whole
    set of flat weights beside the packed buffer."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import RBDConfig, TrainConfig
    from repro.launch.mesh import make_host_mesh, manual_shard_map
    from repro.models import get_model
    from repro.train import step as steplib

    cfg = model_config(registry, numbers)
    model = get_model(cfg)
    rbd_cfg = RBDConfig(enabled=True, total_dim=wl["rbd_dim"],
                        granularity=wl["granularity"],
                        allocation=wl["allocation"],
                        normalization=wl["normalization"],
                        mode=wl["rbd_mode"], base_seed=BASE_SEED,
                        backend="pallas", packed="on",
                        prng_impl=wl["prng_impl"])
    n_accum = wl["grad_accum_steps"]
    tcfg = TrainConfig(model=cfg, rbd=rbd_cfg, learning_rate=wl["lr"],
                       steps=1, batch_size=wl["batch"], seq_len=wl["seq"],
                       grad_accum_steps=n_accum,
                       optimizer=wl["optimizer"])
    mesh = make_host_mesh(workers, 1)
    transform = steplib.make_transform(model, rbd_cfg)
    layout = transform.plan.packed()
    init_state, train_step, sub_opt = steplib.make_train_step(
        model, tcfg, transform, axis_name="data", k_workers=workers,
        return_optimizer=True)
    eplan = sub_opt.plan_execution()

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    names = leaf_names(shapes)
    repl = NamedSharding(mesh, P())
    leaves, off = [], 0
    for lp in transform.plan.leaves:
        psize = -(-lp.size // layout.pos_block) * layout.pos_block
        leaves.append((names[lp.leaf_idx], lp.n_stack, lp.size, off, psize))
        off += lp.n_stack * psize
    leaves = tuple(leaves)

    # The packed buffer is written one leaf at a time in place, in the
    # layout of ``sub_opt.prepare_params`` (each compartment zero-padded
    # to the position block); the rest of the state needs only the
    # shapes.  Each leaf exists only inside the call that writes it, as
    # the call's scratch: the buffers the set-up holds are the state's.
    @functools.partial(jax.jit, static_argnums=2, donate_argnums=0,
                       out_shardings=repl)
    def write_leaf(buf, key, j):
        name, n_stack, size, off, psize = leaves[j]
        x = init_leaf(key, name)
        for layer in range(n_stack):
            buf = jax.lax.dynamic_update_slice(
                buf, x[layer * size: (layer + 1) * size],
                (off + layer * psize,))
        return buf

    @functools.partial(jax.jit, out_shardings=repl)
    def empty_state():
        zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return steplib.TrainState(
            params=jnp.zeros((layout.q_packed,), jnp.float32),
            rbd_state=sub_opt.init_rbd_state(zeros),
            opt_state=sub_opt.init_opt_state(zeros),
            step=jnp.zeros((), jnp.int32), guard=())

    def make_state(seed):
        key = jax.random.PRNGKey(seed)
        state = empty_state()
        buf = state.params
        state = state._replace(params=None)
        for j in range(len(leaves)):
            buf = write_leaf(buf, key, j)
        return state._replace(params=buf)

    with mesh:
        state = make_state(seed)
        state_spec = jax.tree_util.tree_map(lambda _: P(), state)
        bspec = P(None, "data") if n_accum > 1 else P("data")
        metrics_spec = {"ce": P(), "aux": P(), "loss": P(),
                        "update_norm": P()}
        step = jax.jit(manual_shard_map(
            train_step, mesh=mesh,
            in_specs=(state_spec, {"tokens": bspec, "labels": bspec}),
            out_specs=(state_spec, metrics_spec),
            manual_axes=("data", "model")), donate_argnums=(0,))

    @functools.partial(jax.jit, static_argnums=2)
    def leaf_norms(theta, key, j):
        name, n_stack, size, off, psize = leaves[j]
        x = init_leaf(key, name)
        return jnp.sqrt(jnp.stack([jnp.sum(jnp.square(
            theta[off + layer * psize: off + layer * psize + size]
            - x[layer * size: (layer + 1) * size]))
            for layer in range(n_stack)]))

    def norms(theta, seed):
        key = jax.random.PRNGKey(seed)
        return np.concatenate([np.asarray(leaf_norms(theta, key, j))
                               for j in range(len(leaves))])

    return Program(
        step=step, state=state, make_state=make_state, norms=norms,
        batch_sharding=NamedSharding(mesh, bspec), leaves=leaves,
        plan_strategy=eplan.strategy, prng_impl=eplan.prng_impl,
        q_packed=layout.q_packed,
        seg_dims=np.asarray(layout.seg_dim), seg_sizes=np.asarray(
            layout.seg_size))


def segment_norms(prog: Program, seed: int):
    """Per-segment ||theta - theta_0|| of the program's packed buffer
    against the benchmark's initial weights for ``seed``, in packed
    order, one leaf of theta_0 at a time (it reads the buffer; the
    weights are the benchmark's)."""
    return prog.norms(prog.state.params, seed)
