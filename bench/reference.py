"""Plain float32 reference of the benchmarked training step.

Imports nothing of the program.  It restates, in straightforward
``jax.numpy`` at ``Precision.HIGHEST``, what one optimizer step of the
benchmarked configuration computes:

* the decoder-only LM (RMSNorm with a ``1 + w`` scale, rotary
  embeddings on split halves, grouped-query causal attention, SwiGLU or
  tanh-GELU MLP, embeddings scaled by sqrt(d_model), tied or untied
  head) and its mean next-token cross entropy, with the gradient taken
  layer by layer so that it fits beside the weights;
* random-bases descent (Gressmann et al. 2020): every compartment c (a
  layer of a stacked leaf, or an unstacked leaf) draws a d_c x Q_c
  Gaussian basis B from a counter-based Threefry-2x32 stream keyed by
  (step, leaf, layer[, worker]); the update is
  theta_c -= lr / Q_c * B^T (B g_c), with d_c allocated in proportion to
  Q_c, and with K independent workers
  theta_c -= lr / (K Q_c) * sum_k B_k^T (B_k g_{c,k}).

The weights come from :func:`init_params` (the benchmark's own seeded
init, which the program is given too).  ``control`` switches the model's
compute to float8 (e4m3, per-tensor scaled, both directions): the
precision below the bfloat16 the configuration computes in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------


def param_shapes(m: dict) -> dict:
    """{leaf name: shape}, in sorted-name order (the order compartments
    and their seed tags are numbered in)."""
    n, d, ff, v = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    qd, kvd = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    s = {
        "embed": (v, d),
        "final_norm": (d,),
        "layers/attn/wk": (n, d, kvd),
        "layers/attn/wo": (n, qd, d),
        "layers/attn/wq": (n, d, qd),
        "layers/attn/wv": (n, d, kvd),
        "layers/ln1": (n, d),
        "layers/ln2": (n, d),
        "layers/mlp/w_down": (n, ff, d),
        "layers/mlp/w_up": (n, d, ff),
    }
    if m["qkv_bias"]:
        s.update({"layers/attn/bq": (n, qd), "layers/attn/bk": (n, kvd),
                  "layers/attn/bv": (n, kvd)})
    if m["act"] == "silu":
        s["layers/mlp/w_gate"] = (n, d, ff)
    if not m["tie_embeddings"]:
        s["lm_head"] = (d, v)
    return dict(sorted(s.items()))


def n_params(m: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(m).values())


def _init_leaf(key, name: str, shape, flat: bool = False):
    # Threefry's bits depend only on each element's flat index, so the
    # flat draw holds the same bits as the shaped one, in row-major order
    gen = (math.prod(shape),) if flat else shape
    if name == "embed":
        return 0.02 * jax.random.normal(key, gen, jnp.float32)
    if len(shape) == 1 or (name.startswith("layers/") and len(shape) == 2):
        return jnp.zeros(gen, jnp.float32)      # norm scales and biases
    return jax.random.normal(key, gen, jnp.float32) / np.float32(
        np.sqrt(shape[-2]))


def _init(items: tuple, key) -> dict:
    return {name: _init_leaf(jax.random.fold_in(key, i), name, shape)
            for i, (name, shape) in enumerate(items)}


@functools.lru_cache(maxsize=8)
def _init_fn(items: tuple):
    return jax.jit(functools.partial(_init, items))


def init_leaf(m: dict, key, name: str):
    """Leaf ``name`` of the benchmark's weights for PRNG ``key``,
    flattened: the bits :func:`init_params` gives it, in row-major
    order.  Traceable, for a caller that makes the weights one leaf at a
    time.  Drawn flat: on the chip a shaped draw flattened afterwards
    takes several copies of the leaf in scratch."""
    items = list(param_shapes(m).items())
    i = [n for n, _ in items].index(name)
    return _init_leaf(jax.random.fold_in(key, i), name, items[i][1],
                      flat=True)


def init_params(m: dict, seed: int) -> dict:
    """The benchmark's weights for ``seed``, made on the device in one
    jitted call.  The same executable runs every time, so a second call
    gives the same bits."""
    return _init_fn(tuple(param_shapes(m).items()))(
        jax.random.PRNGKey(seed))


def nest(flat: dict) -> dict:
    """{'a/b': x} -> {'a': {'b': x}}."""
    out: dict = {}
    for name, x in flat.items():
        *head, last = name.split("/")
        node = out
        for h in head:
            node = node.setdefault(h, {})
        node[last] = x
    return out


# --------------------------------------------------------------------------
# model
# --------------------------------------------------------------------------


def _fp8_round(x):
    """Per-tensor scaled float8 (e4m3) rounding, saturating at the
    format's largest value (448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = jnp.clip(x / s, -448.0, 448.0)
    return y.astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _fp8_round(x)


_fp8.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (_fp8_round(g),))


def _ident(x):
    return x


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta: float):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, rnd):
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    mask = np.tril(np.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return rnd(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST))


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _layer(m, p, x, rnd):
    b, s, _ = x.shape
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["d_head"]
    y = rnd(_rms(x, p["ln1"], m["norm_eps"]))
    q, k, v = (_mm(y, p["attn"][w]) for w in ("wq", "wk", "wv"))
    if m["qkv_bias"]:
        q, k, v = q + p["attn"]["bq"], k + p["attn"]["bk"], v + p["attn"]["bv"]
    q = rnd(_rope(rnd(q).reshape(b, s, h, hd), m["rope_theta"]))
    k = rnd(_rope(rnd(k).reshape(b, s, kv, hd), m["rope_theta"]))
    v = rnd(v).reshape(b, s, kv, hd)
    o = _attention(q, k, v, rnd).reshape(b, s, h * hd)
    x = rnd(x + rnd(_mm(o, p["attn"]["wo"])))
    y = rnd(_rms(x, p["ln2"], m["norm_eps"]))
    up = rnd(_mm(y, p["mlp"]["w_up"]))
    if m["act"] == "silu":
        hid = jax.nn.silu(rnd(_mm(y, p["mlp"]["w_gate"]))) * up
    else:
        hid = _gelu_tanh(up)
    return rnd(x + rnd(_mm(rnd(hid), p["mlp"]["w_down"])))


def _layer_fn(m, control):
    rnd = _fp8 if control else _ident

    def f(lp, x):
        lp = nest({k.split("/", 1)[1]: rnd(v) for k, v in lp.items()})
        return _layer(m, lp, x, rnd)

    return f, rnd


def _head_loss(m, control):
    rnd = _fp8 if control else _ident

    def f(x, final_norm, head, labels):
        x = rnd(_rms(x, rnd(final_norm), m["norm_eps"]))
        head = rnd(head)
        logits = _mm(x, head.T if m["tie_embeddings"] else head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))

    return f


@functools.lru_cache(maxsize=8)
def _fns(mkey: tuple, control: bool):
    m = dict(mkey)
    layer, rnd = _layer_fn(m, control)

    def embed(table, tokens):
        return rnd(rnd(table)[tokens] * np.float32(np.sqrt(m["d_model"])))

    def layer_vjp(lp, x, dy):
        _, pull = jax.vjp(layer, lp, x)
        dlp, dx = pull(dy)
        return dx, dlp

    def embed_grad(table, tokens, dx):
        _, pull = jax.vjp(lambda t: embed(t, tokens), table)
        return pull(dx)[0]

    head = jax.value_and_grad(_head_loss(m, control), argnums=(0, 1, 2))
    return (jax.jit(embed), jax.jit(layer), jax.jit(layer_vjp),
            jax.jit(head), jax.jit(embed_grad))


def backward(m: dict, params: dict, tokens, labels, sink, *, rows: int,
             control: bool = False) -> float:
    """Mean cross entropy of ``tokens`` (np (B, S)) and its gradient,
    layer by layer: ``sink(leaf, layer, grad)`` receives each
    compartment's gradient (layer None for unstacked leaves) as soon as
    it is complete, so the whole gradient never has to be held.  The
    head runs over blocks of ``rows`` rows."""
    embed, layer, layer_vjp, head, embed_grad = _fns(
        tuple(sorted(m.items())), control)
    n_tok = tokens.size
    tok = jnp.asarray(tokens)
    xs = [embed(params["embed"], tok)]
    lnames = [k for k in params if k.startswith("layers/")]
    for i in range(m["n_layers"]):
        xs.append(layer({k: params[k][i] for k in lnames}, xs[-1]))
    hw = params["embed"] if m["tie_embeddings"] else params["lm_head"]
    total, dxs, g_fn, g_head = 0.0, [], 0.0, 0.0
    inv = np.float32(1.0 / n_tok)
    for r in range(0, tokens.shape[0], rows):
        loss, (dx, gf, gh) = head(xs[-1][r:r + rows], params["final_norm"],
                                  hw, jnp.asarray(labels[r:r + rows]))
        total += float(loss)
        dxs.append(dx * inv)
        g_fn, g_head = g_fn + gf * inv, g_head + gh * inv
    dx = jnp.concatenate(dxs)
    del dxs
    sink("final_norm", None, g_fn)
    if not m["tie_embeddings"]:
        sink("lm_head", None, g_head)
        g_head = 0.0
    for i in reversed(range(m["n_layers"])):
        dx, dlp = layer_vjp({k: params[k][i] for k in lnames}, xs[i], dx)
        xs[i + 1] = None
        for k in lnames:
            sink(k, i, dlp[k])
        del dlp
    sink("embed", None, embed_grad(params["embed"], tok, dx) + g_head)
    return total / n_tok


# --------------------------------------------------------------------------
# random bases
# --------------------------------------------------------------------------

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1, xp=jnp):
    """Threefry-2x32, 20 rounds (Salmon et al. 2011), on uint32 arrays
    of ``xp`` (jax.numpy on the device, numpy on the host)."""
    u = functools.partial(xp.asarray, dtype=xp.uint32)
    k0, k1 = u(k0), u(k1)
    ks = (k0, k1, k0 ^ k1 ^ u(_PARITY))
    x0, x1 = u(x0) + k0, u(x1) + k1
    for grp in range(5):
        for i in range(4):
            x0 = x0 + x1
            x1 = _rotl(x1, _ROT[(4 * grp + i) % 8]) ^ x0
        x0 = x0 + ks[(grp + 1) % 3]
        x1 = x1 + ks[(grp + 2) % 3] + u(grp + 1)
    return x0, x1


def fold(*parts) -> np.uint32:
    """Fold integers into one uint32 seed by iterated Threefry (host)."""
    s = np.full(1, 0x243F6A88, np.uint32)
    for p in parts:
        p = np.full(1, p, np.uint32)
        a, b = threefry2x32(s, p, p ^ np.uint32(0x9E3779B9), s, xp=np)
        s = a ^ _rotl(b, 16)
    return s[0]


def _uniform(bits):
    top = (bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    return top * np.float32(2.0 ** -24) + np.float32(2.0 ** -25)


def basis_block(seed, n_rows: int, col0, n_cols: int):
    """All rows and columns col0.. of a compartment's Gaussian basis:
    element (r, j) is Box-Muller of Threefry((seed, seed ^ 0x85EBCA6B),
    (j, r ^ ~j))."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (n_rows, n_cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (n_rows, n_cols), 1) + col0
    seed = jnp.asarray(seed, jnp.uint32)
    b0, b1 = threefry2x32(seed, seed ^ jnp.uint32(0x85EBCA6B), c, r ^ ~c)
    rad = jnp.sqrt(-2.0 * jnp.log(_uniform(b0)))
    return rad * jnp.cos(np.float32(2.0 * np.pi) * _uniform(b1))


def allocate(m: dict, total_dim: int) -> dict:
    """{leaf: d per compartment}: largest-remainder allocation of
    ``total_dim`` in proportion to each leaf's size, at least 1, split
    evenly over a stacked leaf's layers, at most the compartment size."""
    shapes = param_shapes(m)
    names = list(shapes)
    sizes = np.array([math.prod(s) for s in shapes.values()], np.float64)
    raw = sizes / sizes.sum() * total_dim
    dims = np.maximum(np.floor(raw).astype(int), 1)
    deficit = total_dim - dims.sum()
    if deficit > 0:
        order = np.argsort(-(raw - np.floor(raw)))
        for i in range(deficit):
            dims[order[i % len(dims)]] += 1
    out = {}
    for name, budget in zip(names, dims):
        n_stack, q = _stack(name, shapes[name])
        out[name] = min(max(1, int(round(budget / n_stack))), q)
    return out


def _stack(name, shape):
    """(compartments, size of each) of a leaf."""
    if name.startswith("layers/"):
        return shape[0], math.prod(shape[1:])
    return 1, math.prod(shape)


def compartments(m: dict, total_dim: int):
    """[(leaf, layer or None, seed tag, d, Q)] in packed order."""
    dims = allocate(m, total_dim)
    out = []
    for tag, (name, shape) in enumerate(param_shapes(m).items()):
        n_stack, q = _stack(name, shape)
        for layer in range(n_stack):
            out.append((name, layer if name.startswith("layers/") else None,
                        tag, dims[name], q))
    return out


def seg_seed(base_seed: int, step: int, tag: int, layer, worker=None):
    s = fold(base_seed, step)
    if worker is not None:
        s = fold(s, worker + 1)
    s = fold(s, tag)
    return s if layer is None else fold(s, layer)


_CHUNK_ELEMS = 1 << 24


def _chunk(d: int, q: int) -> int:
    c = max(512, (_CHUNK_ELEMS // max(d, 1)) // 512 * 512)
    return min(c, -(-q // 512) * 512)


@functools.partial(jax.jit, static_argnames=("d",))
def project(seed, g, d: int):
    """u = B g for one compartment: g (Q,) -> (d,)."""
    q = g.shape[0]
    c = _chunk(d, q)
    n = -(-q // c)
    gp = jnp.pad(g, (0, n * c - q))

    def body(i, u):
        blk = basis_block(seed, d, (i * c).astype(jnp.uint32), c)
        gc = jax.lax.dynamic_slice(gp, (i * c,), (c,))
        return u + jnp.matmul(blk, gc, precision=HIGHEST)

    return jax.lax.fori_loop(0, n, body, jnp.zeros((d,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("q",))
def reconstruct(seed, s, q: int):
    """delta = s @ B for one compartment: s (d,) -> (Q,)."""
    d = s.shape[0]
    c = _chunk(d, q)
    n = -(-q // c)

    def body(i, out):
        blk = basis_block(seed, d, (i * c).astype(jnp.uint32), c)
        return jax.lax.dynamic_update_slice(
            out, jnp.matmul(s, blk, precision=HIGHEST), (i * c,))

    return jax.lax.fori_loop(0, n, body, jnp.zeros((n * c,), jnp.float32))[:q]


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_layer(leaf, layer, delta):
    return leaf.at[layer].add(delta.reshape(leaf.shape[1:]))


def _seg_view(x, layer):
    return (x if layer is None else x[layer]).reshape(-1)


def seg_norms(a: dict, b: dict, comps) -> np.ndarray:
    """Per-compartment ||a - b||."""
    return np.asarray([
        float(jnp.linalg.norm(_seg_view(a[name], layer)
                              - _seg_view(b[name], layer)))
        for name, layer, *_ in comps])


def train(m: dict, seed: int, batches, *, total_dim: int, base_seed: int,
          lr: float, rows: int, workers: int = 1, control: bool = False,
          pass_rows: int | None = None):
    """The first ``len(batches)`` random-bases SGD steps from
    :func:`init_params` (``seed``).  ``batches``: [(tokens, labels)], np
    int arrays (accum, workers * rows_per_worker, S); worker k takes the
    k-th block of rows, microbatches are averaged (the mean over all
    their rows, taken ``pass_rows`` rows per backward pass; the head
    ``rows`` at a time).

    Returns each step's loss (before its update), the per-compartment
    norm of the first update, and of the change after all the steps."""
    comps = compartments(m, total_dim)
    index = {(c[0], c[1]): i for i, c in enumerate(comps)}
    pass_rows = pass_rows or batches[0][0].shape[1]
    params = init_params(m, seed)
    losses, first = [], None
    for t, (tok, lab) in enumerate(batches):
        n_acc, n_rows, _ = tok.shape
        per = n_rows // workers
        coords = [[None] * len(comps) for _ in range(workers)]
        loss = 0.0
        if n_acc == 1:
            for k in range(workers):
                def sink(name, layer, g, k=k):
                    i = index[(name, layer)]
                    _, _, tag, d, _ = comps[i]
                    w = k if workers > 1 else None
                    coords[k][i] = project(
                        seg_seed(base_seed, t, tag, layer, w),
                        g.reshape(-1), d)

                rs = slice(k * per, (k + 1) * per)
                loss += backward(m, params, tok[0, rs], lab[0, rs], sink,
                                 rows=rows, control=control) / workers
        else:
            if workers != 1:
                raise ValueError("accumulation runs on one worker")
            acc = {}
            tok_all = tok.reshape(-1, tok.shape[-1])
            lab_all = lab.reshape(-1, lab.shape[-1])
            n_pass = tok_all.shape[0] // pass_rows

            def sink(name, layer, g):
                g = g / np.float32(n_pass)
                acc[(name, layer)] = g + acc.get((name, layer), 0.0)

            for a in range(0, tok_all.shape[0], pass_rows):
                loss += backward(m, params, tok_all[a:a + pass_rows],
                                 lab_all[a:a + pass_rows], sink, rows=rows,
                                 control=control) / n_pass
            for (name, layer), g in acc.items():
                i = index[(name, layer)]
                coords[0][i] = project(seg_seed(base_seed, t, comps[i][2],
                                                layer), g.reshape(-1),
                                       comps[i][3])
            del acc
        norms = []
        for i, (name, layer, tag, d, q) in enumerate(comps):
            delta = jnp.zeros((q,), jnp.float32)
            for k in range(workers):
                w = k if workers > 1 else None
                scale = coords[k][i] * np.float32(lr / (q * workers))
                delta = delta - reconstruct(
                    seg_seed(base_seed, t, tag, layer, w), scale, q)
            norms.append(float(jnp.linalg.norm(delta)))
            if layer is None:
                params[name] = params[name] + delta.reshape(
                    params[name].shape)
            else:
                params[name] = _add_layer(params[name], layer, delta)
        losses.append(loss)
        if first is None:
            first = np.asarray(norms)
    change = seg_norms(params, init_params(m, seed), comps)
    return {"loss": np.asarray(losses), "first": first, "change": change}
