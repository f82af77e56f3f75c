"""Plain float32 round of the paper's federated CNN, written from its
description: each worker runs SGD with momentum on its own shard (epochs
of shuffled minibatches), then the fog tier folds the workers' deltas from
the global model (rowwise int8 along the last axis, absmax/127 scales),
each fog cell takes its sample-weighted mean, the cloud takes the cells'
mean weighted by their samples, and that is the new global model.

Workers train one after another (`lax.map`, no vmap), convolutions at
`highest` precision.  It imports nothing of the program; minibatch order
comes from the same seeded keys the harness hands the program, through
`jax.random.permutation` as the paper's local loop shuffles.

`dtype=bfloat16` is the control: weights, momentum, images and
activations held in bfloat16, the step below the float32 the
configuration states.  `half_batch=True` plants a fault: each step's
gradient is the mean over the first half of its minibatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def forward(p, x, n_conv: int):
    for i in range(n_conv):
        x = lax.conv_general_dilated(
            x, p[f"conv{i}_w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + p[f"conv{i}_b"])
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    return x @ p["fc_w"] + p["fc_b"]


def loss(p, x, y, n_conv: int):
    logits = forward(p, x, n_conv).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - gold)


def local_train(p, x, y, key, *, epochs, batch, lr, momentum, n_conv,
                half_batch=False):
    n = x.shape[0]
    bs = min(batch, n)
    nb = max(n // bs, 1)
    use = bs // 2 if half_batch else bs
    m = jax.tree.map(jnp.zeros_like, p)
    grad = jax.grad(loss)
    for ekey in jax.random.split(key, epochs):
        order = jax.random.permutation(ekey, n)[:nb * bs].reshape(nb, bs)

        def step(b, pm):
            p, m = pm
            idx = order[b][:use]
            g = grad(p, x[idx], y[idx], n_conv)
            m = jax.tree.map(lambda a, b: (momentum * a + b).astype(a.dtype),
                             m, g)
            p = jax.tree.map(lambda a, b: (a - lr * b).astype(a.dtype), p, m)
            return p, m

        p, m = lax.fori_loop(0, nb, step, (p, m))
    return p


def q8(x):
    """Rowwise symmetric int8 along the last axis, dequantised."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-12)), -127, 127)
    return q * scale


@partial(jax.jit, static_argnames=("epochs", "batch", "lr", "momentum",
                                   "n_conv", "n_cells", "dtype",
                                   "half_batch"))
def round_(g, x, y, keys, cell_of, *, epochs, batch, lr, momentum, n_conv,
           n_cells, dtype=jnp.float32, half_batch=False):
    """(new global params, every worker's local params) of one round from
    global params g.  Workers have equal sample counts, so sample weights
    are uniform within a cell and the cloud weighs cells by size."""
    with jax.default_matmul_precision("highest"):
        gd = jax.tree.map(lambda a: a.astype(dtype), g)
        train = partial(local_train, epochs=epochs, batch=batch, lr=lr,
                        momentum=momentum, n_conv=n_conv,
                        half_batch=half_batch)
        local = lax.map(lambda a: train(gd, a[0].astype(dtype), a[1], a[2]),
                        (x, y, keys))
        size = jnp.zeros(n_cells).at[cell_of].add(1.0)

        def fold(lw, gw):
            base = gw.astype(jnp.float32)
            dq = q8(lw.astype(jnp.float32) - base)                # edge hop
            cell = jax.ops.segment_sum(dq, cell_of, n_cells)
            cell = cell / size.reshape((-1,) + (1,) * gw.ndim)
            fog = (base + cell).astype(dtype)
            dq2 = q8(fog.astype(jnp.float32) - base)              # cloud hop
            share = (size / size.sum()).reshape((-1,) + (1,) * gw.ndim)
            return (base + jnp.sum(share * dq2, axis=0)).astype(dtype)

        new = jax.tree.map(fold, local, gd)
    return new, local


@partial(jax.jit, static_argnames=("n_conv",))
def eval_loss(p, x, y, *, n_conv):
    with jax.default_matmul_precision("highest"):
        return loss(jax.tree.map(lambda a: a.astype(jnp.float32), p),
                    x, y, n_conv)
