"""The interval-loader cell's consumer: a two-layer GCN (Kipf & Welling)
trained with Adam, in plain JAX — the step the loader feeds in
``examples/temporal_gnn_train.py``, kept here so the consumer stays the
same whatever the program's model code becomes."""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp


def _forward(p, x, src, dst, emask):
    n = x.shape[0]
    deg = jax.ops.segment_sum(emask, dst, num_segments=n) + 1.0
    norm = jax.lax.rsqrt(deg)
    for i in range(2):
        h = x @ p[f"w{i}"]
        m = h[src] * (norm[src] * emask)[:, None]
        x = (jax.ops.segment_sum(m, dst, num_segments=n) * norm[:, None]
             + h * norm[:, None] ** 2 + p[f"b{i}"])
        if i == 0:
            x = jax.nn.relu(x)
    return x


def _loss(p, x, edge_index, emask, labels, lmask):
    logits = _forward(p, x, edge_index[0], edge_index[1], emask)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               labels[:, None], axis=1)[:, 0]
    return (nll * lmask).sum() / jnp.maximum(lmask.sum(), 1.0)


@partial(jax.jit, donate_argnums=(0, 1))
def _step(p, opt, x, edge_index, emask, labels, lmask, lr):
    loss, g = jax.value_and_grad(_loss)(p, x, edge_index, emask, labels,
                                        lmask)
    m, v, t = opt
    t = t + 1
    m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
    v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
    c1, c2 = 1 - 0.9 ** t, 1 - 0.999 ** t
    p = jax.tree.map(lambda w, a, b: w - lr * (a / c1)
                     / (jnp.sqrt(b / c2) + 1e-8), p, m, v)
    return p, (m, v, t), loss


@partial(jax.jit, static_argnums=(1, 2, 3))
def _init(key, d_in, hidden, classes):
    dims = [d_in, hidden, classes]
    p = {}
    for i, k in enumerate(jax.random.split(key, 2)):
        p[f"w{i}"] = (jax.random.normal(k, (dims[i], dims[i + 1]))
                      / jnp.sqrt(dims[i]))
        p[f"b{i}"] = jnp.zeros((dims[i + 1],))
    zeros = jax.tree.map(jnp.zeros_like, p)
    return p, (zeros, zeros, jnp.zeros((), jnp.float32))


class GCN:
    """Parameters made on the device from the seed; ``consume`` runs one
    step per snapshot of a loader batch."""

    def __init__(self, d_in: int, hidden: int, classes: int, lr: float,
                 seed: int) -> None:
        self.params, self.opt = _init(jax.random.key(seed % 2**32), d_in,
                                      hidden, classes)
        self.lr = jnp.float32(lr)
        self.losses: list[float] = []

    def consume(self, batch):
        """One step per snapshot; yields the monotonic time at which
        each step's loss reached the host."""
        for j in range(len(batch["times"])):
            self.params, self.opt, loss = _step(
                self.params, self.opt, batch["x"][j], batch["edge_index"],
                batch["edge_mask"][j], batch["labels"][j],
                batch["label_mask"][j], self.lr)
            self.losses.append(float(loss))
            yield time.monotonic()
