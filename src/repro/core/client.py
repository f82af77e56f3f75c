"""FL worker: local training on a private data shard (paper SSIII-C.3).

Local training is a single jitted scan over (epochs x minibatches); the
worker never shares raw data, only the resulting weights -- the FL
invariant.  Used by the Tier-A simulator and the examples.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.trace import span


def softmax_xent(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def accuracy(logits, labels):
    return (jnp.argmax(logits, axis=-1) == labels).mean()


@dataclasses.dataclass
class LocalTrainer:
    """SGD-with-momentum local trainer for classifier models."""
    model: object                 # repro.models.Model
    lr: float = 0.05
    momentum: float = 0.9
    batch_size: int = 64

    def __post_init__(self):
        self._train = jax.jit(self._train_impl, static_argnames=("epochs",))
        self._eval = jax.jit(self._eval_impl)

        def cohort_impl(params, images, labels, keys, epochs):
            return jax.vmap(
                lambda im, la, k: self._train_impl(params, im, la, k,
                                                   epochs=epochs)
            )(images, labels, keys)

        self._train_cohort = jax.jit(cohort_impl,
                                     static_argnames=("epochs",))

        def finite_ok(tree):
            return jnp.all(jnp.stack(
                [jnp.all(jnp.isfinite(l.astype(jnp.float32)))
                 for l in jax.tree.leaves(tree)]))

        def finite_members(stacked):
            """(C,) per-member finiteness over a stacked cohort tree."""
            oks = [jnp.all(jnp.isfinite(l.astype(jnp.float32)),
                           axis=tuple(range(1, l.ndim)))
                   for l in jax.tree.leaves(stacked)]
            return jnp.all(jnp.stack(oks, axis=0), axis=0)

        self._finite_ok = jax.jit(finite_ok)
        self._finite_members = jax.jit(finite_members)

    def _loss(self, params, images, labels):
        logits, aux = self.model.apply(params, {"images": images},
                                       mode="train")
        return softmax_xent(logits, labels) + 0.01 * aux

    def _train_impl(self, params, images, labels, key, *, epochs: int):
        n = images.shape[0]
        bs = min(self.batch_size, n)
        nb = max(n // bs, 1)
        mom = jax.tree.map(jnp.zeros_like, params)

        def epoch_step(carry, ekey):
            params, mom = carry
            perm = jax.random.permutation(ekey, n)[: nb * bs].reshape(nb, bs)

            def batch_step(carry, idx):
                params, mom = carry
                g = jax.grad(self._loss)(params, images[idx], labels[idx])
                mom = jax.tree.map(lambda m, gg: self.momentum * m + gg, mom, g)
                params = jax.tree.map(lambda p, m: p - self.lr * m, params, mom)
                return (params, mom), None

            (params, mom), _ = jax.lax.scan(batch_step, (params, mom), perm)
            return (params, mom), None

        (params, mom), _ = jax.lax.scan(epoch_step, (params, mom),
                                        jax.random.split(key, epochs))
        return params

    def _eval_impl(self, params, images, labels):
        logits, _ = self.model.apply(params, {"images": images}, mode="train")
        return accuracy(logits, labels)

    def train(self, params, images, labels, key, epochs: int):
        return self._train(params, images, labels, key, epochs=int(epochs))

    def train_checked(self, params, images, labels, key, epochs: int):
        """`train` with the non-finite guard: a diverged local step (any
        NaN/Inf in the result) is SKIPPED -- the input params come back
        unchanged with ok=False so the caller can report the divergence
        (the server's quarantine counters; see server.note_divergence)
        instead of shipping poison to the aggregator."""
        new = self._train(params, images, labels, key, epochs=int(epochs))
        if bool(self._finite_ok(new)):
            return new, True
        return params, False

    def train_cohort(self, params, images, labels, keys, epochs: int):
        """Batched local training: ONE vmapped step over the cohort axis.

        images: (C, S, ...), labels: (C, S), keys: (C,) per-worker PRNG
        keys.  Returns params stacked over the cohort axis (C, ...) --
        member i equals `train(params, images[i], labels[i], keys[i])` up
        to vmap's reduction-order jitter (pinned by tests/test_cohort.py).
        The `flight.fl.train` span covers the dispatch, not the device's
        work.
        """
        with span("fl.train"):
            return self._train_cohort(params, jnp.asarray(images),
                                      jnp.asarray(labels), keys,
                                      epochs=int(epochs))

    def train_cohort_checked(self, params, images, labels, keys, epochs: int):
        """`train_cohort` with the per-member non-finite guard: diverged
        members are replaced by the unchanged input params and flagged
        False in the returned (C,) ok vector."""
        stacked = self.train_cohort(params, images, labels, keys, epochs)
        oks = np.asarray(self._finite_members(stacked))
        if not oks.all():
            bad = ~oks
            stacked = jax.tree.map(
                lambda s, p: jnp.where(
                    jnp.asarray(bad).reshape((-1,) + (1,) * p.ndim),
                    p[None], s), stacked, params)
        return stacked, oks

    def evaluate(self, params, images, labels) -> float:
        return float(self._eval(params, images, labels))


@dataclasses.dataclass
class SimWorker:
    """One simulated worker: data shard + trainer + ground-truth profile."""
    wid: int
    images: np.ndarray
    labels: np.ndarray
    trainer: LocalTrainer
    profile: object               # WorkerProfile

    base_version: int = -1        # server version the local model is based on
    diverged: bool = False        # last local step hit the non-finite guard

    def local_train(self, params, key, epochs: int):
        if self.images.shape[0] == 0:
            return params
        new, ok = self.trainer.train_checked(
            params, jnp.asarray(self.images), jnp.asarray(self.labels),
            key, epochs)
        self.diverged = not ok
        return new
