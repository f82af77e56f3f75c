"""The FL cells' inputs, made from the seed: non-IID synCIFAR shards and
the CNN's weights, both on the device in one jitted call each.

synCIFAR follows the program's `data/synthetic.py` (copied here, since a
later PR may change the program): ten fixed class prototypes
N(0.5, 0.35^2) of 32x32x3, each image its class prototype rolled by up to
two pixels each way plus N(0, 2^2) noise, clipped to [0, 1].  Each
worker's labels follow its own Dirichlet(alpha) draw over the classes
(Hsu et al. 2019, arXiv:1909.06335)."""
from __future__ import annotations

import math
import zlib

import numpy as np

NOISE = 2.0


def prototypes(c: dict) -> np.ndarray:
    hw, ch = c["image_size"], c["image_channels"]
    rng = np.random.default_rng(zlib.crc32(b"syncifar"))
    return rng.normal(0.5, 0.35, size=(c["num_classes"], hw, hw, ch)
                      ).astype(np.float32)


def labels(n_workers: int, per_worker: int, alpha: float, classes: int,
           seed: int) -> np.ndarray:
    """(W, S) int32: worker w's labels drawn from its Dirichlet mix."""
    rng = np.random.default_rng([seed, 4])
    mix = rng.dirichlet([alpha] * classes, size=n_workers)
    counts = rng.multinomial(per_worker, mix)
    lab = np.stack([np.repeat(np.arange(classes), k) for k in counts])
    return rng.permuted(lab, axis=1).astype(np.int32)


def images(key, lab, protos):
    """(W, S, hw, hw, ch) fp32 images for labels `lab`, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, lab, protos):
        k1, k2 = jax.random.split(key)
        flat = lab.reshape(-1)
        shift = jax.random.randint(k1, (flat.shape[0], 2), -2, 3)
        roll = jax.vmap(lambda i, s: jnp.roll(protos[i], (s[0], s[1]),
                                              axis=(0, 1)))
        x = roll(flat, shift)
        x = x + NOISE * jax.random.normal(k2, x.shape, jnp.float32)
        return jnp.clip(x, 0.0, 1.0).reshape(lab.shape + protos.shape[1:])

    return make(key, jnp.asarray(lab), jnp.asarray(protos))


def cnn_weights(c: dict, key, program_tree):
    """fp32 CNN weights: kernels N(0, 1/fan_in), biases N(0, 0.01^2).
    The program's abstract tree must have the same leaves and shapes."""
    import jax
    import jax.numpy as jnp
    from chipbench import flops
    shapes = flops.cnn_params(c)
    want = {k: tuple(v.shape) for k, v in program_tree.items()}
    if want != shapes:
        raise ValueError(f"program CNN differs from the file: {want}")
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for i, n in enumerate(names):
            z = jax.random.normal(jax.random.fold_in(key, i), shapes[n],
                                  jnp.float32)
            s = shapes[n]
            out[n] = (0.01 * z if len(s) == 1
                      else z / math.sqrt(math.prod(s[:-1])))
        return out

    return make(key)
