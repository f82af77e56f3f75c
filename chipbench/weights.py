"""Random weights of a dense decoder, made from the seed on the device in
one jitted call, in the type they are served in.

The leaf names are the ones the program's parameter tree uses
(`embed/tok`, `layers/attn/wq`, ...); `dense_decoder` checks the tree it
builds against the program's own abstract tree, so a program that renames
or reshapes a leaf stops the run instead of being fed the wrong tensor.
The plain reference reads the same names.

Scales keep a 40-layer residual stream of unit-size updates: matrices
N(0, 1/fan_in), the embedding N(0, 1), norm scales 1 + N(0, 0.1^2) and
QKV biases N(0, 0.5^2) (non-zero, so the bias path is exercised)."""
from __future__ import annotations

import math

#: fan-in axes of each matrix, counted without the stacked layer axis
FAN_IN = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
          "w_up": (0,), "w_gate": (0,), "w_down": (0,), "unembed": (0,)}


def decoder_shapes(c: dict) -> dict:
    """{path: shape} of a dense decoder with QKV bias and gated MLP, from a
    configuration file's published keys."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, Hkv, V = c["num_attention_heads"], c["num_key_value_heads"], c["vocab_size"]
    Dh = d // H
    shapes = {
        "embed/tok": (V, d), "final_norm/scale": (d,),
        "layers/ln1/scale": (L, d), "layers/ln2/scale": (L, d),
        "layers/attn/wq": (L, d, H, Dh), "layers/attn/wk": (L, d, Hkv, Dh),
        "layers/attn/wv": (L, d, Hkv, Dh), "layers/attn/wo": (L, H, Dh, d),
        "layers/mlp/w_up": (L, d, f), "layers/mlp/w_gate": (L, d, f),
        "layers/mlp/w_down": (L, f, d),
    }
    if c.get("qkv_bias"):
        shapes.update({"layers/attn/bq": (L, H, Dh),
                       "layers/attn/bk": (L, Hkv, Dh),
                       "layers/attn/bv": (L, Hkv, Dh)})
    if not c.get("tie_word_embeddings"):
        shapes["embed/unembed"] = (d, V)
    return shapes


def _leaf(key, path: str, shape, dtype):
    import jax
    import jax.numpy as jnp
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        x = 1.0 + 0.1 * z
    elif name in ("bq", "bk", "bv"):
        x = 0.5 * z
    elif name == "tok":
        x = z
    else:
        stacked = path.startswith("layers/")
        dims = shape[1:] if stacked else shape
        fan_in = math.prod(dims[a] for a in FAN_IN[name])
        x = z / math.sqrt(fan_in)
    return x.astype(dtype)


def make(shapes: dict, key, dtype):
    """{path: array}: every leaf from its own fold of `key`, in one jit
    (XLA fuses each draw into its cast, so no fp32 copy is kept)."""
    import jax
    paths = sorted(shapes)

    def build(key):
        return {p: _leaf(jax.random.fold_in(key, i), p, shapes[p], dtype)
                for i, p in enumerate(paths)}

    return jax.jit(build)(key)


def nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}."""
    out: dict = {}
    for path, x in flat.items():
        node = out
        *heads, last = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = x
    return out


def dense_decoder(c: dict, key, program_tree):
    """The weights as the program's nested tree.  `program_tree` is the
    program's abstract parameter tree (jax.eval_shape of its init); any
    difference in paths, shapes or dtypes is an error."""
    import jax
    want = {"/".join(str(k.key) for k in path): (tuple(l.shape), l.dtype)
            for path, l in jax.tree_util.tree_flatten_with_path(
                program_tree)[0]}
    shapes = decoder_shapes(c)
    dtypes = {l[1] for l in want.values()}
    if len(dtypes) != 1:
        raise ValueError(f"program leaves have mixed dtypes {dtypes}")
    got = {p: (tuple(s), next(iter(dtypes))) for p, s in shapes.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"program parameter tree differs from the "
                         f"configuration file: {diff[:6]}")
    return nest(make(shapes, key, next(iter(dtypes))))
