"""The paged decode attention kernel (kernels/paged_attention) against its
gather + dense reference, in interpret mode, the block write into the
stacked pool, and the paged serve loop driven through the kernel.

The kernel reads only the blocks each slot holds, at one layer of the
stacked head-major (L, NB, Hkv, BS, D) pool; the reference gathers every
table entry and masks by length.  They agree wherever a slot has
positions; a slot of length 0 (a free slot, whose output is not read)
gets zeros from the kernel."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.ref import paged_decode_attention_ref
from repro.models.layers import paged_kv_write

D = 128
LAYERS = 3       # stacked pool layers
LAYER = 2        # the layer read and written
NB = 40          # pool blocks
NBMAX = 6        # table entries per slot
FIXED_HEAD = 1   # the head whose argmax must agree exactly


def tables(lens, bs, share, rng):
    """Block tables as the host keeps them: distinct blocks for each
    slot's positions, zeros past them.  With `share`, slot 1 starts with
    slot 0's first two blocks (a shared prefix)."""
    bt = np.zeros((len(lens), NBMAX), np.int32)
    free = list(rng.permutation(NB))
    for b, n in enumerate(lens):
        k = -(-n // bs)
        bt[b, :k] = [free.pop() for _ in range(k)]
    if share:
        bt[1, :2] = bt[0, :2]
    return bt


CASES = {
    # lengths 0, 1, 15, 16, 17 and a slot filling its whole table
    "ragged": dict(lens=(0, 1, 15, 16, 17, NBMAX * 16), share=False),
    # slots 0 and 1 read the same first two blocks
    "shared_prefix": dict(lens=(40, 70, 3), share=True),
}


@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2), (20, 20)],
                         ids=["mha", "gqa4", "mha20"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_reference(case, heads, bs):
    H, Hkv = heads
    lens = [min(n, NBMAX * bs) for n in CASES[case]["lens"]]
    if case == "ragged":
        lens[-1] = NBMAX * bs
    rng = np.random.default_rng(len(lens) * bs + H)
    B = len(lens)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.bfloat16)
    pool = (LAYERS, NB, Hkv, bs, D)
    kp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
    bt = jnp.asarray(tables(lens, bs, CASES[case]["share"], rng))
    lengths = jnp.asarray(lens, jnp.int32)
    layer = jnp.int32(LAYER)
    got = np.asarray(ops.paged_decode_attention(q, kp, vp, layer, bt,
                                                lengths, impl="pallas"),
                     np.float32)
    want = np.asarray(paged_decode_attention_ref(q, kp, vp, layer, bt,
                                                 lengths), np.float32)
    live = np.asarray(lens) > 0
    assert np.abs(got[live] - want[live]).max() <= 2e-2
    np.testing.assert_array_equal(got[live, 0, FIXED_HEAD].argmax(-1),
                                  want[live, 0, FIXED_HEAD].argmax(-1))
    assert not got[~live].any()


def write_oracle(pool, layer, bt, new, positions):
    """The write one row at a time, in numpy: row (b, c) lands at
    [layer, bt[b, p // BS], :, p % BS] for its position p >= 0."""
    out = np.array(pool)
    bs = pool.shape[3]
    for b, c in zip(*np.nonzero(np.asarray(positions) >= 0)):
        p = int(positions[b, c])
        out[layer, bt[b, p // bs], :, p % bs] = np.asarray(new[b, c])
    return out


WRITES = {
    # decode: three live slots at offsets 0, 7, 15 of their blocks, one
    # free slot (-1) whose write is dropped
    "decode": dict(bs=16, positions=[[16], [-1], [39], [15]]),
    # a block-aligned 32-token chunk over two whole blocks
    "chunk": dict(bs=16, positions=[list(range(32, 64))]),
    # a tail bucket of 8 rows, 5 valid, inside one block of 16
    "tail_in_a_block": dict(bs=16,
                            positions=[list(range(48, 53)) + [-1] * 3]),
    # a 32-row bucket with 20 valid rows: the second block is written
    # partly, and a third block the rows could span is not touched
    "tail_over_blocks": dict(bs=16,
                             positions=[list(range(16, 36)) + [-1] * 12]),
    # two slots in one chunk step, one of them all padding
    "two_slots": dict(bs=8, positions=[list(range(8, 16)),
                                       [-1] * 8]),
}


@pytest.mark.parametrize("case", sorted(WRITES))
def test_block_write_lands_rows_and_leaves_the_rest(case):
    """paged_kv_write merges the new rows into whole blocks of one layer:
    every valid row lands at its block and offset, -1 rows are dropped,
    and every other byte of the pool -- other layers, other blocks, the
    untouched rows of a written block -- is unchanged."""
    bs = WRITES[case]["bs"]
    positions = np.asarray(WRITES[case]["positions"], np.int32)
    B, C = positions.shape
    Hkv = 3
    rng = np.random.default_rng(B * C + bs)
    shape = (LAYERS, NB, Hkv, bs, D)
    kp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    bt = np.stack([rng.permutation(NB)[:NBMAX] for _ in range(B)]
                  ).astype(np.int32)
    kk = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(B, C, Hkv, D)), jnp.float32)
    got_k, got_v = jax.jit(paged_kv_write)(kp, vp, jnp.int32(LAYER),
                                           jnp.asarray(bt), kk, vv,
                                           jnp.asarray(positions))
    for got, pool, new in ((got_k, kp, kk), (got_v, vp, vv)):
        want = write_oracle(pool, LAYER, bt, new.astype(jnp.bfloat16),
                            positions)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      want.astype(np.float32))
    # the oracle wrote something, so the comparison saw the rows move
    assert not np.array_equal(np.asarray(got_k, np.float32),
                              np.asarray(kp, np.float32))


def test_auto_keeps_the_reference_off_the_tpu(monkeypatch):
    """impl="auto" runs the kernel only on a TPU and where it fits."""
    calls = []
    monkeypatch.setattr(ops.kernel, "paged_decode_attention",
                        lambda *a, **k: calls.append(k) or a[0])
    q = jnp.zeros((2, 1, 4, D), jnp.bfloat16)
    kp = jnp.zeros((LAYERS, NB, 4, 16, D), jnp.bfloat16)
    bt = jnp.zeros((2, NBMAX), jnp.int32)
    lengths = jnp.ones((2,), jnp.int32)
    ops.paged_decode_attention(q, kp, kp, LAYER, bt, lengths)
    assert calls == []
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.paged_decode_attention(q, kp, kp, LAYER, bt, lengths)
    assert calls == [{"interpret": False}]
    for q_, kp_ in ((q[..., :64], kp[..., :64]),          # D % 128
                    (q, kp[:, :, :, :8]),                # BS % 16
                    (q, kp.astype(jnp.float32))):        # pool dtype
        ops.paged_decode_attention(q_, kp_, kp_, LAYER, bt, lengths)
    assert len(calls) == 1


def test_paged_loop_through_the_kernel_is_token_identical(monkeypatch):
    """A drain with mid-flight joins and a forced preemption (9 blocks x 8
    hold 72 positions for 3 x (>= 21 + 16)): the kernel path emits
    exactly the reference path's tokens.

    The weights are cast to f32, so both paths compute attention in f32
    and differ only in summation order.  With bf16 activations they round
    differently by design (the reference rounds the probabilities to bf16
    before p.V, the kernel keeps them in f32), which moves a near-tied
    argmax of this tiny model; the parity test above bounds that case."""
    from repro.configs import get_smoke_config
    from repro.launch.serve_loop import PagedServeLoop, Request
    from repro.models import build_model
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          model.init(jax.random.key(4)))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (21, 23, 22, 9)]

    def drain():
        loop = PagedServeLoop(model, params, max_batch=3, num_blocks=9,
                              block_size=8, chunk=16)
        for i, p in enumerate(prompts):
            loop.submit(Request(rid=i, prompt=p, max_new=16))
        done = loop.run_until_drained()
        assert loop.counters["preemptions"] >= 1
        return {r.rid: r.out for r in done}

    want = drain()
    monkeypatch.setattr(ops, "paged_decode_attention", functools.partial(
        ops.paged_decode_attention, impl="pallas"))
    assert drain() == want
