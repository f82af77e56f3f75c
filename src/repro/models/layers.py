"""Core neural layers (pure JAX, shardable, scan-friendly).

Conventions:
  * activations bf16, softmax/normalisation statistics fp32;
  * attention tensors are (batch, seq, heads, head_dim);
  * every layer is a pure function  f(params_subtree, x, ...) -> y;
  * sequence lengths are static; decode uses a cache + scalar position.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import constrain, mesh_axis_size
from repro.models import cache as kvcache
from repro.models.param import pdef

# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def norm_defs(cfg, kind=None):
    kind = kind or cfg.norm
    d = {"scale": pdef((cfg.d_model,), (None,), init="ones")}
    if kind == "layernorm":
        d["bias"] = pdef((cfg.d_model,), (None,), init="zeros")
    return d


def apply_norm(p, x, kind="rmsnorm", eps=1e-6):
    if "bias" in p:
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"], eps)


# --------------------------------------------------------------------------
# Activations
# --------------------------------------------------------------------------

def act_fn(name):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "gelu_plain": jax.nn.gelu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# --------------------------------------------------------------------------
# RoPE (full + partial/"2d" fraction, as in ChatGLM)
# --------------------------------------------------------------------------

def rope_apply(x, positions, theta=10_000.0, fraction=1.0):
    """x: (..., T, H, D); positions: (..., T) int32. Rotates first
    `fraction*D` dims, passes the rest through (ChatGLM partial rotary)."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # positions (..., T) -> (..., T, 1, half): broadcast over heads
    ang = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate(
        [y1.astype(x.dtype), y2.astype(x.dtype), x_pass], axis=-1
    )


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

# decode headroom appended to non-windowed prefill caches; the value (and
# every other cache convention) lives in models/cache.py
PREFILL_DECODE_MARGIN = kvcache.PREFILL_DECODE_MARGIN


def attention_full(q, k, v, *, causal=True, window=0, q_offset=0):
    """Exact attention with a materialised score matrix. Use for seq <= ~8k.

    q: (B,T,H,D)  k: (B,S,Hkv,D)  v: (B,S,Hkv,Dv).  GQA via head grouping.
    """
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, D)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(T) + q_offset
    kpos = jnp.arange(S)
    mask = jnp.ones((T, S), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(B, T, H, v.shape[-1])


def flash_attention_xla(q, k, v, *, causal=True, window=0, q_offset=0,
                        q_block=1024, kv_block=1024):
    """Memory-bounded blockwise attention (pure-XLA 'flash') with online
    softmax.  Never materialises (T,S) scores: peak extra memory is
    O(q_block * kv_block) per (batch, head).

    For sliding-window attention only ceil((window+q_block)/kv_block)+1 kv
    blocks are visited per q block (FLOPs proportional to the window).  For
    full causal attention the baseline visits the full rectangle with
    masking; the triangular schedule is a recorded perf iteration.
    """
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    q_block = min(q_block, T)
    kv_block = min(kv_block, S)
    assert T % q_block == 0 and S % kv_block == 0
    nq, nkv = T // q_block, S // kv_block
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, nq, q_block, Hkv, G, D)

    if window:
        n_win = (window + q_block + kv_block - 2) // kv_block + 1
        n_win = min(n_win, nkv)
    else:
        n_win = nkv

    kpos_all = jnp.arange(S)

    def q_step(_, qi):
        qblk, iq = qi  # (B,Cq,Hkv,G,D), scalar block index
        qpos = iq * q_block + jnp.arange(q_block) + q_offset

        if window:
            lo = iq * q_block + q_offset - (window - 1)
            first = jnp.clip(lo // kv_block, 0, nkv - n_win)
        else:
            first = jnp.int32(0)

        def kv_step(carry, j):
            m, l, acc = carry
            jb = first + j
            kblk = lax.dynamic_slice_in_dim(k, jb * kv_block, kv_block, 1)
            vblk = lax.dynamic_slice_in_dim(v, jb * kv_block, kv_block, 1)
            kpos = jb * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bthgd,bshd->bhgts", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            msk = jnp.ones((q_block, kv_block), bool)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window:
                msk &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgts,bshd->bhgtd", p.astype(q.dtype), vblk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, v.shape[-1]), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_win))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (B,Hkv,G,Cq,D) -> (B,Cq,Hkv,G,D)
        return None, out.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    qblocks = qg.transpose(1, 0, 2, 3, 4, 5)  # (nq,B,Cq,Hkv,G,D)
    _, outs = lax.scan(q_step, None, (qblocks, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, H, v.shape[-1])
    return out


def flash_attention_xla_triangular(q, k, v, *, q_offset=0, block=1024):
    """Causal blockwise attention with a BALANCED TRIANGULAR schedule.

    The plain blockwise path visits the full (nq x nkv) rectangle and masks
    the upper triangle -- half the attention FLOPs are dead.  Pairing query
    row p with row nq-1-p gives every pair the same fixed budget of nq+1 kv
    steps (p+1 for the early row + nq-p for the late row), so a scan over
    nq/2 pairs x (nq+1) steps covers exactly the causal triangle:
    ~2x fewer attention FLOPs at 32k prefill (EXPERIMENTS.md SSPerf).
    Requires T == S, T % block == 0, nq even; callers fall back otherwise.
    """
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    assert T == S and T % block == 0 and (T // block) % 2 == 0
    nq = T // block
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, nq, block, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)

    def pair_step(_, p):
        qa = jax.lax.dynamic_index_in_dim(qg, p, 0, keepdims=False)
        qb = jax.lax.dynamic_index_in_dim(qg, nq - 1 - p, 0, keepdims=False)
        pos_a = p * block + jnp.arange(block) + q_offset
        pos_b = (nq - 1 - p) * block + jnp.arange(block) + q_offset

        def kv_step(carry, jj):
            ma, la, acca, mb, lb, accb = carry
            take_a = jj <= p
            kv_idx = jnp.where(take_a, jj, jj - p - 1)
            kblk = lax.dynamic_slice_in_dim(k, kv_idx * block, block, 1)
            vblk = lax.dynamic_slice_in_dim(v, kv_idx * block, block, 1)
            kpos = kv_idx * block + jnp.arange(block)
            qsel = jnp.where(take_a, qa, qb)
            qpos = jnp.where(take_a, pos_a, pos_b)
            s = jnp.einsum("bthgd,bshd->bhgts", qsel, kblk,
                           preferred_element_type=jnp.float32) * scale
            msk = kpos[None, :] <= qpos[:, None]
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_old = jnp.where(take_a, ma, mb)
            l_old = jnp.where(take_a, la, lb)
            acc_old = jnp.where(take_a, acca, accb)
            m_new = jnp.maximum(m_old, s.max(axis=-1))
            pexp = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_old - m_new)
            l_new = l_old * corr + pexp.sum(axis=-1)
            pv = jnp.einsum("bhgts,bshd->bhgtd", pexp.astype(q.dtype), vblk)
            acc_new = acc_old * corr[..., None].astype(acc_old.dtype) + \
                pv.astype(jnp.float32)
            ma = jnp.where(take_a, m_new, ma)
            la = jnp.where(take_a, l_new, la)
            acca = jnp.where(take_a, acc_new, acca)
            mb = jnp.where(take_a, mb, m_new)
            lb = jnp.where(take_a, lb, l_new)
            accb = jnp.where(take_a, accb, acc_new)
            return (ma, la, acca, mb, lb, accb), None

        z = lambda *s_: jnp.zeros(s_, jnp.float32)
        m0 = jnp.full((B, Hkv, G, block), -jnp.inf, jnp.float32)
        Dv = v.shape[-1]
        carry0 = (m0, z(B, Hkv, G, block), z(B, Hkv, G, block, Dv),
                  m0, z(B, Hkv, G, block), z(B, Hkv, G, block, Dv))
        (ma, la, acca, mb, lb, accb), _ = lax.scan(
            kv_step, carry0, jnp.arange(nq + 1))
        outa = (acca / jnp.maximum(la[..., None], 1e-30))
        outb = (accb / jnp.maximum(lb[..., None], 1e-30))
        # (B,Hkv,G,block,D) -> (B,block,Hkv,G,D)
        f = lambda o: o.transpose(0, 3, 1, 2, 4).astype(q.dtype)
        return None, (f(outa), f(outb))

    _, (outs_a, outs_b) = lax.scan(pair_step, None, jnp.arange(nq // 2))
    # outs_a rows: p = 0..nq/2-1; outs_b rows: nq-1-p (descending)
    out = jnp.concatenate([outs_a, outs_b[::-1]], axis=0)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, H, v.shape[-1])
    return out


def decode_attention(q, k_cache, v_cache, cache_len, *, window=0):
    """Single-step decode: q (B,1,H,D) over cache (B,S,Hkv,D); positions
    >= cache_len are masked.  `window` additionally masks stale entries
    (the SWA ring buffer keeps only `window` positions so S == window)."""
    B, _, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)
    valid = kpos[None, :] < cache_len[:, None]  # (B,S)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache)
    return out.reshape(B, 1, H, D)


def ring_decode_attention(q, k_cache, v_cache, cache_len, *, segments):
    """Seq-sharded (ring) decode: identical math to decode_attention,
    restructured so the seq dim splits into `segments` independent
    slices merged by log-sum-exp.

    Under SPMD with the cache's seq dim sharded over "model" (the
    CacheSpec "ring" layout), each shard computes partial attention over
    its OWN S/n cache slice; the cross-shard traffic is the per-segment
    (B, n, Hkv, G) max/sum statistics plus the (B, Hkv, G, D) partial
    outputs -- instead of GSPMD all-gathering the whole cache to every
    model shard (the measured 68 GB/step failure mode this layout
    replaces).  Numerics: scores and softmax statistics in fp32 with ONE
    global max (exp(s - M) == what jax.nn.softmax computes), so the
    probabilities match decode_attention's bit-for-bit up to fp32
    summation order; greedy decode is token-identical on the parity
    suite (tests/test_cache_spec.py).
    """
    B, _, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    n = segments
    Sn = S // n
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    seg_ax = ("batch", ("model",), None, "kv_heads", None)
    ks = constrain(k_cache.reshape(B, n, Sn, Hkv, D), seg_ax)
    vs = constrain(v_cache.reshape(B, n, Sn, Hkv, D), seg_ax)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhgd,bnshd->bnhgs", qg, ks,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(n)[:, None] * Sn + jnp.arange(Sn)[None, :]   # (n,Sn)
    valid = kpos[None] < cache_len[:, None, None]                  # (B,n,Sn)
    s = jnp.where(valid[:, :, None, None, :], s, -1e30)
    m_seg = s.max(axis=-1)                     # (B,n,Hkv,G) segment-local
    M = m_seg.max(axis=1, keepdims=True)       # cross-segment (tiny)
    p = jnp.exp(s - M[..., None])
    l = p.sum(axis=-1).sum(axis=1)             # (B,Hkv,G) cross-segment
    probs = (p / l[:, None, :, :, None]).astype(q.dtype)
    out = jnp.einsum("bnhgs,bnshd->bhgd", probs, vs)
    return out.reshape(B, 1, H, D)


def paged_kv_write(kp, vp, layer, bt, kk, vv, positions):
    """Write per-token K/V into layer `layer` of the stacked paged pool,
    whole blocks at a time.

    kp/vp: (L, NB, Hkv, BS, D) block pools shared by ALL sequences;
    layer: int32 scalar; bt: (B, nbmax) block tables; kk/vv: (B, C, Hkv,
    D) new K/V; positions: (B, C) ABSOLUTE positions, consecutive from a
    row's first, -1 marking padding rows whose writes are dropped
    (bucketed prefill pads the tail chunk; a free decode slot is all -1).

    C consecutive positions span at most `nt` blocks.  Those blocks are
    read, the new rows merged in at their offsets, and the blocks written
    back whole into the layer's slice in place: a scatter over the block
    axis only, which keeps the head-major layout, so a layer scan can
    carry the stacked pool without relaying it.  A block no row writes is
    dropped from the scatter.  Distinct sequences write distinct blocks
    by construction (shared prefix blocks are read-only: the allocator
    only shares full prompt blocks, and writes happen at positions >= the
    private tail), so the scatter is collision-free.
    """
    nb, bs = kp.shape[1], kp.shape[3]
    B, C = positions.shape
    nt = -(-(C - 1) // bs) + 1              # blocks C consecutive rows span
    first = positions[:, :1]
    blk = first // bs + jnp.arange(nt)                          # (B, nt)
    # the position each row of each touched block holds, and the row of
    # kk / vv that writes it, if any
    pos = (blk[:, :, None] * bs + jnp.arange(bs)).reshape(B, nt * bs)
    src = pos - first
    row = jnp.clip(src, 0, C - 1)
    keep = ((src == row) & (pos >= 0)
            & (jnp.take_along_axis(positions, row, axis=1) == pos)
            ).reshape(B, nt, bs)
    page = jnp.take_along_axis(bt, jnp.clip(blk, 0, bt.shape[1] - 1), axis=1)
    dest = jnp.where(keep.any(-1), page, nb)                    # nb: dropped

    def merge(pool, new):
        old = pool[layer, jnp.minimum(dest, nb - 1)]        # (B,nt,Hkv,BS,D)
        rows = jnp.take_along_axis(new, row[:, :, None, None], axis=1)
        rows = rows.reshape(B, nt, bs, *new.shape[2:]).swapaxes(2, 3)
        blocks = jnp.where(keep[:, :, None, :, None],
                           rows.astype(pool.dtype), old)
        return pool.at[layer, dest].set(blocks, mode="drop")

    return merge(kp, kk), merge(vp, vv)


def paged_gather_kv(kp, vp, layer, bt):
    """Gather each sequence's K/V view from layer `layer` of the stacked
    (L, NB, Hkv, BS, D) block pools.

    Returns head-major (B, Hkv, nbmax*BS, D) -- unallocated table entries
    (0-filled) gather block 0's contents; callers mask by sequence length
    so the garbage never contributes attention weight.
    """
    B, nbmax = bt.shape

    def view(pool):
        g = pool[layer, bt].swapaxes(1, 2)         # (B, Hkv, nbmax, BS, D)
        return g.reshape(B, g.shape[1], nbmax * g.shape[3], g.shape[4])

    return view(kp), view(vp)


def paged_chunk_attention(q, k_seq, v_seq, positions):
    """Exact causal attention of a prefill CHUNK over the paged view.

    q: (B, C, H, D) chunk queries; k_seq/v_seq: (B, Hkv, S, D) gathered
    pages (already containing this chunk's K/V *and* any shared-prefix
    blocks); positions: (B, C) absolute query positions (-1 = padding;
    such rows attend to nothing real and their output is discarded).
    Scores materialise as (C, S) only -- long prompts stream through in
    bounded-size chunks.
    """
    B, C, H, D = q.shape
    Hkv, S = k_seq.shape[1], k_seq.shape[2]
    G = H // Hkv
    qg = q.reshape(B, C, Hkv, G, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bthgd,bhsd->bhgts", qg, k_seq,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)
    mask = kpos[None, None, :] <= positions[:, :, None]        # (B, C, S)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bhsd->bthgd", p, v_seq)
    return out.reshape(B, C, H, D)


# the paged-cache convention lives in models/cache.py with the rest
paged_attention_cache_defs = kvcache.paged_attention_cache_defs


def select_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """Pick exact vs blockwise path from the (static) sequence length.

    Threshold 4096: a lower threshold was tried and REFUTED -- the XLA
    blockwise path's scan carries round-trip HBM every kv step, so its
    measured traffic is HIGHER than materialising (T,S) scores at 4k; true
    flash locality needs the fused Pallas kernel (kernels/flash_attention,
    TPU path).  Blockwise remains required above 4k where (T,S) scores
    would not fit at all (EXPERIMENTS.md SSPerf, mixtral iteration 2)."""
    T, S = q.shape[1], k.shape[1]
    if max(T, S) <= 4096:
        return attention_full(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    if (causal and not window and T == S and T % 1024 == 0
            and (T // 1024) % 2 == 0):
        # long causal prefill: triangular schedule halves attention FLOPs
        return flash_attention_xla_triangular(q, k, v, q_offset=q_offset)
    return flash_attention_xla(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)


# --------------------------------------------------------------------------
# Attention block (params + apply, train/prefill/decode)
# --------------------------------------------------------------------------

def attention_defs(cfg, d_model=None, cross=False):
    d = d_model or cfg.d_model
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    defs = {
        "wq": pdef((d, H, Dh), ("embed", "heads", None), fan_in_axes=(0,)),
        "wk": pdef((d, Hkv, Dh), ("embed", "kv_heads", None), fan_in_axes=(0,)),
        "wv": pdef((d, Hkv, Dh), ("embed", "kv_heads", None), fan_in_axes=(0,)),
        "wo": pdef((H, Dh, d), ("heads", None, "embed_tp"), fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias:
        defs["bq"] = pdef((H, Dh), ("heads", None), init="zeros")
        defs["bk"] = pdef((Hkv, Dh), ("kv_heads", None), init="zeros")
        defs["bv"] = pdef((Hkv, Dh), ("kv_heads", None), init="zeros")
    return defs


def attention_apply(p, cfg, x, positions, *, mode="train", cache=None,
                    kv_source=None, causal=True, window=None,
                    is_cross=False):
    """mode: train/prefill (full seq) or decode (T==1, uses cache).

    Cross-attention (enc-dec): pass kv_source=enc_out in train/prefill, or
    is_cross=True in decode (cache then holds the STATIC encoder K/V built
    at prefill -- never updated, no RoPE).  Returns (out, new_cache).
    """
    is_cross = is_cross or kv_source is not None
    window = cfg.window if window is None else window
    B, T, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    # When heads don't divide the TP axis (e.g. 20H on a 16-way model axis)
    # head-sharding is impossible and attention would run fully REPLICATED
    # on every model shard.  Fall back to sequence/context parallelism: the
    # q blocks shard over "model", k/v stay full, and the output re-gathers.
    m = mesh_axis_size("model")
    seq_cp = (cfg.num_heads % m != 0 and T % m == 0 and T > 1
              and not is_cross)
    q_axes = ("batch", ("model",), "heads", None) if seq_cp else \
        ("batch", None, "heads", None)
    q = constrain(q, q_axes)
    if not is_cross:
        q = rope_apply(q, positions, cfg.rope_theta, cfg.rope_fraction)

    if is_cross and mode == "decode":
        # static encoder K/V cache: read-only attention over enc_len
        out = decode_attention(q, cache["k"], cache["v"], cache["len"])
        out = constrain(out, ("batch", None, "heads", None))
        y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
        return constrain(y, ("batch", None, None)), cache

    xs = kv_source if kv_source is not None else x
    kk = jnp.einsum("bsd,dhk->bshk", xs, p["wk"])
    vv = jnp.einsum("bsd,dhk->bshk", xs, p["wv"])
    if "bk" in p:
        kk = kk + p["bk"]
        vv = vv + p["bv"]
    if not is_cross:
        kk = rope_apply(kk, positions, cfg.rope_theta, cfg.rope_fraction)

    new_cache = cache
    if mode == "chunk_prefill" and cache is not None and "kp" in cache:
        # paged chunked prefill: write this chunk's K/V into the layer's
        # blocks of the stacked pool, then exact attention over the
        # sequence's gathered view (which already holds any shared-prefix
        # blocks -- their positions are simply never re-computed).
        assert not window, "paged cache does not support sliding windows"
        layer, bt = cache["layer"], cache["bt"]
        with jax.named_scope("paged_gather"):
            kp, vp = paged_kv_write(cache["kp"], cache["vp"], layer, bt,
                                    kk, vv, positions)
            k_seq, v_seq = paged_gather_kv(kp, vp, layer, bt)
        out = paged_chunk_attention(q, k_seq, v_seq, positions)
        new_cache = {"kp": kp, "vp": vp}
    elif mode == "chunk_prefill":
        # CONTIGUOUS chunked prefill (rectangular batch: all rows at the
        # same offset): write this chunk's K/V into the spec'd cache at
        # the current length, then blockwise attention of the chunk over
        # the cache prefix.  Streams a long prompt through in bounded
        # chunks so the per-step temporaries scale with the chunk, while
        # the resident cache keeps the spec's (ring / int8) footprint --
        # the prefill path the layout policy probes for cells whose
        # one-shot prefill blows the HBM budget.
        spec = kvcache.spec_of(cfg)
        cache_len = cache["len"]
        new_cache = kvcache.write_kv(cache, kk, vv,
                                     cache_len.astype(jnp.int32), spec=spec)
        new_cache["len"] = cache_len + T
        k_read, v_read = kvcache.read_kv(new_cache)
        out = select_attention(q, k_read, v_read, causal=True,
                               window=window, q_offset=cache_len[0])
    elif mode == "decode" and "kp" in cache:
        assert not window, "paged cache does not support sliding windows"
        layer, bt, cache_len = cache["layer"], cache["bt"], cache["len"]
        with jax.named_scope("paged_gather"):
            kp, vp = paged_kv_write(cache["kp"], cache["vp"], layer, bt,
                                    kk, vv, cache_len[:, None])
        # the write comes first: the new token attends to itself
        from repro.kernels.paged_attention import ops as paged_ops
        out = paged_ops.paged_decode_attention(q, kp, vp, layer, bt,
                                               cache_len + 1)
        new_cache = {"kp": kp, "vp": vp}
    elif mode == "decode":
        spec = kvcache.spec_of(cfg)
        cache_len = cache["len"]
        S = cache["k"].shape[1]
        if window and S == window:
            slots = (cache_len % window).astype(jnp.int32)  # ring buffer
        else:
            slots = cache_len.astype(jnp.int32)
        # PER-BATCH slot writes (vmapped DUS inside cache.write_kv):
        # sequences at different positions coexist in one batch
        # (continuous batching, serve_loop); int8 caches quantise the new
        # row and update the rowwise scales alongside.
        new_cache = kvcache.write_kv(cache, kk, vv, slots, spec=spec)
        new_cache["len"] = cache_len + 1
        k_read, v_read = kvcache.read_kv(new_cache)
        # SWA ring buffers (S == window) keep their wraparound masking in
        # decode_attention's window arg; segment the seq dim otherwise.
        n = kvcache.ring_segments(spec, S) if not window else 1
        if n > 1:
            out = ring_decode_attention(q, k_read, v_read, cache_len + 1,
                                        segments=n)
        else:
            out = decode_attention(q, k_read, v_read, cache_len + 1,
                                   window=window)
    else:
        out = select_attention(q, kk, vv, causal=causal and kv_source is None,
                               window=window)
        if mode == "prefill" and kv_source is None:
            new_cache = kvcache.pack_prefill_cache(
                cfg, kk, vv, window=window)
    out = constrain(out, q_axes if seq_cp else ("batch", None, "heads", None))
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return constrain(y, ("batch", None, None)), new_cache


# the contiguous-cache convention (shapes / dtypes / logical axes per
# CacheSpec) lives in models/cache.py
attention_cache_defs = kvcache.attention_cache_defs


# --------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2 / V3)
# --------------------------------------------------------------------------
#
# Keys and values come from one latent per token: c = norm(x W_DKV) of
# kv_lora_rank values, with a rotary key part k_pe = rope(x W_KR) of
# qk_rope_head_dim values shared by every head.  Per head, k_nope = c W_UK
# and v = c W_UV.  Train and prefill run this EXPANDED form.  The paged
# paths keep only the latent row [c || k_pe] per token and layer, and run
# the ABSORBED form: q_nope W_UK^T scores against c itself, the rope part
# against k_pe, and the weighted sum of c goes back through W_UV -- the
# same numbers with the per-head K and V never formed.

def mla_defs(cfg):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": pdef((d, H, dn + dr), ("embed", "heads", None), fan_in_axes=(0,)),
        "wkv_a": pdef((d, r + dr), ("embed", None), fan_in_axes=(0,)),
        "kv_norm": {"scale": pdef((r,), (None,), init="ones")},
        # wkv_b of the published checkpoint, split into its K and V halves
        "w_uk": pdef((r, H, dn), (None, "heads", None), fan_in_axes=(0,)),
        "w_uv": pdef((r, H, dv), (None, "heads", None), fan_in_axes=(0,)),
        "wo": pdef((H, dv, d), ("heads", None, "embed_tp"), fan_in_axes=(0, 1)),
    }


def latent_write(pool, layer, bt, rows, positions):
    """Scatter latent rows into layer `layer` of the stacked latent pool.

    pool: (L, NB, BS, W); bt: (B, nbmax) block tables; rows: (B, C, W);
    positions: (B, C) absolute, -1 marking rows whose writes are dropped.
    Indexing the stacked pool in place (no per-layer slice) lets the
    scan carry it without copies."""
    L, nb, bs, W = pool.shape
    valid = positions >= 0
    pos = jnp.where(valid, positions, 0)
    page = jnp.take_along_axis(bt, pos // bs, axis=1)
    flat = jnp.where(valid, (layer * nb + page) * bs + pos % bs, L * nb * bs)
    pf = pool.reshape(L * nb * bs, W).at[flat.reshape(-1)].set(
        rows.reshape(-1, W).astype(pool.dtype), mode="drop")
    return pf.reshape(pool.shape)


def latent_gather(pool, layer, bt):
    """(B, nbmax * BS, W): the latent rows each table names, of layer
    `layer`; unallocated entries gather block 0, masked by the caller."""
    L, nb, bs, W = pool.shape
    idx = ((layer * nb + bt)[:, :, None] * bs
           + jnp.arange(bs)[None, None]).reshape(bt.shape[0], -1)
    return pool.reshape(L * nb * bs, W)[idx]


def latent_attention(q, rows, positions, *, scale, dv):
    """Absorbed MLA attention over gathered latent rows.

    q: (B, C, H, W) latent queries [q_nope W_UK^T || q_pe || 0]; rows:
    (B, S, W) [c || k_pe || 0] at positions 0..S-1; positions: (B, C), the
    last key position each query sees.  Returns (B, C, H, dv), the
    weighted sum of the first dv lanes of the rows (c)."""
    S = rows.shape[1]
    s = jnp.einsum("bchw,bsw->bhcs", q, rows,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]   # (B,C,S)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhcs,bsr->bchr", p, rows[..., :dv])


def mla_apply(p, cfg, x, positions, *, mode="train", cache=None):
    """MLA block: expanded in train / prefill; absorbed over the latent
    pool in chunk_prefill and decode, where `cache` holds the stacked
    pool ("latent"), this layer's index ("layer"), the block tables
    ("bt") and, in decode, the lengths ("len").  Returns (out, {"latent":
    pool} or None)."""
    B, T, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = 1.0 / math.sqrt(dn + dr)
    q = constrain(jnp.einsum("btd,dhk->bthk", x, p["wq"]),
                  ("batch", None, "heads", None))
    q_nope = q[..., :dn]
    q_pe = rope_apply(q[..., dn:], positions, cfg.rope_theta)
    kv = jnp.einsum("btd,dr->btr", x, p["wkv_a"])
    c = rmsnorm(kv[..., :r], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope_apply(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]

    if cache is None:
        k_nope = jnp.einsum("btr,rhk->bthk", c, p["w_uk"])
        v = jnp.einsum("btr,rhk->bthk", c, p["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, T, H, dr))], -1)
        out = select_attention(jnp.concatenate([q_nope, q_pe], -1), k, v,
                               causal=True)
        new_pool = None
    else:
        pool, layer, bt = cache["latent"], cache["layer"], cache["bt"]
        W = pool.shape[-1]
        pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                                + [(0, W - a.shape[-1])])
        wpos = cache["len"][:, None] if mode == "decode" else positions
        with jax.named_scope("latent_write"):
            new_pool = latent_write(pool, layer, bt,
                                    pad(jnp.concatenate([c, k_pe], -1)), wpos)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bthk,rhk->bthr", q_nope, p["w_uk"])
            q_row = pad(jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)],
                                        -1))
        if mode == "decode":
            # the write comes first: the new token attends to itself
            from repro.kernels.paged_attention import ops as paged_ops
            o_lat = paged_ops.paged_latent_attention(
                q_row[:, 0], new_pool, layer, bt, cache["len"] + 1,
                scale=scale, dv=r)[:, None]
        else:
            o_lat = latent_attention(q_row, latent_gather(new_pool, layer, bt),
                                     positions, scale=scale, dv=r)
        with jax.named_scope("mla_absorb"):
            out = jnp.einsum("bthr,rhk->bthk", o_lat, p["w_uv"])
        new_pool = {"latent": new_pool}
    out = constrain(out, ("batch", None, "heads", None))
    y = jnp.einsum("bthk,hkd->btd", out, p["wo"])
    return constrain(y, ("batch", None, None)), new_pool


# --------------------------------------------------------------------------
# Dense MLP (gated or plain)
# --------------------------------------------------------------------------

def mlp_defs(cfg, d_ff=None):
    gated = cfg.act in ("silu", "gelu")
    d, f = cfg.d_model, d_ff or cfg.d_ff
    defs = {
        "w_up": pdef((d, f), ("embed", "ffn"), fan_in_axes=(0,)),
        "w_down": pdef((f, d), ("ffn", "embed_tp"), fan_in_axes=(0,)),
    }
    if gated:
        defs["w_gate"] = pdef((d, f), ("embed", "ffn"), fan_in_axes=(0,))
    return defs


def mlp_apply(p, cfg, x):
    h = jnp.einsum("btd,df->btf", x, p["w_up"])
    if "w_gate" in p:
        g = jnp.einsum("btd,df->btf", x, p["w_gate"])
        h = act_fn(cfg.act)(g) * h
    else:
        h = act_fn(cfg.act)(h)
    h = constrain(h, ("batch", None, "ffn"))
    return jnp.einsum("btf,fd->btd", h, p["w_down"])


# --------------------------------------------------------------------------
# MoE (gather-based dispatch: no (T,E,C) one-hot einsum FLOPs)
# --------------------------------------------------------------------------

def moe_defs(cfg):
    """The router keeps every expert's output; only the held experts'
    weights live here (cfg.experts_here)."""
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    Eh = cfg.experts_here[1]
    defs = {
        "w_router": pdef((d, E), ("embed", None), dtype=jnp.float32,
                         fan_in_axes=(0,)),
        "w_gate": pdef((Eh, d, f), ("experts", "embed", "expert_ffn"),
                       fan_in_axes=(1,)),
        "w_up": pdef((Eh, d, f), ("experts", "embed", "expert_ffn"),
                     fan_in_axes=(1,)),
        "w_down": pdef((Eh, f, d), ("experts", "expert_ffn", "embed"),
                       fan_in_axes=(1,)),
    }
    if cfg.router == "sigmoid":
        # e_score_correction_bias: shifts which experts are chosen, never
        # their weights
        defs["e_bias"] = pdef((E,), (None,), dtype=jnp.float32, init="zeros")
    if cfg.n_shared_experts:
        defs["shared"] = mlp_defs(cfg, d_ff=cfg.n_shared_experts * f)
    return defs


def moe_capacity(cfg, tokens: int) -> int:
    # capacity_factor <= 0 means DROPLESS: an expert can receive at most one
    # choice per token, so capacity == tokens guarantees no token ever
    # overflows (smoke configs use this -- an untrained router is imbalanced
    # enough to overflow any reasonable factor at test scale).
    if cfg.capacity_factor <= 0:
        return tokens
    c = int(math.ceil(tokens * cfg.experts_per_token / cfg.num_experts
                      * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8


def _moe_groups(B: int, T: int, min_tokens: int = 2048) -> int:
    """Largest divisor of B keeping >= min_tokens tokens per group.

    The GROUP dimension is the key to sharded dispatch: routing/capacity is
    computed per group and groups shard over the data axis, so the expert
    einsums are (G, E, C_g, d) with G sharded -- WITHOUT it, the (E, C)
    dispatch is global and GSPMD replicates the whole expert computation on
    every data shard (measured 16x FLOP blowup; EXPERIMENTS.md SSPerf)."""
    g = B
    while g > 1 and (B * T) // g < min_tokens:
        g //= 2
    while B % g != 0:
        g -= 1
    return max(g, 1)


def moe_route(p, cfg, xg):
    """(weights (G,ng,k), expert ids (G,ng,k), probs (G,ng,E)) over all E
    experts.  softmax: top-k of the softmax, renormalised.  sigmoid
    (DeepSeek-V3 noaux_tc with one group): top-k of sigmoid + e_bias,
    weighted by the unbiased sigmoid, normalised, times routed_scaling."""
    logits = jnp.einsum("gnd,de->gne", xg.astype(jnp.float32), p["w_router"])
    k = cfg.experts_per_token
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, gidx = lax.top_k(scores + p["e_bias"], k)
        gval = jnp.take_along_axis(scores, gidx, axis=-1)
        gval = gval / (gval.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling
        return gval, gidx, scores / scores.sum(-1, keepdims=True)
    probs = jax.nn.softmax(logits, axis=-1)
    gval, gidx = lax.top_k(probs, k)                     # (G,ng,k)
    gval = gval / jnp.maximum(gval.sum(-1, keepdims=True), 1e-9)
    return gval, gidx, probs


def moe_apply(p, cfg, x):
    """Top-k routed expert MLP with per-group capacity + token dropping
    (dropless at capacity_factor <= 0), plus the shared experts.

    The router scores all E experts; only the held ones (cfg.experts_here)
    are computed, and a choice of an expert held elsewhere adds nothing
    here -- its part of the result is another chip's.
    Dispatch/combine are GATHERS (memory movement), not one-hot einsums, so
    HLO FLOPs stay proportional to active-expert compute.
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    lo, Eh = cfg.experts_here
    n = B * T
    G = _moe_groups(B, T)
    ng = n // G
    C = moe_capacity(cfg, ng)
    xg = x.reshape(G, ng, d)

    with jax.named_scope("router"):
        gval, gidx, probs = moe_route(p, cfg, xg)
    onehot = jax.nn.one_hot(gidx, E, dtype=jnp.int32)    # (G,ng,k,E)
    if cfg.held_experts:
        local = gidx - lo
        held = (local >= 0) & (local < Eh)
        gidx = jnp.where(held, local, Eh)                # Eh: held elsewhere
        here = jax.nn.one_hot(gidx, Eh, dtype=jnp.int32)  # (G,ng,k,Eh)
    else:
        here = onehot

    # Position of each (token, choice) within its expert, per group:
    # slot-major cumsum so choice 0 of token t beats choice 1 of token t.
    flat = here.transpose(0, 2, 1, 3).reshape(G, k * ng, Eh)
    pos_flat = jnp.cumsum(flat, axis=1) - flat
    pos = (pos_flat.reshape(G, k, ng, Eh).transpose(0, 2, 1, 3)
           * here).sum(-1)                               # (G,ng,k)
    keep = pos < C
    if cfg.held_experts:
        keep &= held

    # slot_token[g, e, c] = source token index within group (ng == padding)
    gg = jnp.arange(G, dtype=jnp.int32)[:, None]
    e_flat = jnp.where(keep, gidx, Eh).reshape(G, -1)
    c_flat = jnp.where(keep, pos, 0).reshape(G, -1)
    tok = jnp.broadcast_to(jnp.arange(ng, dtype=jnp.int32)[None, :, None],
                           (G, ng, k)).reshape(G, -1)
    slot_token = jnp.full((G, Eh + 1, C), ng, jnp.int32)
    slot_token = slot_token.at[gg, e_flat, c_flat].set(tok, mode="drop")
    slot_token = slot_token[:, :Eh]

    x_pad = jnp.concatenate([xg, jnp.zeros((G, 1, d), xg.dtype)], axis=1)
    xe = jnp.take_along_axis(
        x_pad[:, :, None, :],                            # (G,ng+1,1,d)
        slot_token.reshape(G, -1)[:, :, None, None], axis=1
    ).reshape(G, Eh, C, d)                               # local gather per G
    xe = constrain(xe, ("batch", "experts", None, None))

    with jax.named_scope("routed_experts"):
        g_ = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
        u = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
        h = act_fn(cfg.act)(g_) * u
        ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])    # (G,Eh,C,d)
    ye = constrain(ye, ("batch", "experts", None, None))

    # combine: gather each token-choice's slot output, weight, sum over k
    ye_flat = jnp.concatenate(
        [ye.reshape(G, Eh * C, d), jnp.zeros((G, 1, d), ye.dtype)], axis=1)
    slot_id = jnp.where(keep, gidx * C + pos, Eh * C)    # (G,ng,k)
    yk = jnp.take_along_axis(
        ye_flat[:, :, None, :],
        slot_id.reshape(G, -1)[:, :, None, None], axis=1
    ).reshape(G, ng, k, d)
    y = jnp.einsum("gnkd,gnk->gnd", yk, gval.astype(yk.dtype) * keep)
    y = y.reshape(B, T, d)
    if "shared" in p:
        with jax.named_scope("shared_experts"):
            y = y + mlp_apply(p["shared"], cfg, x)
    aux = _load_balance_loss(probs.reshape(n, E),
                             onehot.reshape(n, k, E), E, k)
    return y, aux


def _load_balance_loss(probs, onehot, E, k):
    """Switch-style auxiliary loss: E * sum(frac_tokens * frac_probs)."""
    frac_tokens = onehot.sum(axis=(0, 1)).astype(jnp.float32) / (
        probs.shape[0] * k)
    frac_probs = probs.mean(axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------

def embed_defs(cfg):
    # The INPUT table is sharded only on d_model (over data x model jointly):
    # a gather over a vocab-sharded table triggers SPMD "involuntary full
    # rematerialization" (replicates the gathered activations); the OUTPUT
    # projection contracts d_model, so vocab-sharding is fine there.
    defs = {"tok": pdef((cfg.vocab_size, cfg.d_model),
                        (None, ("data", "model")), init="embed")}
    if not cfg.tie_embeddings:
        defs["unembed"] = pdef((cfg.d_model, cfg.vocab_size),
                               ("embed", "vocab"), fan_in_axes=(0,))
    return defs


def embed_apply(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed_apply(p, x):
    """Logits stay in activation dtype (bf16): with 150k+ vocabs an fp32
    (B,T,V) tensor would dominate memory; the loss reduces in fp32."""
    w = p.get("unembed")
    if w is None:
        w = p["tok"].T
    return jnp.einsum("btd,dv->btv", x, w)
