"""Continuous-batching serve loops (vLLM-flavoured, beyond-paper).

Two cache disciplines behind one Request/submit/tick API:

* ``ServeLoop`` -- the original CONTIGUOUS cache: a fixed pool of B slots
  shares one batched KV/state cache sized B x max_len; requests join
  mid-flight (prefill into a free slot), a single batched decode step
  runs for ALL live slots each tick with PER-SLOT positions, and
  finished slots are recycled.  Works for every family with a decode
  cache (incl. SSM state), but concurrency is capped at max_batch and a
  short request pays for max_len positions of HBM.

* ``PagedServeLoop`` -- the BLOCK-TABLE PAGED cache (transformer
  families): one KV block pool shared by all slots (core/paging.py
  allocator: free list, refcounts, prefix sharing), per-slot block
  tables mapping position -> (block, offset), chunked+bucketed prefill
  so any prompt length streams through a bounded number of jit cache
  entries, lazy block growth during decode, and preemption (requeue the
  youngest sequence) when the pool runs dry.  Greedy decode is
  token-identical to ServeLoop (tests/test_serve_loop.py).  Its tick
  records host spans (`flight.serve.*`, repro/trace.py) around each
  phase, and `counters` counts the work where it happens.

CPU-runnable at smoke scale; the same loops drive TPU serving, with the
weight layout (stationary / hybrid / fsdp) picked per model by the
memory-aware policy in repro.dist.policy (launch/serve.py passes its
decision as `layout=`).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.paging import BlockAllocator, OutOfBlocks
from repro.trace import span


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (T,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class _ServeBase:
    """Layout plumbing + queue discipline shared by both loops."""

    def __init__(self, model, params, *, max_batch: int,
                 layout: str = "auto"):
        self.model = model
        self.params = params
        self.B = max_batch
        self.rules = None
        if layout != "auto":
            from repro.dist.sharding import serve_layout_rules
            self.rules = serve_layout_rules(layout)
        self.live: dict[int, Request] = {}   # slot -> request
        self.free = list(range(max_batch))
        self.queue: list[Request] = []
        # host-side truth for per-slot positions.  int32, NOT int64: the
        # device `_next`/positions arrays are int32, and an int64 host
        # array silently wraps on the implicit cast once lengths cross
        # 2^31 (regression-pinned in tests/test_serve_loop.py).
        self.lengths = np.zeros(max_batch, np.int32)
        self._next = jnp.zeros((max_batch,), jnp.int32)

    def _rules_ctx(self):
        """Make the chosen layout's rules ambient while a step traces.
        constrain() in model code binds them only under an ambient mesh
        (`jax.set_mesh` around the loop); without one it is a no-op."""
        if self.rules is None:
            return contextlib.nullcontext()
        from repro.dist.sharding import use_rules
        return use_rules(self.rules)

    def submit(self, req: Request):
        self.queue.append(req)

    def run_until_drained(self, max_ticks: int = 10_000):
        done = []
        for _ in range(max_ticks):
            done += self.tick()
            if not self.live and not self.queue:
                break
        return done


class ServeLoop(_ServeBase):
    """Contiguous per-slot cache (see module docstring)."""

    def __init__(self, model, params, *, max_batch: int = 4,
                 max_len: int = 512, layout: str = "auto",
                 cache_spec: str | None = None):
        super().__init__(model, params, max_batch=max_batch, layout=layout)
        # cache_spec: "layout[:shards]/dtype" (models/cache.py) forces the
        # KV-cache layout; None keeps the config's own spec.
        spec = cache_spec
        if spec and model.supports_cache_spec \
                and spec != model.cfg.cache_spec:
            from repro.models import build_model
            model = build_model(
                dataclasses.replace(model.cfg, cache_spec=spec))
            self.model = model    # params are spec-independent
        self.cache_spec = spec
        self.S = max_len
        from repro.models.param import is_def
        self.cache = jax.tree.map(
            lambda d: jnp.zeros(d.shape, d.dtype),
            model.cache_defs(max_batch, max_len), is_leaf=is_def)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_impl)

    # -- jitted kernels -------------------------------------------------
    def _prefill_impl(self, params, tokens):
        with self._rules_ctx():
            logits, cache = self.model.apply(params, {"tokens": tokens},
                                             mode="prefill")
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt, cache

    def _decode_impl(self, params, cache, tokens, positions):
        with self._rules_ctx():
            logits, cache = self.model.apply(
                params, {"tokens": tokens, "positions": positions},
                mode="decode", cache=cache)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt, cache

    # -- slot management -------------------------------------------------
    def _admit(self):
        while self.queue and self.free:
            req = self.queue.pop(0)
            slot = self.free.pop(0)
            T = len(req.prompt)
            assert T < self.S, "prompt exceeds slot capacity"
            toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
            nxt, pcache = self._prefill(self.params, toks)
            self._write_slot(slot, pcache, T)
            self._next = self._next.at[slot].set(int(nxt[0]))
            self.lengths[slot] = T
            req.out.append(int(nxt[0]))
            self.live[slot] = req

    def _write_slot(self, slot: int, pcache, true_len: int):
        """Scatter a single-sequence prefill cache (leaves (L, 1, ...)) into
        the batched cache (leaves (L, B, ...)) at `slot`; time-like axes are
        padded/cropped to the slot capacity."""
        def one(bc, pc):
            if bc.dtype == jnp.int32 and bc.ndim == 2:   # (L, B) lengths
                return bc.at[:, slot].set(jnp.minimum(pc[:, 0], true_len))
            src = pc[:, 0]                               # (L, ...)
            want = bc.shape[2:]
            if src.shape[1:] != want:                    # time axis differs
                width = min(src.shape[1], want[0])
                src = src[:, :width]
                pad = [(0, 0), (0, want[0] - width)] + \
                    [(0, 0)] * (src.ndim - 2)
                src = jnp.pad(src, pad)
            return bc.at[:, slot].set(src.astype(bc.dtype))

        self.cache = jax.tree.map(one, self.cache, pcache)

    # -- main tick --------------------------------------------------------
    def tick(self) -> list[Request]:
        """Admit waiting requests, run ONE batched decode step, return the
        requests that finished this tick."""
        self._admit()
        if not self.live:
            return []
        # copy: on CPU jnp.asarray may alias the numpy buffer, which the
        # loop below mutates while the dispatched decode still reads it
        positions = jnp.array(self.lengths.reshape(self.B, 1), jnp.int32,
                              copy=True)
        nxt, self.cache = self._decode(
            self.params, self.cache, self._next[:, None], positions)
        self._next = nxt.astype(jnp.int32)
        out = np.asarray(nxt)          # one device->host read per tick
        finished = []
        for slot, req in list(self.live.items()):
            self.lengths[slot] += 1
            req.out.append(int(out[slot]))
            if len(req.out) >= req.max_new:
                req.done = True
                finished.append(req)
                del self.live[slot]
                self.free.append(slot)
        return finished


def _bucket(n: int) -> int:
    """Next power of two >= n: tail prefill chunks pad to a bucket so jit
    compiles O(log chunk) entries, not one per prompt length."""
    b = 1
    while b < n:
        b *= 2
    return b


class PagedServeLoop(_ServeBase):
    """Block-table paged KV cache + chunked/bucketed prefill (see module
    docstring).  ``num_blocks * block_size`` total cache positions are
    shared by up to ``max_batch`` concurrent sequences."""

    def __init__(self, model, params, *, max_batch: int = 4,
                 num_blocks: int = 64, block_size: int = 16,
                 chunk: int = 64, layout: str = "auto",
                 max_seq_len: int | None = None):
        """max_seq_len bounds prompt + output tokens of a request, and so
        the width of the block tables (nbmax); without it a table may
        span the whole pool."""
        assert chunk % block_size == 0, "chunk must be block-aligned"
        # a table can never exceed the pool
        nbmax = num_blocks if max_seq_len is None else min(
            num_blocks, -(-max_seq_len // block_size))
        # raises ValueError for a model whose cache cannot be paged
        defs = model.paged_cache_defs(max_batch, num_blocks, block_size,
                                      nbmax)
        super().__init__(model, params, max_batch=max_batch, layout=layout)
        self.alloc = BlockAllocator(num_blocks, block_size)
        self.bs = block_size
        self.nbmax = nbmax
        self.max_seq_len = max_seq_len
        self.chunk = chunk
        from repro.models.param import is_def
        full = jax.tree.map(lambda d: jnp.zeros(d.shape, d.dtype), defs,
                            is_leaf=is_def)
        # only the block pool lives on device between ticks; tables and
        # lengths are rebuilt from host truth every step
        self.pages = {k: v for k, v in full.items() if k not in ("bt", "len")}
        self.bt = np.zeros((max_batch, self.nbmax), np.int32)
        self._seq_of_slot: dict[int, int] = {}
        self._admit_order: list[int] = []   # slots, oldest first
        self._seq_counter = 0
        # work done, counted where it happens; host_syncs counts every
        # blocking device->host read (one a prefill, one a decode step), and
        # kv_blocks_read the pool blocks the decode steps' live slots hold
        self.counters = dict.fromkeys(
            ("decode_steps", "prefill_chunks", "host_syncs", "admissions",
             "preemptions", "kv_blocks_read"), 0)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._chunk_prefill = jax.jit(self._chunk_impl, donate_argnums=(1,))

    # -- jitted kernels -------------------------------------------------
    def _stack(self, x):
        """Broadcast a per-slot host array across the layer axis (every
        layer shares one block table / length vector)."""
        L = self.model.cfg.num_layers
        return jnp.broadcast_to(x[None], (L,) + x.shape)

    def _decode_impl(self, params, pages, bt, tokens, positions):
        cache = {**pages, "bt": self._stack(bt),
                 "len": self._stack(positions[:, 0])}
        with self._rules_ctx():
            logits, cache = self.model.apply(
                params, {"tokens": tokens, "positions": positions},
                mode="decode", cache=cache)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt, {k: cache[k] for k in pages}

    def _chunk_impl(self, params, pages, tokens, positions, bt_row,
                    last_index):
        with self._rules_ctx():
            logits, pages = self.model.apply(
                params, {"tokens": tokens, "positions": positions,
                         "block_tables": bt_row, "last_index": last_index},
                mode="chunk_prefill", cache=pages)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return nxt, pages

    # -- admission -------------------------------------------------------
    def _admit(self):
        while self.queue and self.free:
            req = self.queue[0]
            with span("serve.admit", rid=req.rid):
                if not self._admit_head(req):
                    return                 # head-of-line waits for blocks

    def _admit_head(self, req: Request) -> bool:
        """Admit and prefill the queue's head; False when the pool has no
        room for it yet."""
        prompt = np.asarray(req.prompt, np.int32)
        T = len(prompt)
        if self.max_seq_len is not None and T + req.max_new > self.max_seq_len:
            raise ValueError(
                f"request {req.rid}: {T} prompt + {req.max_new} new tokens "
                f"exceed max_seq_len {self.max_seq_len}")
        if (T + 1 + self.bs - 1) // self.bs > self.alloc.num_blocks:
            raise RuntimeError(
                f"prompt of {T} tokens can never fit the "
                f"{self.alloc.num_blocks}x{self.bs} block pool")
        sid = self._seq_counter
        try:
            res = self.alloc.admit(sid, prompt.tolist(), reserve=1)
        except OutOfBlocks:
            if not self.live and not self._preempt_youngest(protect=-1):
                raise RuntimeError(
                    "admission stalled with no live sequences: "
                    "block pool exhausted by the prefix cache?")
            return False
        self._seq_counter += 1
        self.queue.pop(0)
        slot = self.free.pop(0)
        self._seq_of_slot[slot] = sid
        self._admit_order.append(slot)
        self._set_table(slot, res.table)
        self.counters["admissions"] += 1
        nxt = self._prefill_chunks(slot, prompt, res.n_shared_tokens, T,
                                   rid=req.rid)
        self._next = self._next.at[slot].set(nxt)
        self.lengths[slot] = T
        req.out.append(nxt)
        self.live[slot] = req
        return True

    def _set_table(self, slot: int, table: list[int]):
        self.bt[slot] = 0
        self.bt[slot, : len(table)] = table

    def _prefill_chunks(self, slot: int, prompt: np.ndarray, start: int,
                        T: int, rid: int) -> int:
        """Stream prompt positions [start, T) through the pool in
        block-aligned chunks; the tail pads to a power-of-two bucket
        (positions -1 => writes dropped, logits taken at the last valid
        row).  `start` skips positions covered by shared prefix blocks --
        their K/V is already resident.  Returns the first output token,
        read back to the host."""
        chunks = -(-(T - start) // self.chunk)
        with span("serve.prefill", rid=rid, chunks=chunks):
            bt_row = jnp.array(self.bt[slot: slot + 1], copy=True)  # see tick
            pos = start
            nxt = None
            while pos < T:
                c = min(self.chunk, T - pos)
                cb = c if c == self.chunk else _bucket(c)
                toks = np.zeros((1, cb), np.int32)
                toks[0, :c] = prompt[pos: pos + c]
                pv = np.full((1, cb), -1, np.int32)
                pv[0, :c] = np.arange(pos, pos + c, dtype=np.int32)
                nxt, self.pages = self._chunk_prefill(
                    self.params, self.pages, jnp.asarray(toks),
                    jnp.asarray(pv), bt_row,
                    jnp.asarray([c - 1], jnp.int32))
                self.counters["prefill_chunks"] += 1
                pos += c
            self.counters["host_syncs"] += 1
            return int(nxt[0])

    # -- eviction / preemption -------------------------------------------
    def _release(self, slot: int):
        self.alloc.finish(self._seq_of_slot.pop(slot))
        self._admit_order.remove(slot)
        self.bt[slot] = 0
        self.lengths[slot] = 0
        self.free.append(slot)

    def _preempt_youngest(self, protect: int) -> bool:
        """Requeue the most recently admitted live sequence (other than
        `protect`) at the FRONT of the queue, releasing its blocks.
        Greedy decode is deterministic, so re-running it from the prompt
        reproduces the same tokens."""
        for slot in reversed(self._admit_order):
            if slot == protect or slot not in self.live:
                continue
            req = self.live.pop(slot)
            req.out = []
            self.queue.insert(0, req)
            self._release(slot)
            self.counters["preemptions"] += 1
            return True
        return False

    def _grow_tables(self):
        """Give every live slot a block for the position it writes this
        tick, preempting the youngest sequences when the pool is dry."""
        for slot in list(self.live):
            if slot not in self.live:
                continue
            sid = self._seq_of_slot[slot]
            while True:
                try:
                    if self.alloc.ensure_capacity(sid, int(self.lengths[slot])):
                        self._set_table(slot, self.alloc.table(sid))
                    break
                except OutOfBlocks:
                    if not self._preempt_youngest(protect=slot):
                        raise RuntimeError(
                            "block pool too small for a single sequence: "
                            f"{self.alloc.num_blocks} x {self.bs}")

    # -- main tick --------------------------------------------------------
    def tick(self) -> list[Request]:
        with span("serve.tick"):
            self._admit()
            if not self.live:
                return []
            with span("serve.grow"):
                self._grow_tables()
            with span("serve.step"):
                # free slots decode with position -1: their K/V write is
                # dropped (paged_kv_write) and their output ignored
                positions = np.full(self.B, -1, np.int32)
                for slot in self.live:
                    positions[slot] = self.lengths[slot]
                    self.counters["kv_blocks_read"] += \
                        -(-(int(self.lengths[slot]) + 1) // self.bs)
                nxt, self.pages = self._decode(
                    self.params, self.pages, jnp.array(self.bt, copy=True),
                    self._next[:, None], jnp.asarray(positions[:, None]))
                self._next = nxt.astype(jnp.int32)
                self.counters["decode_steps"] += 1
            with span("serve.readback"):
                # one device->host read for every live slot's token
                out = np.asarray(nxt)
                self.counters["host_syncs"] += 1
                finished = []
                for slot, req in list(self.live.items()):
                    self.lengths[slot] += 1
                    req.out.append(int(out[slot]))
                    if len(req.out) >= req.max_new:
                        req.done = True
                        finished.append(req)
                        del self.live[slot]
                        self._release(slot)
            return finished
