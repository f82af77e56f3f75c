"""Tier B: the paper's FL technique as SPMD collectives over the `pod` axis.

Each pod is one federated island (cross-silo FL).  Islands run E local SPMD
steps (FSDP x TP inside the island), then exchange weights through ONE
mixing collective:

    new_params_i = sum_j M[i, j] * params_j        (M: island mixing matrix)

M encodes the whole FLight control plane -- worker selection (zeroed
columns), FedAvg weighting (data-proportional rows), and async staleness
mixes (diagonal + rank-1) -- as RUNTIME INPUTS, so selection decisions never
trigger recompilation.  The collective moves param-shard bytes over the pod
axis: this is the paper's 'FTP bulk channel', ridden on ICI/DCN.

Island-distinct parameters are expressed with a leading `island` axis
sharded over "pod"; the island-local train step is vmapped over it with
spmd_axis_name="pod" (see launch/train.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import aggregation, compression
from repro.trace import span


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_islands: int = 1
    local_steps: int = 8           # E: train steps between aggregations
    aggregation: str = "fedavg"
    mode: str = "sync"             # sync | async
    async_base_alpha: float = 0.6
    staleness_scheme: str = "polynomial"
    compress: str = "none"         # exchange compression:
    #                                none | q8 | topk | q8_topk
    topk_frac: float = 0.05        # kept fraction for the topk modes
    overlap: bool = False          # double-buffer exchange w/ local steps


def stack_islands(tree, n_islands: int):
    """Tile a single-island pytree into (n_islands, ...) leaves."""
    with span("fl.stack_islands"):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_islands,) + x.shape),
            tree)


def island_slice(tree, i: int):
    with span("fl.island_slice"):
        return jax.tree.map(lambda x: x[i], tree)


def cohort_train(trainer, params, shards, keys, epochs: int):
    """Train a whole cohort in ONE batched step instead of Python-looping
    `local_train`: stack the worker shards along a leading cohort axis and
    vmap the island-local trainer over it (`params` broadcast, exactly the
    `stack_islands` layout).  Returns params stacked (C, ...) -- feed
    straight into `fl_aggregate` / `hierarchy.hierarchical_sync_aggregate`.

    shards: sequence of (images, labels) with EQUAL shapes (the caller
    groups by shape; see events.FLSimulation._train_plan)."""
    images = jnp.stack([jnp.asarray(x) for x, _ in shards])
    labels = jnp.stack([jnp.asarray(y) for _, y in shards])
    return trainer.train_cohort(params, images, labels, jnp.stack(keys),
                                epochs)


def fl_aggregate(stacked_params, mixing):
    """The FLight exchange: one mixing collective over the island axis.
    stacked_params: pytree with leading island axis sharded over "pod";
    mixing: (P, P) runtime array (selection/weights/staleness encoded)."""
    return aggregation.mix_islands(stacked_params, mixing)


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _quantize_per_island(delta):
    """The Pallas rowwise quantise of a stacked (P, ..., C) delta.

    GSPMD cannot partition a Mosaic kernel, so under a multi-device mesh
    (launch/train.island_mesh) the island axis is split by hand: a
    shard_map over "pod" runs the kernel on each device's own islands,
    before the mixing contraction moves anything.  Other mesh axes see
    the delta whole."""
    from repro.kernels.quant8 import ops as q8ops
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return q8ops.quantize_rowwise(delta)
    spec = PartitionSpec("pod")
    # check_vma=False: a pallas_call's outputs carry no varying-axes type
    return jax.shard_map(q8ops.quantize_rowwise, in_specs=spec,
                         out_specs=(spec, spec), check_vma=False)(delta)


def fl_aggregate_compressed(stacked_params, base_params, mixing, *,
                            mode: str = "q8", k_frac: float = 0.05,
                            impl: str = "auto"):
    """Beyond-paper: exchange compressed DELTAS from the shared last-sync
    base instead of raw weights, leaf by leaf:
    (sparsify ->) quantize -> dequantize -> mixing contraction.

    It is not jitted.  Called eagerly, as the fog round
    (hierarchy.hierarchical_sync_aggregate) calls it, every jnp operation
    of every leaf dispatches its own small program: the subtraction, the
    casts, the reshapes around the kernel, the `quantize_blocked` kernel
    program, the dequantising multiply, the tensordot and the add, about
    eight per leaf per call, issued by the host one by one.  Under an
    enclosing `jax.jit` the same code traces into one program.

    Every island already holds `base_params` (the previous exchange's
    result), so only the compressed delta crosses the pod axis: int8 +
    per-channel scales for "q8" (~4x fewer wire bytes than f32, and
    immune to the CPU backend's bf16->f32 collective legalisation -- int8
    stays int8), optionally top-k sparsified first ("topk" keeps fp32
    values, "q8_topk" stacks both).  Requires row-stochastic mixing
    (sum_j M[i,j] = 1), which all FLight mixes satisfy.

    Per-channel (last-dim) scales keep q the SAME shape/sharding as the
    leaf -- flattening would force a cross-axis reshard (a first
    formulation gathered over every mesh axis; see SSPerf).  The top-k
    stage is the threshold-mask form (compression.topk_mask) for the same
    reason: a gather of the survivors would reshard.

    impl="auto" quantises through the fused kernels/quant8 Pallas pass on
    TPU (per island, see _quantize_per_island) and takes the jnp
    reference (core.compression, same rounding) on other backends;
    dequantisation stays jnp so XLA fuses it into the mixing
    contraction."""
    if mode == "none":
        return fl_aggregate(stacked_params, mixing)
    if mode not in compression.MODES:
        raise ValueError(f"unknown exchange compression mode '{mode}'")
    use_pallas = _resolve_impl(impl) == "pallas"

    def mix(leaf, b):
        delta = (leaf.astype(jnp.float32) - b.astype(jnp.float32))
        if mode in ("topk", "q8_topk"):
            # per-island top-k over the leaf (batch dim = island axis)
            mask = compression.topk_mask(delta, k_frac=k_frac,
                                         batch_dims=1)
            delta = jnp.where(mask, delta, 0.0)
        if mode in ("q8", "q8_topk"):
            if use_pallas:
                q, scale = _quantize_per_island(delta)
            else:
                q, scale = compression.quantize_rowwise(delta)
            delta = q.astype(jnp.float32) * scale
        mixed = jnp.tensordot(mixing.astype(jnp.float32), delta, axes=1)
        return (b.astype(jnp.float32) + mixed).astype(leaf.dtype)

    return jax.tree.map(mix, stacked_params, base_params)


def fl_aggregate_robust(stacked_params, method: str, *, base_params=None,
                        **kw):
    """Byzantine-robust exchange: every island receives the robust fold of
    all island models (trimmed mean / median / multi-Krum / norm clipping,
    see aggregation.ROBUST_METHODS) instead of the mixing-matrix weighted
    average.  Unlike `fl_aggregate` this is NOT expressible as a
    row-stochastic mixing matrix -- robustness is exactly the refusal to
    take fixed linear combinations an attacker could dominate."""
    agg = aggregation.robust_aggregate_stacked(stacked_params, method,
                                               base=base_params, **kw)
    return jax.tree.map(
        lambda a, s: jnp.broadcast_to(a.astype(s.dtype)[None],
                                      s.shape), agg, stacked_params)


def fl_overlap_merge(params, mixed, snapshot):
    """Re-apply the local progress made WHILE the exchange was in flight.

    With the double-buffered exchange (launch/train.py --overlap) the
    mixing collective for round r runs concurrently with the first local
    step of round r+1, which therefore starts from the pre-exchange
    snapshot.  When the collective lands, the exchange correction
    (mixed - snapshot) is added on top of the current params -- the local
    step is never recomputed, the exchange is one step stale."""
    def one(p, m, s):
        out = (p.astype(jnp.float32) + m.astype(jnp.float32)
               - s.astype(jnp.float32))
        return out.astype(p.dtype)
    return jax.tree.map(one, params, mixed, snapshot)


def selection_mixing(weights: np.ndarray, selected: np.ndarray) -> np.ndarray:
    """Sync FedAvg restricted to selected islands; unselected islands still
    RECEIVE the aggregate (they re-sync, matching the paper's workers that
    download the latest server model when next contacted)."""
    w = np.asarray(weights, np.float64) * np.asarray(selected, np.float64)
    if w.sum() <= 0:
        return np.eye(len(w))
    w = w / w.sum()
    return aggregation.sync_mixing_matrix(w)


def async_mixing(alphas, contributors) -> np.ndarray:
    return aggregation.async_mixing_matrix(np.asarray(alphas),
                                           np.asarray(contributors))


@dataclasses.dataclass
class IslandClock:
    """Host-side straggler monitor: EWMA step-times per island (the Tier-B
    analogue of the FogBus2 profiler feeding Algorithm 2)."""
    n_islands: int
    beta: float = 0.3
    ewma: Optional[np.ndarray] = None

    def observe(self, step_times: np.ndarray):
        t = np.asarray(step_times, np.float64)
        self.ewma = t if self.ewma is None else \
            (1 - self.beta) * self.ewma + self.beta * t

    def selection(self, slack: float = 1.5) -> np.ndarray:
        """Islands slower than `slack` x median are dropped this round
        (Algorithm 2's T-threshold with T = slack * median estimate)."""
        if self.ewma is None:
            return np.ones(self.n_islands)
        med = np.median(self.ewma)
        return (self.ewma <= slack * med).astype(np.float64)
