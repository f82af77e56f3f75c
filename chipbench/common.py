"""Pieces every driver shares: the device check, the compile clock, seeded
keys, percentiles and the window's host spans.

Nothing here imports the program; drivers import it after `require_chip`
has passed."""
from __future__ import annotations

import contextlib
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent
#: what a run writes (traces); listed in .gitignore
OUT_DIR = ROOT / ".chipbench"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chip(chips: int):
    """The attached devices, when they are `chips` or more TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"'{devs[0].platform}' device(s) ({devs[0].device_kind})")
    return devs


def device_info(devs) -> dict:
    """platform, kind, count and the peak bytes of the fullest chip."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def bytes_limit(devs) -> int:
    return int((devs[0].memory_stats() or {}).get("bytes_limit", 0))


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may pass 2**31)."""
    import jax
    key = jax.random.key(seed % (1 << 32))
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), for every
    program however quickly it compiles, so that only a checkout's first
    run of a cell compiles (the eager exchange alone is ~100 small
    programs)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events
    (tracing and lowering nest inside and are left out)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


def quantile(values, q: float) -> float:
    """The q-quantile (0 < q < 1) of every value, by linear interpolation
    between order statistics (numpy's default); NaN when there are none."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = q * (len(v) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Spans:
    """Host spans of the harness's own calls, on the profiler's timeline
    too when a trace is on: (name, start, end) in perf_counter seconds."""

    def __init__(self, traced: bool):
        self.items: list[tuple[str, float, float]] = []
        self.traced = traced

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.items.append((name, t0, time.perf_counter()))


def say(**fields):
    """One diagnostic line on stdout (never the last one)."""
    print(json.dumps(fields, default=float), flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def program_path():
    """Make the program under test importable (it lives in src/)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
