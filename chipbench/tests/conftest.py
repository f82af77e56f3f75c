"""CPU tests of the benchmark harness at sizes a test run holds.

  JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import os
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import common  # noqa: E402


@pytest.fixture
def qwen_smoke():
    """qwen1.5-4b's file with the program's smoke widths."""
    c = common.load_json(ROOT / "chipbench/configs/qwen1.5-4b.json")
    c.update(smoke=True, hidden_size=48, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, vocab_size=499,
             serve_pool={"block_size": 16, "num_blocks": 48})
    return c


def small_serve_traffic(name: str) -> dict:
    t = common.load_json(ROOT / f"chipbench/traffic/{name}.json")
    t.update(prompt_median=40, prompt_sigma=0.5, prompt_min=8,
             prompt_max=120, out_mean=10, out_min=2, out_max=30,
             max_batch=4, clients=4, rate=4.0)
    return t


def small_fl_traffic(name: str) -> dict:
    t = common.load_json(ROOT / f"chipbench/traffic/{name}.json")
    t.update(workers=8, samples_per_worker=64, fog_cells=2)
    return t


def drive(driver: str, cfgfile, t, seed=2**31 + 5, seconds=2.0):
    """A whole run of a driver without the look for a chip."""
    import importlib
    import jax
    mod = importlib.import_module(f"chipbench.drivers.{driver}")
    return mod.run(cfgfile, t, seed=seed, seconds=seconds, traced=False,
                   clock=common.CompileClock(), t_start=time.perf_counter(),
                   devs=jax.devices())
