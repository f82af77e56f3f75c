"""CPU rehearsal of chip_smoke.py: its phases at smoke size, the island
path on four virtual devices, the compile-cache placement, and the
refusal to run anywhere but a TPU.  The numbers checked here are counts
and parities; no timing is asserted."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_a_non_tpu_platform(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out, err = capsys.readouterr()
    assert f"'{jax.devices()[0].platform}'" in err
    assert out == ""                    # no result line, no "ok": true


def test_paged_phase_at_smoke_size():
    res, params = chip_smoke.phase_paged(
        get_smoke_config("qwen1.5-4b"), seed=0, n_requests=4,
        prompt_lens=(20, 60), gen=8)
    assert res["ok"], res
    assert res["requests"] == 4 and res["tokens"] == 32
    assert res["agreement_rate"] >= chip_smoke.MIN_AGREEMENT
    assert params is not None


def test_int8_phase_at_smoke_size():
    from repro.models import build_model
    cfg = get_smoke_config("qwen1.5-4b")
    params = build_model(cfg).init(jax.random.key(0))
    res = chip_smoke.phase_int8(cfg, params, seed=0, on_chip=False, T=16,
                                n=4)
    assert res["ok"], res
    assert res["cache_spec"] == "head/int8" and res["generated"] == 4
    assert res["rel_max_err"] <= res["rel_bound"]
    assert res["int4_control"]["rel_max_err"] > res["rel_bound"]


def test_federated_phase_at_smoke_size():
    res = chip_smoke.phase_federated(seed=0, on_chip=False, n_workers=8,
                                     per_worker=128, fog_cells=2, rounds=2)
    assert res["ok"], res
    assert max(res["exchange_max_abs"]) <= chip_smoke.EXCHANGE_PARITY


_FOUR_DEVICES = """
import dataclasses, json, sys
sys.path.insert(0, {root!r})
import chip_smoke
from repro.configs import get_smoke_config
from repro.core import federated
# "auto" takes the kernel, interpreted; "ref" stays the jnp reference
federated._resolve_impl = lambda impl: "pallas" if impl == "auto" else impl
cfg = dataclasses.replace(get_smoke_config("granite-20b"), d_model=128,
                          num_heads=4, num_kv_heads=2, head_dim=32,
                          d_ff=256, vocab_size=1024)
res = chip_smoke.phase_four_chips(seed=0, on_chip=False, cfg=cfg, batch=4,
                                  seq=64)
print(json.dumps(res, default=float))
"""


def test_four_chip_phase_on_four_virtual_devices():
    """The island axis lands on `pod` across 4 devices, the Pallas
    quantise runs per island inside the shard_map, and the sharded run
    matches the same islands stacked on one device."""
    r = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES.format(root=str(ROOT))],
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["ok"], res
    assert res["mesh"] == {"pod": 4, "data": 1, "model": 1}
    assert res["island_devices"] == 4
    assert res["exchange_max_abs"] <= chip_smoke.EXCHANGE_PARITY


_CACHE = """
import json, sys
import jax
from repro.launch.compile_cache import enable_compile_cache
where = enable_compile_cache()
if len(sys.argv) > 1:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
print(json.dumps([where, jax.config.jax_compilation_cache_dir]))
"""


def test_compile_cache_follows_the_environment(tmp_path):
    r = subprocess.run([sys.executable, "-c", _CACHE, "compile"],
                       env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    where, configured = json.loads(r.stdout.strip().splitlines()[-1])
    assert where == configured == str(tmp_path)
    assert any(tmp_path.iterdir())     # the entry landed there


def test_compile_cache_defaults_to_the_checkout():
    r = subprocess.run([sys.executable, "-c", _CACHE], env=_cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    where, configured = json.loads(r.stdout.strip().splitlines()[-1])
    assert where == configured == str(ROOT / ".jax_cache")
