"""``chip_smoke.py`` on the CPU at smoke width, so the script cannot rot.

The phase functions run with the Pallas kernels interpreted
(``impl="pallas_interpret"``); the device check, which demands a TPU, is
not run here except to show that it refuses the CPU.  The ``--chips 4``
phase runs in a child process on 4 forced host devices (jax fixes the
device count at first init), the pattern of ``tests/test_lowering.py``.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_serve_phase_smoke_width():
    out = chip_smoke.serve_phase(scale="smoke", impl="pallas_interpret",
                                 requests=2, prompt_len=16, gen=4, slots=2,
                                 workers=2)
    assert sorted(out["tokens"]) == [0, 1]
    assert all(len(t) == 4 for t in out["tokens"].values())
    assert out["logit_err"] >= 0.0


def test_stencil_phase_smoke_block():
    out = chip_smoke.stencil_phase(impl="pallas_interpret", bs=16)
    assert out["err"] < chip_smoke.STENCIL_TOL


def test_device_check_refuses_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.device_check(1)


def test_script_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


@pytest.mark.slow
def test_collectives_phase_four_host_devices():
    code = (
        "import json, chip_smoke\n"
        "r = chip_smoke.collectives_phase(impl='pallas_interpret', "
        "elems=1 << 12, grad_layers='smoke')\n"
        "print(json.dumps(sorted(r)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    names = json.loads(r.stdout.strip().splitlines()[-1])
    assert {"ring", "ring/segments=4", "ring/stage=pallas/wire=bf16",
            "ring/stage=pallas/wire=int8", "doubling",
            "hierarchical(pod=2,data=2)", "sync_grads", "halo"} <= set(names)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, from_env):
    # A child process: the cache directory is process-wide jax config.
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import enable_compile_cache\n"
        "d = enable_compile_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == d, d\n"
        "print(d)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        code += "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n"
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    got = r.stdout.strip().splitlines()[-1]
    if from_env:
        assert got == str(tmp_path / "cache")
        assert any((tmp_path / "cache").iterdir())
    else:
        assert got == str(ROOT / ".jax_cache")
