"""Shared set-up of the benchmark's own tests (``python -m pytest bench/tests``).

They run on the CPU.  ``smoke_root`` builds a copy of the benchmark's data
(BENCHMARK.json, configurations, mixes, cell files) in a temporary
directory, with every configuration at a width a CPU run can hold and the
Pallas kernels interpreted, so that the harness runs whole here.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

SMOKE = {
    "granite-3-2b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                         d_ff=128, vocab=128),
}

# The logit-gap limit at smoke width, set as the chip limits are, from CPU
# readings over eleven seeds (calibrate.py at smoke width): the program at
# most 0.0048, the float8 control at least 0.0246.
SMOKE_LIMIT = {"granite-3-2b": 0.012}


def make_smoke_root(dest: pathlib.Path) -> pathlib.Path:
    (dest / "bench").mkdir(parents=True)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for sub in ("configs", "mixes", "cells"):
        shutil.copytree(BENCH / sub, dest / "bench" / sub, dirs_exist_ok=True)
    for name, small in SMOKE.items():
        path = dest / "bench" / "configs" / f"{name}.json"
        conf = json.loads(path.read_text())
        conf["model"].update(small, kernel_impl="pallas_interpret")
        path.write_text(json.dumps(conf))
    for path in (dest / "bench" / "mixes").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(prompt_len=32, warmup_s=0.5)
        mix["output"].update(median=10, max=24)
        mix["check"].update(min_tokens=80, min_requests=3, max_requests=8)
        if mix["loop"] == "closed":
            mix.update(clients=6, requests_per_client=64)
        path.write_text(json.dumps(mix))
    config_of = {w["name"]: w["config"] for w in spec["workloads"]}
    for path in (dest / "bench" / "cells").glob("*.json"):
        cell = json.loads(path.read_text())
        if "rate_per_s" in cell:
            cell["rate_per_s"] = 10.0
        cell["limits"]["logit_gap"] = SMOKE_LIMIT[config_of[path.stem]]
        path.write_text(json.dumps(cell))
    return dest


@pytest.fixture
def smoke_root(tmp_path):
    return make_smoke_root(tmp_path / "root")


@pytest.fixture
def no_chip_check(monkeypatch):
    """Skip the harness's look for a chip: the rest of a run drives the
    CPU, reporting the device the chip runs report."""
    import harness
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
