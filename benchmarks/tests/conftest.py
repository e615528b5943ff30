"""The benchmark's own tests run on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``benchmarks/``) whose configurations hold 2^10 rows and whose pools
are small, so a whole run of a cell fits a test; ``tiny_devices_root``
is the same with each configuration's reference on the devices."""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DPF_TPU_COMPILE_CACHE", "0")
os.environ.setdefault("DPF_TPU_TUNE_CACHE", "0")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def copy_benchmark(dst: str) -> str:
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst


def edit_json(path: str, **changes) -> None:
    with open(path) as f:
        d = json.load(f)
    d.update(changes)
    with open(path, "w") as f:
        json.dump(d, f)


def make_tiny_root(dst: str, **check) -> str:
    root = copy_benchmark(dst)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for c in spec["configs"]:
        edit_json(os.path.join(root, c["file"]), log2_rows=10,
                  check=dict({"recover": 64, "reference": 4}, **check))
    for w in spec["workloads"]:
        path = os.path.join(root, "benchmarks", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            if "pool_keys" in json.load(f):
                edit_json(path, pool_keys=2048)
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="session")
def tiny_devices_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench_devices")),
                          reference_on="devices")


def cpu_device(chips, root):
    """Stands in for the harness's look for a chip."""
    return {"platform": "cpu", "kind": "cpu", "count": 1}
