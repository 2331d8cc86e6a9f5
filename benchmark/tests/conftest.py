"""Tests of the benchmark itself: ``python -m pytest benchmark/tests``
(tier-1 runs ``tests/`` only).  They run on the CPU backend with four
virtual devices; nothing here is a device number."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of BENCHMARK.json and benchmark/ (as an export holds them)
    that a test may add files to: ``(root, bench, save)`` with ``bench``
    the parsed BENCHMARK.json, written into the copy by ``save(bench)``."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def save(changed: dict) -> None:
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(changed, f)
    save(bench)
    return root, bench, save
