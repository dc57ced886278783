"""CPU tests of the benchmark: ``python -m pytest bench/tests``.

They run the harness, the references and the trace reduction on the CPU
with four virtual devices (the 2x2 cell's mesh), at grid sizes a test can
hold. The tiny cells are the real ones with ``n`` made small.
"""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

TINY_N = 32


def make_root(dst: Path, n: int = TINY_N) -> Path:
    """A copy of the benchmark's manifest, configurations and traffic with
    every grid cut to ``n``."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dst / "bench" / "configs").mkdir(parents=True)
    (dst / "bench" / "traffic").mkdir(parents=True)
    for entry in manifest["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        if "n" in cfg:
            cfg["n"] = n
        (dst / entry["file"]).write_text(json.dumps(cfg))
    for cell in manifest["workloads"]:
        src = ROOT / "bench" / "traffic" / f"{cell['traffic']}.json"
        traffic = json.loads(src.read_text())
        traffic["n"] = n
        (dst / "bench" / "traffic" / src.name).write_text(json.dumps(traffic))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))
