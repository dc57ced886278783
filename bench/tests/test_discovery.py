"""A configuration, a traffic mix and a metric are found by name: adding
them takes files under ``bench/`` and entries in ``BENCHMARK.json`` only."""

import json

from bench import harness
from bench.tests.conftest import ROOT


def test_manifest_names_resolve_to_files():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in manifest["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert (ROOT / "bench" / "kinds" / f"{cfg['kind']}.py").is_file()
        assert (ROOT / cfg["reference"]).is_file()
    for cell in manifest["workloads"]:
        spec = harness.load_cell(cell["name"])
        assert spec["end_to_end"] and spec["per_layer"]
    for m in manifest["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_added_config_and_cell_are_found_by_name(tiny_root, tmp_path):
    """A new configuration file and cell entry run with no code edit."""
    manifest = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/speed3d_r2c_f32.json").read_text())
    cfg.update(name="speed3d_r2c_f32_normal",
               input={"distribution": "uniform", "low": 0.0, "high": 1.0})
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench/configs/speed3d_r2c_f32_normal.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/n16.1x1.json").write_text(json.dumps(
        {"n": 16, "mesh": [1, 1], "in_flight": 2, "trace_steps": 4}))
    manifest["configs"].append({
        "name": cfg["name"], "source": cfg["source"],
        "file": "bench/configs/speed3d_r2c_f32_normal.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "speed3d_r2c_f32_normal.n16.1x1", "config": cfg["name"],
        "traffic": "n16.1x1", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    spec = harness.load_cell("speed3d_r2c_f32_normal.n16.1x1", tmp_path)
    assert spec["config"]["input"]["low"] == 0.0
    assert [m["name"] for m in spec["per_layer"]] == [
        m["name"] for m in manifest["per_layer"] if "workloads" not in m]
    r = harness.run("speed3d_r2c_f32_normal.n16.1x1", 5, 0.2, False, 0.0,
                    require_tpu=False, root=tmp_path)
    assert r["correct"]
