"""Cells, configurations, traffic mixes and metrics are found by name,
from files alone: adding them needs no edit of a file that is there."""

import json
import shutil

import pytest

from harness import spec

CHECKOUT = spec.CHECKOUT


def test_every_cell_resolves():
    bench = spec.load_json(CHECKOUT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"])
        assert cell.driver().Driver
        assert cell.reference()
        for m in cell.per_layer:
            assert spec.metric_reader(cell, m["name"]).read
        assert cell.end_to_end and cell.per_layer


CELLS = [w["name"] for w in
         spec.load_json(CHECKOUT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_driver_reports_each_end_to_end_metric(name):
    """Every end-to-end metric that applies to a cell, set-up aside, is
    one that the cell's driver reports (``run.py`` refuses a run that
    lacks one); a metric whose ``workloads`` name the wrong cell fails
    here.  The driver runs a few units at the small sizes."""
    import jax
    import small
    from harness.context import RunContext
    cell = small.small_cell(name)
    drv = cell.driver().Driver(RunContext(cell=cell, seed=2**31 + 13,
                                          devices=jax.devices()))
    drv.begin()
    for _ in range(3):
        drv.unit()
    reported = set(drv.facts(1.0)["end_to_end"])
    wanted = {m["name"] for m in cell.end_to_end} - {"setup_s"}
    assert wanted and wanted <= reported, (wanted, reported)


def test_unknown_cell():
    with pytest.raises(KeyError):
        spec.find_cell("no-such.cell")


@pytest.fixture
def copy_of_benchmark(tmp_path):
    """A checkout holding the benchmark's committed files and src/."""
    bench = spec.load_json(CHECKOUT / "BENCHMARK.json")
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    root = bench["paths"][0]
    shutil.copytree(CHECKOUT / root, tmp_path / root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path, bench


def test_new_cell_config_traffic_and_metric_from_files(copy_of_benchmark):
    checkout, bench = copy_of_benchmark
    root = checkout / bench["paths"][0]
    # a new configuration, a new traffic mix and a new metric: new files ...
    conf = json.loads((root / "configs" / "kernelgen-e5.json").read_text())
    conf["grid_2d"] = [1024, 1024]
    (root / "configs" / "kernelgen-e5-small.json").write_text(json.dumps(conf))
    shutil.copy(root / "configs" / "kernelgen-e5.reference.py",
                root / "configs" / "kernelgen-e5-small.reference.py")
    traffic = json.loads((root / "traffic" / "jacobi.json").read_text())
    traffic["scalars"] = {"c0": 0.25, "c1": 0.125, "c2": 0.0625}
    (root / "traffic" / "jacobi-quarter.json").write_text(json.dumps(traffic))
    (root / "metrics" / "sweeps_per_s.py").write_text(
        "def read(ctx, facts, trace):\n"
        "    return facts['units'] / facts['window_s']\n")
    # ... and entries in BENCHMARK.json
    bench["configs"].append({
        "name": "kernelgen-e5-small", "source": "https://arxiv.org/abs/2301.11389",
        "file": f"{bench['paths'][0]}/configs/kernelgen-e5-small.json",
        "reduced": ["grid_2d"], "why": "test"})
    bench["workloads"].append({
        "name": "kernelgen-e5-small.jacobi-quarter",
        "config": "kernelgen-e5-small", "traffic": "jacobi-quarter",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "sweeps_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "stencil_gpts_s",
        "workloads": ["kernelgen-e5-small.jacobi-quarter"]})
    bench["end_to_end"][0]["workloads"].append(
        "kernelgen-e5-small.jacobi-quarter")
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.find_cell("kernelgen-e5-small.jacobi-quarter",
                          checkout=checkout)
    assert cell.config["grid_2d"] == [1024, 1024]
    assert cell.traffic["scalars"]["c0"] == 0.25
    assert cell.driver().__file__.endswith("drivers/stencil_sweeps.py")
    assert cell.reference().__file__.endswith("kernelgen-e5-small.reference.py")
    assert [m["name"] for m in cell.end_to_end] == ["stencil_gpts_s", "setup_s"]
    assert "sweeps_per_s" in [m["name"] for m in cell.per_layer]
    reader = spec.metric_reader(cell, "sweeps_per_s")
    assert reader.read(None, {"units": 10, "window_s": 2.0}, None) == 5.0


def test_new_cell_runs_end_to_end(copy_of_benchmark):
    """A cell made of new files only runs through the harness."""
    import small
    checkout, bench = copy_of_benchmark
    root = checkout / bench["paths"][0]
    traffic = json.loads((root / "traffic" / "jacobi.json").read_text())
    traffic["scalars"] = {"c0": 0.25, "c1": 0.125, "c2": 0.0625}
    (root / "traffic" / "jacobi-quarter.json").write_text(json.dumps(traffic))
    bench["workloads"].append({
        "name": "kernelgen-e5.jacobi-quarter", "config": "kernelgen-e5",
        "traffic": "jacobi-quarter", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("kernelgen-e5.jacobi-quarter")
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.find_cell("kernelgen-e5.jacobi-quarter", checkout=checkout)
    result = small.run_cell_small(cell)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"stencil_gpts_s", "setup_s"}
