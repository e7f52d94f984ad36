"""Discovery by name, name rules, and the committed BENCHMARK.json."""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from chipbench_tiny import CHECKOUT, CHIP
from registry import Registry, check_name

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def _tree(tmp_path):
    """A checkout with one config, one mix and one metric of each kind."""
    root = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "e2e_metrics", "layer_metrics"):
        (root / d).mkdir(parents=True)
    (root / "configs" / "toy-cfg.json").write_text('{"kind": "toy", "x": 1}')
    (root / "gen_toy.py").write_text(
        "class Driver:\n"
        "    def __init__(self, cfg, mix, seed):\n"
        "        self.cfg, self.mix, self.seed = cfg, mix, seed\n")
    (root / "traffic" / "toy-mix.json").write_text('{"spec": {"mode": "flat"}}')
    (root / "e2e_metrics" / "toy_s.py").write_text("def read(run):\n    return 1.5\n")
    (root / "layer_metrics" / "toy_share.cell.py").write_text(
        "def read(run):\n    return None\n")
    bench = {
        "configs": [{"name": "toy-cfg", "file": "benchmarks/chip/configs/toy-cfg.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy-cfg", "traffic": "toy-mix",
                       "chips": 1}],
        "end_to_end": [{"name": "toy_s", "unit": "s"}],
        "per_layer": [{"name": "toy_share.cell", "unit": "%", "workloads": ["toy.cell"]},
                      {"name": "other", "unit": "%", "workloads": ["elsewhere"]}],
    }
    return Registry(bench, root)


def test_discovers_files_dropped_into_their_directories(tmp_path):
    reg = _tree(tmp_path)
    cell = reg.cell("toy.cell")
    cfg = reg.config(cell["config"])
    assert cfg["x"] == 1 and cfg["_entry"]["name"] == "toy-cfg"
    assert reg.traffic(cell["traffic"]) == {"spec": {"mode": "flat"}}
    assert [m["name"] for m in reg.metrics("end_to_end", "toy.cell")] == ["toy_s"]
    assert [m["name"] for m in reg.metrics("per_layer", "toy.cell")] == ["toy_share.cell"]
    assert reg.reader("end_to_end", "toy_s")(None) == 1.5
    assert reg.reader("per_layer", "toy_share.cell")(None) is None


def test_discovers_a_new_kind_of_configuration(tmp_path):
    """A kind of configuration is a generator file gen_<kind>.py; the
    harness opens a cell of it with no edit of its own."""
    import harness

    reg = _tree(tmp_path)
    _, device, drv = harness.open_cell(reg, "toy.cell", 11, require_tpu=False)
    assert type(drv).__name__ == "Driver" and drv.cfg["x"] == 1
    assert drv.mix == {"spec": {"mode": "flat"}} and drv.seed == 11
    assert device["count"] == 1
    with pytest.raises(KeyError):
        reg.generator("nope")
    with pytest.raises(ValueError):
        reg.generator("../toy")


@pytest.mark.parametrize("bad", ["a/b", "a b", "", "-x", ".x", "x" * 65, "café",
                                 "a,b", "a\tb"])
def test_refuses_illegal_names(bad, tmp_path):
    with pytest.raises(ValueError):
        check_name(bad)
    reg = _tree(tmp_path)
    with pytest.raises(ValueError):
        reg.traffic(bad)
    with pytest.raises(ValueError):
        reg.reader("per_layer", bad)


def test_unknown_names_are_errors(tmp_path):
    reg = _tree(tmp_path)
    with pytest.raises(KeyError):
        reg.cell("nope")
    with pytest.raises(KeyError):
        reg.reader("end_to_end", "nope")


def test_committed_benchmark_is_whole():
    """Every name legal and unique, every file there, every cell reports
    setup_s, another end-to-end metric and a per-layer metric, and each
    per-layer metric moves an end-to-end metric its cells report."""
    reg = Registry(BENCH)
    names = set()
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[sec]:
            check_name(e["name"])
            assert e["name"] not in names
            names.add(e["name"])
    for c in BENCH["configs"]:
        assert (CHECKOUT / c["file"]).is_file()
        assert all(check_name(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        reg.config(w["config"])
        reg.traffic(w["traffic"])
        e2e = {m["name"] for m in reg.metrics("end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = reg.metrics("per_layer", w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in e2e
    for sec in ("end_to_end", "per_layer"):
        for m in BENCH[sec]:
            assert callable(reg.reader(sec, m["name"]))
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]


def test_without_a_tpu_run_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", "kron20-coarsen",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=CHECKOUT,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr
