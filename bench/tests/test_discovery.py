"""A new cell is data: configuration, mix, limits and metric readers are
found by the names in BENCHMARK.json, and adding them edits no file."""
import json
import shutil

import pytest

from bench import spec


def test_every_named_file_exists():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        c = spec.Cell(cell["name"], bench)
        assert c.config["name"] == cell["config"]
        assert "max_logit_gap" in c.limits
        c.reference()
        for trace in (False, True):
            for m in c.metrics(trace):
                assert callable(c.reader(m["name"]).read)
    for conf in bench["configs"]:
        assert conf["file"].startswith(bench["paths"][0] + "/")


def test_each_cell_reports_what_its_per_layer_metrics_move():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        c = spec.Cell(cell["name"], bench)
        e2e = {m["name"] for m in c.metrics(False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = c.metrics(True)
        assert layer and all(m["moves"] in e2e for m in layer)


@pytest.fixture
def new_tree(tmp_path, monkeypatch):
    """A copy of the benchmark with one more configuration, mix, metric
    and cell, added as new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    bench = spec.load_benchmark()
    conf = json.loads((root / "bench/configs/deepseek-7b.int8.json")
                      .read_text())
    conf["name"] = "tiny-dense"
    (root / "bench/configs/tiny-dense.json").write_text(json.dumps(conf))
    (root / "bench/traffic/bursty.open.json").write_text(json.dumps(
        dict(json.loads((root / "bench/traffic/mixed.open.json")
                        .read_text()), rate_rps=2.0)))
    (root / "bench/limits/tiny.bursty.open.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 0.5}}))
    (root / "bench/metrics/queue_depth.py").write_text(
        "def read(run):\n    return 7.0\n")
    bench["configs"].append({"name": "tiny-dense", "source": "x",
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny.bursty.open",
                               "config": "tiny-dense",
                               "traffic": "bursty.open", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "queue_depth", "unit": "requests",
                               "better": "lower", "source": "host_clock",
                               "layer": "scheduler", "moves": "itl_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH", root / "bench")
    return root, before


def test_a_new_cell_is_new_files_and_entries(new_tree):
    root, before = new_tree
    cell = spec.Cell("tiny.bursty.open", spec.load_benchmark(root))
    assert cell.config["name"] == "tiny-dense"
    assert cell.traffic["rate_rps"] == 2.0
    assert cell.limits["max_logit_gap"]["limit"] == 0.5
    names = [m["name"] for m in cell.metrics(True)]
    # no workloads key: reported wherever what it moves is reported
    assert "queue_depth" in names
    assert cell.reader("queue_depth").read(None) == 7.0
    old = spec.Cell("ds7b.mixed.open", spec.load_benchmark(root))
    assert "queue_depth" in [m["name"] for m in old.metrics(True)]
    for rel, data in before.items():
        assert (root / rel).read_bytes() == data, f"{rel} was edited"
