"""BENCHMARK.json against the contract's limits, and the loader refusing
what it cannot find with a message that says where it looked."""
import json
import os
import re
import shutil

import pytest

from benchmark.harness import manifest as M

REPO = M.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def manifest():
    return M.load_manifest()


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"][:2] == ["python3", "benchmark/run.py"]
    assert isinstance(manifest["run_seconds"], int) \
        and 1 <= manifest["run_seconds"] <= 51
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"])
        assert LINE.match(c["why"]) and c["file"].startswith("benchmark/")
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        names.append(c["name"])
    cells = []
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert LINE.match(w["why"])
        cells.append(w["name"])
    assert len(set(cells)) == len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)
    e2e = {}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert e2e["setup_s"] == set(cells)
    metric_names = list(e2e)
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert LINE.match(m["layer"]) and m["source"] in M.SOURCES
        # reported only by cells that report the metric it moves
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]]
        metric_names.append(m["name"])
    assert len(set(metric_names)) == len(metric_names)
    for cell in cells:
        assert sum(cell in c for n, c in e2e.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_every_cell_loads_with_its_files_and_readers(manifest):
    for w in manifest["workloads"]:
        cell = M.load_cell(w["name"])
        assert cell.kind in M.TRAFFIC_KINDS
        assert ("mesh" in cell.settings) == (cell.kind == "train")
        for m in cell.per_layer:
            assert callable(M.load_reader(m.name))
        for c in manifest["configs"]:
            if c["name"] == cell.config_name:
                assert cell.config["source"] == c["source"]
                assert cell.config["reduced"] == c["reduced"] == []


@pytest.fixture
def copy(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def _edit(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    fn(m)
    with open(path, "w") as f:
        json.dump(m, f)


def test_loader_refuses_a_missing_file_saying_where_it_looked(copy):
    os.remove(copy / "benchmark/traffic/offline-closed.json")
    with pytest.raises(M.ManifestError,
                       match="benchmark/traffic/offline-closed.json"):
        M.load_cell("serve-gpt2-large-sat", str(copy))
    os.remove(copy / "benchmark/cells/train-gpt2-124m.json")
    with pytest.raises(M.ManifestError,
                       match="benchmark/cells/train-gpt2-124m.json"):
        M.load_cell("train-gpt2-124m", str(copy))
    with pytest.raises(M.ManifestError,
                       match="benchmark/layer_metrics/no.such_metric.py"):
        M.load_reader("no.such_metric", str(copy))
    with pytest.raises(M.ManifestError, match="no workload 'nope'"):
        M.load_cell("nope", str(copy))


@pytest.mark.parametrize("bad", ["has space", "a/b", "", "x" * 65, "µs"])
def test_loader_refuses_a_bad_name(copy, bad):
    with pytest.raises(M.ManifestError, match="is not a name"):
        M.load_cell(bad, str(copy))
    _edit(copy, lambda m: m["workloads"][0].update(traffic=bad))
    with pytest.raises(M.ManifestError, match="is not a name"):
        M.load_cell("train-gpt2-124m", str(copy))


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17])
def test_loader_refuses_a_bad_unit(copy, bad):
    _edit(copy, lambda m: m["end_to_end"][0].update(unit=bad))
    with pytest.raises(M.ManifestError, match="unit"):
        M.load_cell("train-gpt2-124m", str(copy))


def test_per_layer_metric_must_move_a_metric_its_cell_reports(copy):
    _edit(copy, lambda m: m["per_layer"][1].update(
        moves="serve_tokens_per_s"))
    with pytest.raises(M.ManifestError, match="does not report"):
        M.load_cell("train-gpt2-124m", str(copy))
