"""Every file the benchmark finds by name loads, BENCHMARK.json keeps
the contract's shape, the peaks table refuses an unknown device, the
traffic generator repeats exactly for one seed, a cell and a metric
added as new files are found without touching the harness, and a family
or kind with no file is an error that names the file."""

import json
import re
import shutil

import numpy as np
import pytest

from chipbench import spec
from chipbench import traffic as TF

BENCH = spec.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_peaks_table():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.fullmatch(n) for n in names)
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or w in moved["workloads"], (m["name"], w)
        layers.setdefault(m["layer"], set()).add(m["name"])
    for cell in BENCH["workloads"]:
        assert spec.cell_metrics(BENCH, cell["name"], trace=True)
        assert len(spec.cell_metrics(BENCH, cell["name"], trace=False)) >= 2


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_load(cell):
    conf = spec.config(cell["config"])
    entry = spec.find(BENCH["configs"], cell["config"], "config")
    assert entry["file"] == f"chipbench/configs/{cell['config']}.json"
    assert sorted(entry["reduced"]) == sorted(conf["reduced"])
    assert set(conf["reduced"]) <= set(conf.get("published", {}))
    mix = spec.traffic(cell["traffic"])
    assert spec.runner(mix["kind"]).__name__ == "chipbench.train_cell"
    assert spec.family(conf).__name__ == "chipbench.families.decoder_lm"
    assert spec.limits(cell["name"])
    for m in spec.cell_metrics(BENCH, cell["name"], trace=True):
        assert callable(spec.metric_reader(m["name"]))


def test_program_agrees_with_each_config():
    for path in sorted((spec.ROOT / "configs").glob("*.json")):
        conf = spec.config(path.stem)
        spec.family(conf).program_config(conf)


@pytest.mark.parametrize("find,name,path", [
    (lambda n: spec.family({"family": n}), "no_such_lm", "chipbench/families/no_such_lm.py"),
    (lambda n: spec.family({"family": n}), "../decoder_lm", "chipbench/families/../decoder_lm.py"),
    (spec.runner, "serve", "chipbench/serve_cell.py")], ids=["family", "path", "kind"])
def test_unknown_family_or_kind_names_the_file(find, name, path):
    with pytest.raises(ModuleNotFoundError, match=re.escape(f"looked for {path}")):
        find(name)


def test_train_batches_repeat():
    mix = spec.traffic("train-bdwp28-2x4096")
    a = TF.train_batch(mix, 49155, 2 ** 31 + 7, 5)
    b = TF.train_batch(mix, 49155, 2 ** 31 + 7, 5)
    c = TF.train_batch(mix, 49155, 2 ** 31 + 7, 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (2, 4096) and np.array_equal(a[0][:, 1:], a[1][:, :-1])
    assert not np.array_equal(a[0][0], a[0][1])      # rows differ


def test_new_cell_and_metric_are_found_as_new_files(tmp_path):
    root = tmp_path / "chipbench"
    shutil.copytree(spec.ROOT, root, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "configs" / "dummy-model.json").write_text(
        json.dumps(dict(spec.config("qwen3-8b-l2-v18992"), name="dummy-model")))
    (root / "traffic" / "dummy-mix.json").write_text(
        json.dumps(dict(spec.traffic("train-bdwp28-1x4096"), batch=3)))
    (root / "cells" / "dummy-cell.json").write_text(json.dumps({"limits": {"x": 1}}))
    (root / "metrics" / "dummy.metric_pct.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy.metric_pct", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels", "moves": "train_tokens_per_s",
                               "workloads": ["dummy-cell"]})
    cell = spec.find(bench["workloads"], "dummy-cell", "workload")
    assert spec.config(cell["config"], root)["name"] == "dummy-model"
    assert spec.traffic(cell["traffic"], root)["batch"] == 3
    assert spec.limits("dummy-cell", root) == {"x": 1}
    listed = [m["name"] for m in spec.cell_metrics(bench, "dummy-cell", trace=True)]
    assert listed == ["dummy.metric_pct"]
    assert spec.metric_reader("dummy.metric_pct", root)({}) == 42.0
