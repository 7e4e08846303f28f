"""BENCHMARK.json and the files it names: every configuration, traffic
mix and per-layer metric is a file found by its name."""
import os

import pytest

from harness.cell import BENCH_DIR, ROOT, load_cell, load_json

SPEC = load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_loads_and_fits_its_budget(cell):
    c = load_cell(cell)
    assert c.batch >= 1 and c.num_pages == c.batch * c.pages_per_slot + 1
    assert c.batch * c.pages_per_slot * c.page_bytes() <= \
        c.serve["kv_budget_bytes"]
    limits = set(c.config["check"]["limits"])
    assert "token_gap_mean" in limits
    assert limits <= {"token_gap_mean", "accept_gap_mean"}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    src = open(path).read()
    assert "def read(ctx)" in src


def test_configs_name_their_reductions():
    for conf in SPEC["configs"]:
        c = load_json(os.path.join(ROOT, conf["file"]))
        assert c["source"] == conf["source"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
