"""BENCHMARK.json and the files it names fit together."""

import json
import os
import re

import pytest

from bench import harness

from .conftest import BENCH, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_names():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in SPEC[group]]
        assert len(got) == len(set(got)), group


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = harness.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    harness.load_module(BENCH, "loops", c.mix["loop"])
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        harness.load_module(BENCH, "metrics", m["name"])
    assert set(c.config["checks"]) == {"dist_err", "rank_gap"}


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"]
    assert body["reduced"] == cfg["reduced"]
    assert body["series_len"] % 16 == 0 and body["n_series"] > 0


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def test_peaks_have_no_cpu_row():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    assert "cpu" not in {k.lower() for k in table}
    with pytest.raises(KeyError):
        harness.peaks_for("cpu", BENCH)
