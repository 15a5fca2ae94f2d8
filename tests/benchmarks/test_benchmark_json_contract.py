"""`BENCHMARK.json` against the limits the driver refuses a file over,
before a single run: exact keys, names, units, bounds, the share of
four-chip cells, what each metric moves and where it is reported."""

import json
import os
import re

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|projection)_size|_dim$|_rank$|head_size"
    r"|expansion|experts_per_tok|n_embd|n_inner")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_command_paths_and_length(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32 and all(map(line, bench["command"]))
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    budget = (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200
    assert budget <= 43200 and 1 <= cells <= 24


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        with open(os.path.join(REPO, c["file"])) as f:
            assert isinstance(json.load(f), dict)


def test_workloads(bench):
    cells = bench["workloads"]
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and line(w["why"])
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in e2e}
    reports = {}  # end-to-end metric -> cells that report it
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        reports[m["name"]] = set(m.get("workloads", cells))
        assert reports[m["name"]] and reports[m["name"]] <= cells
    assert "workloads" not in next(m for m in e2e if m["name"] == "setup_s")
    for cell in cells:  # set-up and at least one other end-to-end metric
        assert sum(cell in r for r in reports.values()) >= 2
    layers_of_cell = {c: 0 for c in cells}
    for m in layer:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and line(m["layer"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in reports
        where = set(m.get("workloads", reports[m["moves"]]))
        assert where and where <= reports[m["moves"]], m["name"]
        for c in where:
            layers_of_cell[c] += 1
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert all(layers_of_cell.values())
    # the whole step's share of the peak, in every cell of its kind
    for kind, e in (("train.mfu", "train.samples_per_s_per_chip"),
                    ("serve.mfu", "serve.tpot_p95_ms")):
        m = next(m for m in layer if m["name"] == kind)
        assert set(m.get("workloads", reports[m["moves"]])) == reports[e]


def test_every_per_layer_metric_lists_its_cells(bench):
    """A PR that adds a cell is refused while a metric that moves its
    end-to-end metric has no list: the new cell would have to report a
    metric whose reader may find nothing to read there."""
    for m in bench["per_layer"]:
        assert m.get("workloads"), m["name"]


def test_files_under_paths_are_named_from_name_characters(bench):
    for p in bench["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert PATH.match(rel), rel
