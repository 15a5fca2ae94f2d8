"""The harness is driven by data: a configuration, a cell and a per-layer
metric are added as new files plus entries of `BENCHMARK.json`, editing no
file that is there, and `run.py` runs them (at the tiny size, on the CPU
rehearsal path). And it refuses what it must: a cell with no file, a
device it has no peaks for, a real cell without a chip, a checkout without
the system under test."""

import json
import os
import types

import pytest

import rehearsal

NEW_METRIC = {
    "name": "test.steps_in_window", "unit": "steps", "better": "higher",
    "source": "program_counter", "layer": "trainer loop",
    "moves": "train.samples_per_s_per_chip", "workloads": ["tiny_dp1"],
}
NEW_READER = '''"""Steps the window counted (a reader a test added)."""


def read(obs):
    return obs.get("steps")
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_tiny_checkout(
        str(tmp_path_factory.mktemp("bench")),
        cells=[{"name": "no_such_mix", "config": "tiny_dp",
                "traffic": "never_written", "chips": 1, "why": "tests only"}],
        per_layer=[NEW_METRIC],
        readers=[("test.steps_in_window", NEW_READER)])


def test_added_files_and_entries_run_untraced(checkout):
    rc, result, out, err = rehearsal.run_cell(checkout, "tiny_dp1", seed=2**31 + 3)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["rehearsal"] is True and result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"train.samples_per_s_per_chip", "setup_s"}
    assert result["metrics"]["setup_s"]["unit"] == "s"
    assert list(result)[-1] == "checks"  # every number beside its limit, last
    assert "check grad1_norm_gap:" in err.strip().splitlines()[-2]
    assert "--metrics-dir" not in out  # an untraced run never blocks per step


def test_added_metric_is_read_in_a_traced_run(checkout):
    rc, result, out, err = rehearsal.run_cell(checkout, "tiny_dp1", trace=1)
    assert rc == 0, err[-3000:]
    got = result["metrics"]
    assert got["test.steps_in_window"]["value"] == result["attempted"] > 0
    assert got["test.steps_in_window"]["unit"] == "steps"
    assert got["train.data_wait_ms"]["value"] >= 0
    # listed for the real training cell alone, so not read here
    assert "train.compile_s" not in got
    # shares of a peak or of a roofline are left out where there is nothing
    # to read them from (no chip, no peaks): never 0, never a CPU number
    assert not {"train.mfu", "kernel.dal_ms", "train.device_idle"} & set(got)
    assert "setup_s" not in got


def test_serving_cell_runs_from_added_files(checkout):
    rc, result, out, err = rehearsal.run_cell(checkout, "tiny_chat1", seconds=3)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 5
    assert set(result["metrics"]) == {
        "serve.ttft_p95_ms", "serve.tpot_p95_ms", "setup_s"}
    assert result["notes"]["checked_tokens"] > 20


@pytest.mark.parametrize("cell", ["no_such_mix", "never_listed", "bert_large_dp1"])
def test_refused_cells_exit_nonzero_and_print_no_result(checkout, cell):
    rc, result, out, err = rehearsal.run_cell(checkout, cell)
    assert rc != 0 and result is None
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]
    assert "benchmarks/run.py:" in err


def test_checkout_without_the_program_is_refused(tmp_path):
    root = rehearsal.make_tiny_checkout(str(tmp_path))
    os.unlink(os.path.join(root, "pytorch_distributed_training_tpu"))
    rc, result, out, err = rehearsal.run_cell(root, "tiny_dp1")
    assert rc != 0 and result is None and "system under test" in err


def test_unknown_device_kind_and_too_few_chips_are_refused(monkeypatch):
    import jax

    import run

    def devices(kind, n):
        dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
        return lambda: [dev] * n

    monkeypatch.setattr(jax, "devices", devices("TPU v9 imaginary", 1))
    with pytest.raises(SystemExit) as e:
        run.device_report(1, rehearsal=False)
    assert e.value.code != 0
    monkeypatch.setattr(jax, "devices", devices("TPU v5 lite", 1))
    with pytest.raises(SystemExit):
        run.device_report(4, rehearsal=False)
    dev, peak = run.device_report(1, rehearsal=False)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_names_only_files_that_exist():
    repo = rehearsal.REPO
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in bench["configs"]:
        with open(os.path.join(repo, cfg["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == cfg["reduced"]
        assert os.path.isfile(os.path.join(
            repo, "benchmarks", "harness", body["driver"] + ".py"))
        assert os.path.isfile(os.path.join(
            repo, "benchmarks", "reference", body["reference"] + ".py"))
    for cell in bench["workloads"]:
        assert os.path.isfile(os.path.join(
            repo, "benchmarks", "traffic", cell["traffic"] + ".json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            repo, "benchmarks", "metrics", m["name"] + ".py"))
