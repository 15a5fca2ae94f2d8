"""What decides `correct`, shown to fail: the control (the reference one
precision down, put in the program's place) and every fault a cell can
have, planted under the harness at a size a test can hold. Each run is
`run.prepare` + `run.execute` in a child process on the CPU: the look for
a chip is skipped (the tiny configurations are rehearsals), the rest of a
run is driven as the chip would see it."""

import os

import pytest

import faults
import rehearsal


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = rehearsal.make_tiny_checkout(str(tmp_path_factory.mktemp("bench")))
    with open(os.path.join(root, "fault_run.py"), "w") as f:
        f.write(faults.FAULT_RUN.format(
            tests_dir=os.path.dirname(os.path.abspath(__file__))))
    return root


def drive(root, cell, fault, seed=11):
    seconds = 0.5 if cell == "tiny_dp1" else 3
    rc, result, err = rehearsal.run_script(
        root, "fault_run.py", cell, fault, seed, seconds)
    assert rc == 0 and result is not None, err[-3000:]
    return result


def over_limit(result, numbers: dict) -> list:
    limits = {k: v["limit"] for k, v in result["checks"].items()}
    return [k for k, v in numbers.items() if not v <= limits[k]]


@pytest.mark.parametrize("cell,control", [
    ("tiny_dp1", "reference_bfloat16"), ("tiny_chat1", "reference_int8"),
    ("tiny_prefill1", "reference_int8")])
def test_sound_run_is_correct_and_its_control_is_not(checkout, cell, control):
    result = drive(checkout, cell, "control")
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    # the same numbers, read off the reference one precision down
    assert over_limit(result, result["notes"]["faults"][control])
    if cell == "tiny_prefill1":
        # short outputs: the mix's own `check` asks for more requests than
        # the configuration's four, so that the sample still holds 60 tokens
        assert result["notes"]["checked_requests"] > 4
        assert result["notes"]["checked_tokens"] >= 60


def test_half_batch_planted_in_the_reference_reads_over_the_limits(checkout):
    result = drive(checkout, "tiny_dp1", "control", seed=12)
    assert result["correct"] is True, result["checks"]
    assert "grad1_norm_gap" in over_limit(
        result, result["notes"]["faults"]["half_batch"])


@pytest.mark.parametrize("cell,fault,caught_by", [
    ("tiny_dp1", "unchanged_state", {"grad1_norm_gap", "delta_norm_gap"}),
    ("tiny_dp1", "half_batch", {"grad1_norm_gap"}),
    ("tiny_chat1", "altered_token", {"max_logit_gap"}),
    ("tiny_prefill1", "altered_token", {"max_logit_gap"}),
])
def test_fault_under_the_harness_comes_out_not_correct(
        checkout, cell, fault, caught_by):
    result = drive(checkout, cell, fault)
    assert result["correct"] is False
    failed = {k for k, v in result["checks"].items()
              if not v["value"] <= v["limit"]}
    assert caught_by <= failed, result["checks"]
    if fault == "unchanged_state":
        # a state left unchanged reads 1 by the measure, whatever the size
        assert result["checks"]["delta_norm_gap"]["value"] == pytest.approx(1.0)
