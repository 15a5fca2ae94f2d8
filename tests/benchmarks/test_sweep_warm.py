"""`harness/sweep_warm.py`: a sweep whose shared contexts stay warm from
step to step. Its schedules are `traffic.schedule`'s own but for the
prefixes, and it runs a tiny mix with tenants through the server on the
CPU (the checkout of `test_glm52_rehearsal.py`)."""

import json
import os
import sys

import pytest
import rehearsal
from test_glm52_rehearsal import CELL, LISTED, TINY_AGENT, TINY_LATENT

sys.path.insert(0, os.path.join(rehearsal.REPO, "benchmarks"))
from harness import sweep_warm, traffic  # noqa: E402


def test_every_step_keeps_the_first_steps_prefixes():
    rates = [4.0, 6.0, 9.0]
    warm, steps = sweep_warm.warm_schedules(TINY_AGENT, 2147490011, 3.0, rates)
    own = [traffic.schedule(TINY_AGENT, 2147490011, 3.0, rate_rps=r)
           for r in rates]
    # traffic.schedule alone gives every step other contexts
    heads = [{r.tenant: r.prompt[: r.prefix_len] for r in reqs} for reqs in own]
    assert heads[0]["tenant0"] != heads[1]["tenant0"] != heads[2]["tenant0"]
    prefix = {}
    for reqs, alone in zip(steps, own):
        assert len(reqs) == len(alone)
        for r, a in zip(reqs, alone):
            head = prefix.setdefault(r.tenant, r.prompt[: r.prefix_len])
            assert r.prompt[: r.prefix_len] == head
            # all else is the schedule's own
            assert r.prompt[r.prefix_len:] == a.prompt[a.prefix_len:]
            assert (r.prompt_len, r.max_new_tokens, r.tenant) == (
                a.prompt_len, a.max_new_tokens, a.tenant)
            assert len(r.prompt) == r.prompt_len
    assert sorted(w.tenant for w in warm) == sorted(prefix)
    for w in warm:
        assert w.prompt.startswith(prefix[w.tenant]) and w.due_s == 0.0
        assert len(w.prompt) == w.prompt_len
    ids = [r.index for r in warm] + [r.index for reqs in steps for r in reqs]
    assert len(set(ids)) == len(ids)
    # steps follow the warming second, one after another
    assert 1.0 < steps[0][0].due_s < 4.0 <= steps[1][0].due_s < 7.0


def test_slots_busy_counts_first_token_to_done():
    log = [{"tokens": [1.0, 2.0], "done": 3.0}, {"tokens": [2.5], "done": None},
           {"tokens": [], "done": None}]
    assert [sweep_warm.slots_busy(log, t) for t in (0.5, 1.0, 2.6, 3.0)] == [
        0, 1, 2, 1]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_checkout(
        str(tmp_path_factory.mktemp("sweepwarm")), configs=[TINY_LATENT],
        traffic=[("tiny_agent", TINY_AGENT)], cells=[CELL],
        end_to_end_cells=[("serve.tpot_p95_ms", "tiny_agent1")], listed=LISTED)


def test_a_warm_sweep_runs_on_the_cpu(checkout):
    rc, _, err = rehearsal.run_script(
        checkout, "benchmarks/harness/sweep_warm.py", "--workload",
        "tiny_agent1", "--rates", "3,6", "--seconds", "2", "--seed", 2147490012)
    assert rc == 0, err[-2000:]
    with open(os.path.join(checkout, "chiprun_out/sweep_warm_tiny_agent1.json")) as f:
        table = json.load(f)
    assert [r["rate_rps"] for r in table["rows"]] == [3.0, 6.0]
    for row in table["rows"]:
        assert row["offered"] >= 2 and row["failed"] == 0
        assert 0 <= row["slots_busy_end"] <= row["slots_busy_max"] <= 4
    # both tenants' contexts were cached before the first step: every
    # request of the steps found its 32-token prefix (8 pages of 4)
    assert table["prefix_cached_tokens"] >= 32 * sum(
        r["offered"] for r in table["rows"])
