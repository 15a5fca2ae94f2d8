"""bench.py's chip contract: no chip, no benchmark.

The default mode times one training cell and reports it as
samples/sec/chip, so it must refuse to run anywhere but on a TPU — with a
structured, diagnosable failure line and a non-zero exit, never a CPU
timing under a device metric's name — and it must touch the backend once,
in its own process: a chip belongs to one process, so a probe child
would have to release it before the benchmark could start.
"""

import json
import os
import subprocess
import sys
import types

import pytest

import bench  # root-level module (pyproject pythonpath = ["."])

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_bench_without_chip_exits_nonzero_with_structured_failure():
    """The command a user (and the driver) runs, on this CPU-only box."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=_REPO,
    )
    assert proc.returncode != 0
    rec = _last_json(proc.stdout)
    assert rec["value"] is None and rec["unit"] == "samples/sec/chip"
    assert "no TPU" in rec["metric"]
    assert rec["error"]["device"]["platform"] == "cpu"
    assert rec["error"]["device"]["count"] >= 1


def test_bench_touches_the_backend_in_process_only(monkeypatch, capsys):
    """No child process on the default path: the platform check is this
    process's own ``jax.devices()``."""

    def no_children(*a, **k):
        raise AssertionError("bench default mode spawned a subprocess")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(subprocess, "run", no_children)
    assert bench.main([]) is None
    rec = _last_json(capsys.readouterr().out)
    assert rec["error"]["device"]["platform"] == "cpu"


def test_bench_backend_init_failure_is_structured(monkeypatch, capsys):
    """A backend that fails to come up names its cause in the artifact."""
    import jax

    def unavailable():
        raise RuntimeError("UNAVAILABLE: TPU initialization failed")

    monkeypatch.setattr(jax, "devices", unavailable)
    assert bench.main([]) is None
    rec = _last_json(capsys.readouterr().out)
    assert rec["value"] is None
    assert "backend unavailable" in rec["metric"]
    assert "UNAVAILABLE" in rec["error"]["message"]


def test_bench_runs_on_a_tpu_and_names_the_device(monkeypatch, capsys):
    """With a TPU reported the check passes through to the benchmark and
    the device rides the result (platform, kind, count)."""
    import jax

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    monkeypatch.setattr(
        "pytorch_distributed_training_tpu.train.compile."
        "enable_compile_cache", lambda *a, **k: None,
    )
    called = {}

    def fake_run_bench(**kw):
        called.update(kw)
        return {"metric": "m", "value": 1.0, "unit": "samples/sec/chip",
                "extra": {"device": bench.chip_device()}}

    monkeypatch.setattr(bench, "run_bench", fake_run_bench)
    result = bench.main([])
    assert called["model_name"] == "bert-large-cased"
    assert result["extra"]["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }
    assert _last_json(capsys.readouterr().out)["value"] == 1.0


def test_probe_flag_is_gone():
    with pytest.raises(SystemExit):
        bench.main(["--probe-budget-s", "5"])
