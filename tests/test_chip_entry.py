"""Entry-point contracts the chip run depends on (PR 21).

What must hold for the system to start on a TPU and for a failed start to
be visible: one externally placeable compile cache, no phase failure
behind exit code 0, one process per chip, a jax-free fleet coordinator.
All checked here on the CPU, without a chip.
"""

import dataclasses
import io
import json
import os
import subprocess
import sys
import tempfile

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_extra: dict, timeout: float = 300.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=timeout,
    )


# --------------------------------------------------------- compile cache


def test_compile_cache_env_wins_and_no_dir_is_set_in_code(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets NO directory in code
    and compiles land where the environment said."""
    env_dir = tmp_path / "from_env"
    code = f"""
import jax, jax.numpy as jnp
updates = []
real_update = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real_update(k, v))
from pytorch_distributed_training_tpu.train.compile import (
    cache_entry_count, enable_compile_cache)
got = enable_compile_cache()
assert got == {str(env_dir)!r}, got
assert "jax_compilation_cache_dir" not in updates, updates
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
assert cache_entry_count(got) > 0
print("OK")
"""
    proc = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(env_dir)})
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]
    assert any(env_dir.iterdir())


@pytest.fixture
def cache_config_restored():
    yield
    jax.config.update("jax_compilation_cache_dir", None)


def test_compile_cache_default_is_one_fixed_path_on_tpu_only(
    monkeypatch, cache_config_restored
):
    """Environment unset: the TPU backend caches at ONE fixed path inside
    the checkout (never a temp name); any other backend stays uncached —
    a choice from the platform, not a knob."""
    from pytorch_distributed_training_tpu.train import compile as c

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert c.enable_compile_cache() is None            # this CPU backend
    assert jax.config.jax_compilation_cache_dir is None

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = c.enable_compile_cache()
    assert got == os.path.join(_REPO, ".jax_cache") == c.REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == got
    assert not got.startswith(tempfile.gettempdir())
    assert c.enable_compile_cache() == got             # idempotent, stable


def test_compile_cache_place_is_not_a_flag():
    """Nothing in the program can move the cache: the helper takes no
    directory, TrainConfig has no such field, the CLIs no such option."""
    import inspect

    from pytorch_distributed_training_tpu.cli import train_dp
    from pytorch_distributed_training_tpu.train import compile as c
    from pytorch_distributed_training_tpu.utils.config import TrainConfig

    assert not inspect.signature(c.enable_compile_cache).parameters
    assert not any("cache" in f.name for f in dataclasses.fields(TrainConfig))
    with pytest.raises(SystemExit):
        train_dp.build_parser().parse_args(["--compile-cache-dir", "x"])
    assert c.cache_entry_count(None) is None
    assert c.cache_entry_count(c.REPO_CACHE_DIR + ".absent") == 0


# ------------------------------------------------ no failure behind exit 0


def test_chip_smoke_without_a_tpu_refuses_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr
    assert "platform=cpu" in proc.stdout
    assert '"ok"' not in proc.stdout       # no result line
    assert "----" not in proc.stdout       # no phase started


def test_chip_smoke_fails_on_an_error_event(monkeypatch):
    """The smoke's own phase checks end the run: an ``error`` event in the
    server's answer is not survivable, whatever the server returned."""
    import chip_smoke
    from pytorch_distributed_training_tpu.cli import serve_lm

    def answers_with_an_error(argv, in_stream, out_stream):
        for line in in_stream:
            rid = json.loads(line)["id"]
            out_stream.write(json.dumps(
                {"id": rid, "event": "error", "error": "page pool gone"}
            ) + "\n")
        return {"finished": 0}

    monkeypatch.setattr(serve_lm, "main", answers_with_an_error)
    with pytest.raises(SystemExit, match="FAILED: no error event"):
        chip_smoke.run_server()


def test_serve_lm_exits_nonzero_when_its_loop_dies(monkeypatch):
    """stdio clients get their ``error`` events AND the process fails."""
    from pytorch_distributed_training_tpu.cli import serve_lm
    from pytorch_distributed_training_tpu.serve.engine import DecodeEngine

    def dead_tick(self):
        raise RuntimeError("injected: the device fell over")

    monkeypatch.setattr(DecodeEngine, "tick", dead_tick)
    out = io.StringIO()
    with pytest.raises(SystemExit) as exc:
        serve_lm.main(
            ["--model", "gpt2-tiny", "--prompt-buckets", "16",
             "--max-new-tokens-cap", "8"],
            in_stream=[json.dumps({"prompt": "hi", "max_new_tokens": 4})],
            out_stream=out,
        )
    assert exc.value.code not in (0, None)
    events = [json.loads(line) for line in out.getvalue().splitlines()]
    assert [e["event"] for e in events] in (["error"], ["done"])
    assert events[0].get("status", "cancelled") != "done"


def test_aot_warm_start_failure_raises_on_the_tpu_backend(
    eight_devices, monkeypatch
):
    """Off-chip a failed AOT compile falls back to lazy jit; on the chip
    the compiler's refusal IS the error."""
    from pytorch_distributed_training_tpu.parallel import ShardingPolicy
    from pytorch_distributed_training_tpu.train import compile as c
    from pytorch_distributed_training_tpu.train import loop
    from pytorch_distributed_training_tpu.utils.config import (
        MeshConfig,
        TrainConfig,
        model_preset,
    )

    t = loop.Trainer(
        model_preset("tiny", compute_dtype="float32"),
        TrainConfig(num_epochs=1, global_batch_size=16, micro_batch_size=8,
                    eval_batch_size=16, train_size=32, eval_size=16,
                    bf16=False, log_every=0),
        MeshConfig(data=8), ShardingPolicy(), task="synthetic",
    )

    def refuse(**kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(c, "aot_warm_start", refuse)
    t._warm_start()                       # CPU: logged, lazy path next
    assert not t._first_step_done
    monkeypatch.setattr(loop.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        t._warm_start()


# ------------------------------------------------- one process per chip


def test_chip_env_gives_each_process_its_own_chip():
    from pytorch_distributed_training_tpu.utils.chips import chip_env

    one, two = chip_env(0), chip_env(1)
    assert one["TPU_VISIBLE_CHIPS"] == "0" and two["TPU_VISIBLE_CHIPS"] == "1"
    assert one["TPU_PROCESS_PORT"] != two["TPU_PROCESS_PORT"]
    assert one["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_fleet_refuses_more_replicas_than_chips(monkeypatch):
    """On a one-chip host ``--replicas 2`` is refused with a message before
    anything spawns; on a four-chip host replica i is handed chip i."""
    from pytorch_distributed_training_tpu.serve import fleet
    from pytorch_distributed_training_tpu.utils import chips

    monkeypatch.setattr(chips, "host_tpu_chips", lambda: 1)
    with pytest.raises(SystemExit, match="a chip belongs to one process"):
        fleet.ServeFleet(fleet.FleetConfig(num_replicas=2))

    monkeypatch.setattr(chips, "host_tpu_chips", lambda: 4)
    pool = fleet.ServeFleet(fleet.FleetConfig(num_replicas=2))
    try:
        envs = [r._env() for r in pool.replicas]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
        with pytest.raises(SystemExit, match="ONE process"):
            chips.require_chips("fleet_lm", 2, chips_each=2)   # --tp 2
    finally:
        pool.router.close()

    monkeypatch.setattr(chips, "host_tpu_chips", lambda: 0)   # not a TPU host
    pool = fleet.ServeFleet(fleet.FleetConfig(num_replicas=3))
    try:
        assert "TPU_VISIBLE_CHIPS" not in pool.replicas[2]._env()
    finally:
        pool.router.close()


def test_launch_refuses_local_processes_on_a_tpu_host(monkeypatch):
    from pytorch_distributed_training_tpu.cli import launch
    from pytorch_distributed_training_tpu.utils import chips

    monkeypatch.setattr(chips, "host_tpu_chips", lambda: 4)
    with pytest.raises(SystemExit, match="one process drives all"):
        launch.main(["--nprocs", "2", "--", sys.executable, "-c", "pass"])


def test_fleet_coordinator_never_imports_jax(tmp_path):
    """``fleet_lm`` spawns chip-using replicas, so it must never load jax
    itself (a log line used to: ``jax.process_index()`` starts the
    backend, and on a TPU host that claims the chips). Runs the real
    coordinator with one replica, SIGTERMs it, and checks its own module
    table."""
    code = f"""
import os, signal, sys, threading
threading.Timer(6.0, os.kill, (os.getpid(), signal.SIGTERM)).start()
from pytorch_distributed_training_tpu.cli import fleet_lm
fleet_lm.main(["--replicas", "1", "--router-port", "0", "--model",
               "gpt2-tiny", "--prompt-buckets", "16",
               "--max-new-tokens-cap", "8", "--metrics-dir", {str(tmp_path)!r},
               "--drain-timeout-s", "5"])
loaded = sorted(m for m in sys.modules if m.split(".")[0] in ("jax", "jaxlib"))
print("LOADED", loaded)
"""
    proc = _run(code, {}, timeout=180)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "LOADED []" in proc.stdout, proc.stdout[-2000:]
    records = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert any(r["record"] == "replica_spawn" for r in records)
