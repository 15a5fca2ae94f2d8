"""Telemetry subsystem tests: registry semantics, JSONL sink round-trip,
header schema, straggler stats, and the end-to-end Trainer integration —
a synthetic-task run with ``metrics_dir`` set must write a parseable JSONL
stream whose final epoch record matches ``trainer.history[-1]``, rendered
by scripts/summarize_metrics.py.
"""

import importlib.util
import json
import logging
import math
import os

import numpy as np
import pytest

from pytorch_distributed_training_tpu.telemetry import (
    JsonlSink,
    MetricsRegistry,
    epoch_straggler_stats,
    get_registry,
    run_metadata,
    set_registry,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_trainer(**tcfg_kw):
    """Tiny synthetic-task Trainer on the 4x2 CPU mesh (the
    test_trainer_integration recipe)."""
    from pytorch_distributed_training_tpu.parallel import ShardingPolicy
    from pytorch_distributed_training_tpu.train.loop import Trainer
    from pytorch_distributed_training_tpu.utils.config import (
        MeshConfig,
        TrainConfig,
        model_preset,
    )

    mcfg = model_preset("tiny", compute_dtype="float32")
    defaults = dict(
        num_epochs=1,
        global_batch_size=32,
        micro_batch_size=16,
        eval_batch_size=32,
        learning_rate=3e-3,
        warmup_steps=10,
        log_every=0,
        bf16=False,
        train_size=128,
        eval_size=32,
    )
    defaults.update(tcfg_kw)
    return Trainer(
        mcfg, TrainConfig(**defaults), MeshConfig(data=4, fsdp=2),
        ShardingPolicy(fsdp=True, fsdp_min_size=128),
        task="synthetic",
    )


def _load_summarizer():
    spec = importlib.util.spec_from_file_location(
        "summarize_metrics",
        os.path.join(REPO_ROOT, "scripts", "summarize_metrics.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- registry


def test_registry_counter_gauge_timer_semantics():
    reg = MetricsRegistry()
    reg.inc("c")
    reg.inc("c", 2)
    reg.gauge("g", 1.0)
    reg.gauge("g", 7.5)  # gauges hold the LAST value
    reg.observe("t", 0.1)
    reg.observe("t", 0.3)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 7.5
    t = snap["timers"]["t"]
    assert t["count"] == 2
    assert t["total_s"] == pytest.approx(0.4)
    assert t["mean_s"] == pytest.approx(0.2)
    assert t["min_s"] == pytest.approx(0.1)
    assert t["max_s"] == pytest.approx(0.3)
    assert t["min_s"] <= t["p50_s"] <= t["p95_s"] <= t["max_s"]


def test_timer_holds_a_fixed_number_of_values_however_many_it_saw():
    """Serving observes ``serve/tick`` for the life of the process and
    never resets: count, total, min and max stay exact, the percentiles
    come from a bounded uniform sample."""
    reg = MetricsRegistry()
    n = 10_000
    for i in range(n):
        reg.observe("serve/tick", (i + 1) / n)   # 0.0001 .. 1.0, in order
    stat = reg._timers["serve/tick"]
    assert len(stat.values) == stat.SAMPLE < n
    t = reg.snapshot()["timers"]["serve/tick"]
    assert t["count"] == n
    assert t["total_s"] == pytest.approx((n + 1) / 2)
    assert t["mean_s"] == pytest.approx((n + 1) / 2 / n)
    assert t["min_s"] == pytest.approx(1 / n) and t["max_s"] == 1.0
    # a uniform sample of 1024: the median within a few hundredths
    assert t["p50_s"] == pytest.approx(0.5, abs=0.06)
    assert t["p95_s"] == pytest.approx(0.95, abs=0.03)
    # up to the sample's size every observation is kept: exact
    small = MetricsRegistry()
    for x in (0.3, 0.1, 0.2):
        small.observe("t", x)
    assert small.snapshot()["timers"]["t"]["p50_s"] == pytest.approx(0.2)


def test_registry_snapshot_reset_clears_window():
    reg = MetricsRegistry()
    reg.inc("c")
    reg.observe("t", 1.0)
    first = reg.snapshot(reset=True)
    assert first["counters"]["c"] == 1
    second = reg.snapshot()
    assert second["counters"] == {}
    assert second["timers"] == {}


def test_registry_timer_context_manager_measures_positive_time():
    reg = MetricsRegistry()
    with reg.timer("t"):
        sum(range(1000))
    s = reg.snapshot()["timers"]["t"]
    assert s["count"] == 1
    assert s["total_s"] > 0


def test_registry_emit_without_sink_is_noop():
    MetricsRegistry().emit({"record": "x"})  # must not raise


def test_default_registry_install_and_restore():
    mine = MetricsRegistry()
    prev = set_registry(mine)
    try:
        assert get_registry() is mine
    finally:
        set_registry(prev)


# -------------------------------------------------------------------- sink


def test_jsonl_sink_roundtrip(tmp_path):
    sink = JsonlSink(str(tmp_path), process_index=0)
    sink.emit({"record": "a", "x": 1})
    sink.emit({"record": "b", "y": [1.5, None, "s"]})
    sink.close()
    lines = [
        json.loads(l)
        for l in open(tmp_path / "metrics.jsonl").read().splitlines()
    ]
    assert [r["record"] for r in lines] == ["a", "b"]
    assert lines[0]["x"] == 1
    assert lines[1]["y"] == [1.5, None, "s"]
    for r in lines:
        assert r["ts"] > 0  # wall-clock stamp added at write time


def test_jsonl_sink_gates_on_process_zero(tmp_path):
    sink = JsonlSink(str(tmp_path / "sub"), process_index=1)
    assert not sink.active
    sink.emit({"record": "dropped"})
    sink.close()
    assert not os.path.exists(tmp_path / "sub")


def test_jsonl_sink_appends_across_instances(tmp_path):
    a = JsonlSink(str(tmp_path), process_index=0)
    a.emit({"record": "first"})
    a.close()
    b = JsonlSink(str(tmp_path), process_index=0)  # a supervised restart
    b.emit({"record": "second"})
    b.close()
    recs = [
        json.loads(l)
        for l in open(tmp_path / "metrics.jsonl").read().splitlines()
    ]
    assert [r["record"] for r in recs] == ["first", "second"]


def test_run_metadata_header_schema(eight_devices):
    from pytorch_distributed_training_tpu.comms.mesh import build_mesh
    from pytorch_distributed_training_tpu.utils.config import (
        MeshConfig,
        TrainConfig,
        model_preset,
    )

    mesh = build_mesh(MeshConfig(data=4, fsdp=2))
    hdr = run_metadata(
        mesh, model_preset("tiny"), TrainConfig(), steps_per_epoch=7
    )
    assert hdr["record"] == "run_meta"
    assert hdr["mesh_shape"] == {
        "data": 4, "fsdp": 2, "stage": 1, "model": 1, "seq": 1
    }
    assert hdr["chip_count"] == 8
    assert isinstance(hdr["jax_version"], str) and hdr["jax_version"]
    assert hdr["config"]["model"]["hidden_size"] == 64
    assert hdr["config"]["train"]["global_batch_size"] == 96
    assert hdr["steps_per_epoch"] == 7
    json.dumps(hdr)  # fully serializable, no repr leakage


# --------------------------------------------------------------- straggler


def test_straggler_stats_single_host():
    stats = epoch_straggler_stats([0.1, 0.2, 0.3], [0.01, 0.02, 0.03])
    assert stats["hosts"] == 1
    assert stats["slowest_host"] == 0
    assert stats["fastest_host"] == 0
    assert stats["slowest_host_mean_step_s"] == pytest.approx(0.2)
    assert stats["wait_skew_s"] == 0.0
    assert stats["slowest_host_max_step_s"] == pytest.approx(0.3)
    assert stats["slowest_host_data_wait_mean_s"] == pytest.approx(0.02)
    assert stats["per_host_mean_step_s"] == pytest.approx([0.2])


def test_straggler_stats_empty_epoch():
    stats = epoch_straggler_stats([])
    assert stats["hosts"] == 1
    assert stats["slowest_host_mean_step_s"] == 0.0


# ----------------------------------------------------------------- logging


def test_log_level_env_and_process_index_format(monkeypatch, capsys):
    from pytorch_distributed_training_tpu.utils.logging import get_logger

    monkeypatch.setenv("PDT_TPU_LOG_LEVEL", "DEBUG")
    logger = get_logger("pdt_tpu_test_env_level")
    assert logger.level == logging.DEBUG
    logger.info("attributable line")
    out = capsys.readouterr().out
    assert "p0" in out  # process index in the format string
    assert "attributable line" in out


def test_log_format_json_switch(capsys):
    from pytorch_distributed_training_tpu.utils.logging import (
        get_logger,
        set_log_format,
    )

    logger = get_logger("pdt_tpu_test_json_fmt")
    try:
        set_log_format("json")
        logger.info("structured %s", "msg")
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["msg"] == "structured msg"
        assert rec["level"] == "INFO"
        assert rec["process"] == 0
    finally:
        set_log_format("text")


def test_log_format_rejects_unknown():
    from pytorch_distributed_training_tpu.utils.logging import set_log_format

    with pytest.raises(ValueError, match="log format"):
        set_log_format("yaml")


# ------------------------------------------------------------- integration


@pytest.fixture(scope="module")
def metrics_run(eight_devices, tmp_path_factory):
    """One tiny synthetic-task training run with the telemetry stream on;
    several tests assert against the same stream."""
    tmp = tmp_path_factory.mktemp("telemetry")
    mdir = str(tmp / "metrics")
    trainer = _small_trainer(
        metrics_dir=mdir,
        checkpoint_dir=str(tmp / "ckpt"),
    )
    trainer.run()
    records = [
        json.loads(l)
        for l in open(os.path.join(mdir, "metrics.jsonl"))
        .read()
        .splitlines()
    ]
    return trainer, records, mdir


def test_stream_header_first(metrics_run):
    trainer, records, _ = metrics_run
    hdr = records[0]
    assert hdr["record"] == "run_meta"
    assert hdr["chip_count"] == 8
    assert hdr["mesh_shape"]["data"] == 4
    assert hdr["config"]["train"]["train_size"] == 128
    assert hdr["steps_per_epoch"] == trainer.train_loader.steps_per_epoch


def test_stream_step_records_breakdown(metrics_run):
    trainer, records, _ = metrics_run
    steps = [r for r in records if r["record"] == "step"]
    assert len(steps) == trainer.train_loader.steps_per_epoch  # 4
    for s in steps:
        assert s["data_wait_s"] >= 0
        assert s["dispatch_s"] >= 0
        assert s["step_s"] >= 0
        # dispatch to dispatch covers the step's wait for its batch and its
        # dispatch (measured against the same perf_counter); the device's
        # own time is a profile's, not the record's
        assert s["step_s"] >= s["data_wait_s"] + s["dispatch_s"] - 1e-9
        assert "device_block_s" not in s
        assert math.isfinite(s["loss"])
        # default prefetch pipeline annotates queue occupancy per step
        assert 0 <= s["prefetch_occupancy"] <= 2
    # AOT warm start moved compilation OUT of the step stream: no step is
    # compile-inclusive, and the compile wall time has its own record
    assert all(s["compile_inclusive"] is False for s in steps)
    assert [s["step"] for s in steps] == list(
        range(1, len(steps) + 1)
    )


def test_stream_compile_record_from_aot_warm_start(metrics_run):
    _, records, _ = metrics_run
    compiles = [r for r in records if r["record"] == "compile"]
    assert len(compiles) == 1
    c = compiles[0]
    assert c["aot"] is True
    assert c["train_compile_s"] > 0
    assert c["eval_compile_s"] > 0
    assert c["compile_s"] == pytest.approx(
        c["train_compile_s"] + c["eval_compile_s"]
    )
    assert c["cache_hit"] is None  # CPU backend, no JAX_COMPILATION_CACHE_DIR


def test_lazy_compile_path_flags_first_step(eight_devices, tmp_path):
    """aot_warmup=False keeps the legacy behavior: the first step carries
    compilation and is flagged, later steps aren't."""
    mdir = str(tmp_path / "lazy")
    trainer = _small_trainer(
        metrics_dir=mdir, aot_warmup=False, train_size=64
    )
    trainer.run()
    records = [
        json.loads(l)
        for l in open(os.path.join(mdir, "metrics.jsonl")).read().splitlines()
    ]
    steps = [r for r in records if r["record"] == "step"]
    assert steps[0]["compile_inclusive"] is True
    assert all(s["compile_inclusive"] is False for s in steps[1:])
    assert not [r for r in records if r["record"] == "compile"]


def test_step_records_lag_a_step_and_never_drain_the_loop(
        eight_devices, tmp_path, monkeypatch):
    """With --metrics-dir each step's record goes out once the next step
    is dispatched (the last at the epoch's join): N records in step order,
    each with its own step's loss, and the loop blocks on the device only
    at the join, never once a step."""
    import sys

    import jax

    from pytorch_distributed_training_tpu.train import loop

    joins = []
    real_block = jax.block_until_ready

    def counted(x):
        if sys._getframe(1).f_code.co_filename == loop.__file__:
            joins.append(sys._getframe(1).f_code.co_name)
        return real_block(x)

    monkeypatch.setattr(jax, "block_until_ready", counted)
    own = []  # each dispatched step's loss, on the device
    real_warm = loop.Trainer._warm_start

    def warm_then_record(self):
        real_warm(self)
        step = self.train_step

        def recorded(state, batch):
            out = step(state, batch)
            own.append(out[1]["loss"])
            return out

        self.train_step = recorded

    monkeypatch.setattr(loop.Trainer, "_warm_start", warm_then_record)
    mdir = str(tmp_path / "lagged")
    trainer = _small_trainer(metrics_dir=mdir, guards="off")
    trainer.run()
    records = [
        json.loads(l)
        for l in open(os.path.join(mdir, "metrics.jsonl")).read().splitlines()
    ]
    steps = [r for r in records if r["record"] == "step"]
    n = trainer.train_loader.steps_per_epoch
    assert n > 1 and len(own) == n
    assert [s["step"] for s in steps] == list(range(1, n + 1))
    assert [s["loss"] for s in steps] == [
        float(x) for x in jax.device_get(own)]
    assert not any("device_block_s" in s for s in steps)
    assert joins == ["_run_epochs"]  # the epoch's join, once


def test_stream_epoch_record_matches_history(metrics_run):
    trainer, records, _ = metrics_run
    epochs = [r for r in records if r["record"] == "epoch"]
    assert len(epochs) == len(trainer.history) == 1
    final, hist = epochs[-1], trainer.history[-1]
    for key, want in hist.items():
        got = final[key]
        if isinstance(want, float) and math.isnan(want):
            assert math.isnan(got)
        else:
            assert got == pytest.approx(want), key
    # straggler stats ride every epoch record
    st = final["straggler"]
    assert st["hosts"] == 1
    assert st["slowest_host"] == 0
    assert st["slowest_host_mean_step_s"] > 0
    assert st["wait_skew_s"] == 0.0
    # the epoch's telemetry window: step breakdown + loader + eval timers
    timers = final["telemetry"]["timers"]
    assert timers["train/step_s"]["count"] == 4
    # both loader engines record placement; assembly is engine-specific
    # (host_assemble_s from the Python loader, prefetch_wait_s from the
    # native C++ batcher)
    assert timers["data/h2d_place_s"]["count"] >= 4
    assert (
        timers.get("data/host_assemble_s", {}).get("count", 0) >= 4
        or timers.get("data/prefetch_wait_s", {}).get("count", 0) >= 4
    )
    assert timers["eval/wall_s"]["count"] == 1
    assert timers["checkpoint/save_submit_s"]["count"] == 1


def test_stream_checkpoint_save_durations(metrics_run):
    _, records, _ = metrics_run
    saves = [r for r in records if r["record"] == "checkpoint_save"]
    assert len(saves) == 1  # the per-epoch save
    assert saves[0]["submit_s"] >= 0
    assert saves[0]["step"] == 4


def test_summarize_metrics_renders_stream(metrics_run, capsys):
    trainer, _, mdir = metrics_run
    sm = _load_summarizer()
    summary = sm.main([mdir])
    out = capsys.readouterr().out
    assert "samp/s/chip" in out and "data-wait %" in out
    row = summary["epochs"][0]
    assert row["steps"] == 4
    assert row["train_loss"] == pytest.approx(
        trainer.history[-1]["train_loss"]
    )
    assert row["slowest_host"] == 0
    assert 0.0 <= row["data_wait_pct"] <= 100.0
    assert summary["checkpoint_saves"] == 1
    assert summary["run"]["chip_count"] == 8
    # --json mode emits machine-readable output
    sm.main([mdir, "--json"])
    assert json.loads(capsys.readouterr().out)["epochs"][0]["steps"] == 4


def test_summarize_skips_torn_lines(tmp_path, capsys):
    sm = _load_summarizer()
    p = tmp_path / "metrics.jsonl"
    p.write_text(
        json.dumps({"record": "run_meta", "chip_count": 1}) + "\n"
        + json.dumps({"record": "epoch", "epoch": 0, "train_loss": 1.0})
        + "\n"
        + '{"record": "step", "epo'  # torn final line (crashed run)
    )
    summary = sm.summarize(sm.load_records(str(p)))
    assert len(summary["epochs"]) == 1


def test_supervisor_restart_event(tmp_path):
    from pytorch_distributed_training_tpu.utils.supervisor import (
        run_with_restarts,
    )

    reg = MetricsRegistry()
    sink = JsonlSink(str(tmp_path), process_index=0)
    reg.attach_sink(sink)
    prev = set_registry(reg)
    try:
        calls = []

        def attempt(i):
            calls.append(i)
            if i == 0:
                raise RuntimeError("injected host failure")
            return "ok"

        assert (
            run_with_restarts(attempt, max_restarts=1, backoff_s=0.0) == "ok"
        )
    finally:
        set_registry(prev)
        sink.close()
    assert calls == [0, 1]
    recs = [
        json.loads(l)
        for l in open(tmp_path / "metrics.jsonl").read().splitlines()
    ]
    restart = [r for r in recs if r["record"] == "restart"]
    assert len(restart) == 1
    assert restart[0]["attempt"] == 0
    assert restart[0]["error"] == "RuntimeError"
    assert restart[0]["will_retry"] is True
    assert reg.snapshot()["counters"]["supervisor/restarts"] == 1


def test_trainer_without_metrics_dir_writes_nothing(eight_devices, tmp_path):
    """Telemetry off (the default): no sink, no per-step sync, and the
    run directory stays clean — the zero-overhead contract."""
    trainer = _small_trainer(train_size=64)
    trainer.run()
    assert trainer.metrics_sink is None
    assert trainer.history  # the run itself still happened
