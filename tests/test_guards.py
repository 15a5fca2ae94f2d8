"""Runtime guard tests (analysis/guards.py): recompile detection around
jitted entry points, implicit-transfer arming, donation/sharding audits,
and the acceptance contracts — zero unexpected retraces/transfers across a
warm 3-step CPU train run and a warm two-bucket serve session, plus
negative tests proving a deliberate violation is detected, recorded in
telemetry and (strict) fails. CPU-only, tier-1."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.analysis.guards import (
    GuardSet,
    GuardViolation,
    RecompileError,
    TransferGuardError,
    donation_audit,
    guard_mode_from_env,
    sharding_audit,
)
from pytorch_distributed_training_tpu.comms.mesh import build_mesh
from pytorch_distributed_training_tpu.telemetry.registry import (
    MetricsRegistry,
)
from pytorch_distributed_training_tpu.utils.config import MeshConfig


class ListSink:
    """In-memory telemetry sink (same contract as JsonlSink.emit)."""

    def __init__(self):
        self.records = []

    def emit(self, record):
        rec = dict(record)
        rec.setdefault("ts", time.time())
        self.records.append(rec)

    def flush(self, **kw):
        pass

    def of(self, kind):
        return [r for r in self.records if r.get("record") == kind]


def _guards(mode):
    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    return GuardSet(mode=mode, registry=reg), sink


# ------------------------------------------------------------ recompile guard


def test_recompile_strict_raises_and_records():
    gs, sink = _guards("strict")
    f = gs.wrap_jit("f", jax.jit(lambda x: x * 2))
    f(jnp.ones((2,)))              # warm-up compile: expected
    f(jnp.ones((2,)))              # warm, same shape: fine
    assert gs.violations == 0 and not sink.of("recompile")

    with pytest.raises(RecompileError, match="recompiled after warm-up"):
        f(jnp.ones((3,)))          # new shape -> new program -> violation
    (rec,) = sink.of("recompile")
    assert rec["name"] == "f" and rec["calls"] == 3
    assert gs.recompile_violations == 1
    assert gs.registry.snapshot()["counters"]["guards/recompiles"] == 1


def test_recompile_record_mode_does_not_raise():
    gs, sink = _guards("record")
    f = gs.wrap_jit("f", jax.jit(lambda x: x + 1))
    f(jnp.ones((2,)))
    out = f(jnp.ones((5,)))        # retrace: recorded, not fatal
    np.testing.assert_array_equal(np.asarray(out), np.full((5,), 2.0))
    assert gs.recompile_violations == 1 and len(sink.of("recompile")) == 1


def test_guard_off_is_passthrough():
    gs, sink = _guards("off")
    f = gs.wrap_jit("f", jax.jit(lambda x: x + 1))
    f(jnp.ones((2,)))
    f(jnp.ones((7,)))              # retrace fine: guards off
    assert gs.violations == 0 and sink.records == []


def test_wrap_is_idempotent_and_forwards_attrs():
    gs, _ = _guards("record")
    jitted = jax.jit(lambda x: x + 1)
    f = gs.wrap_jit("f", jitted)
    assert gs.wrap_jit("f", f) is f
    # .lower passes through to the jit object (the AOT path needs it)
    lowered = f.lower(jnp.ones((2,)))
    assert lowered.compile() is not None


def test_aot_compiled_cannot_retrace():
    gs, sink = _guards("strict")
    compiled = jax.jit(lambda x: x * 3).lower(jnp.ones((4,))).compile()
    f = gs.wrap_jit("aot", compiled)
    assert f.warm  # no trace cache -> warm immediately
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))
    assert gs.violations == 0 and sink.records == []


# ------------------------------------------------------------- transfer guard


def test_transfer_strict_catches_host_array_into_warm_jit():
    gs, sink = _guards("strict")
    f = gs.wrap_jit("f", jax.jit(lambda x: x + 1))
    f(jnp.ones((4,)))              # warm on a placed device array
    with pytest.raises(TransferGuardError, match="implicit transfer"):
        f(np.ones((4,), np.float32))   # un-placed host array -> H2D per call
    (rec,) = sink.of("implicit_transfer")
    assert rec["name"] == "f" and "transfer" in rec["error"]
    assert gs.transfer_violations == 1


def test_transfer_scope_arms_arbitrary_regions():
    gs, sink = _guards("strict")
    g = jax.jit(lambda x: x * 2)
    # arrays created OUTSIDE the scope: creating one inside would itself
    # upload its fill constant and trip the guard
    dev = jnp.ones((3,))
    host = np.ones((3,), np.float32)
    g(dev)                         # compile outside the scope
    with gs.transfer_scope("tick"):
        g(dev)                     # device args: clean
    with pytest.raises(TransferGuardError):
        with gs.transfer_scope("tick"):
            g(host)
    (rec,) = sink.of("implicit_transfer")
    assert rec["name"] == "tick"


def test_transfer_record_mode_never_raises():
    gs, sink = _guards("record")
    f = gs.wrap_jit("f", jax.jit(lambda x: x + 1))
    f(jnp.ones((4,)))
    f(np.ones((4,), np.float32))   # logged by jax, not fatal, not recorded
    assert gs.transfer_violations == 0


# ------------------------------------------------------------- donation audit


def test_donation_audit_ok_and_violation():
    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    a, b = jnp.ones((8,)), jnp.ones((8,))

    donated = jax.jit(lambda s, x: s + x, donate_argnums=(0,)).lower(a, b)
    rec = donation_audit("good", donated, registry=reg, mode="strict")
    assert rec["ok"] and rec["aliased"] >= 1
    # compiled HLO carries the alias map too
    rec2 = donation_audit(
        "good_compiled", donated.compile(), registry=reg, mode="strict"
    )
    assert rec2["ok"]

    dropped = jax.jit(lambda s, x: s + x).lower(a, b)  # no donation requested
    rec3 = donation_audit("bad", dropped, registry=reg, mode="record")
    assert not rec3["ok"] and rec3["aliased"] == 0
    with pytest.raises(GuardViolation, match="donation audit"):
        donation_audit("bad", dropped, registry=reg, mode="strict")
    assert len(sink.of("donation_audit")) == 4


# ------------------------------------------------------------- sharding audit


def test_sharding_audit_flags_replicated_on_sharded_mesh(eight_devices):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(MeshConfig(data=4, fsdp=2))
    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    big = jax.device_put(
        jnp.zeros((64, 64), jnp.float32), NamedSharding(mesh, P())
    )
    small = jax.device_put(
        jnp.zeros((4,), jnp.float32), NamedSharding(mesh, P())
    )
    sharded = jax.device_put(
        jnp.zeros((64, 64), jnp.float32), NamedSharding(mesh, P("fsdp"))
    )
    params = {"big": big, "small": small, "sharded": sharded}

    rec = sharding_audit(
        params, mesh, min_bytes=1024, registry=reg, mode="record"
    )
    assert not rec["ok"]
    assert [f["path"] for f in rec["flagged"]] == ["['big']"]
    with pytest.raises(GuardViolation, match="sharding audit"):
        sharding_audit(
            params, mesh, min_bytes=1024, registry=reg, mode="strict"
        )

    # dp-only mesh: replication is the design, audit is clean
    dp_mesh = build_mesh(MeshConfig(data=-1))
    rec_dp = sharding_audit(
        {"big": jax.device_put(
            jnp.zeros((64, 64)), NamedSharding(dp_mesh, P())
        )},
        dp_mesh, min_bytes=1024, registry=reg, mode="strict",
    )
    assert rec_dp["ok"]


# ----------------------------------------------------------------- env config


def test_guard_mode_from_env(monkeypatch):
    monkeypatch.delenv("PDT_TPU_GUARDS", raising=False)
    assert guard_mode_from_env() == "record"
    monkeypatch.setenv("PDT_TPU_GUARDS", "strict")
    assert guard_mode_from_env() == "strict"
    monkeypatch.setenv("PDT_TPU_GUARDS", "nope")
    with pytest.raises(ValueError, match="PDT_TPU_GUARDS"):
        guard_mode_from_env()
    with pytest.raises(ValueError, match="guards mode"):
        GuardSet(mode="nope")


# ----------------------------------------------- trainer acceptance (3 steps)


def _tiny_trainer(**tcfg_kw):
    from pytorch_distributed_training_tpu.parallel import ShardingPolicy
    from pytorch_distributed_training_tpu.train.loop import Trainer
    from pytorch_distributed_training_tpu.utils.config import (
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
        train_size=96,   # 3 updates per epoch
        eval_size=32,
        guards="strict",
    )
    defaults.update(tcfg_kw)
    return Trainer(
        mcfg, TrainConfig(**defaults), MeshConfig(data=4, fsdp=2),
        ShardingPolicy(fsdp=True, fsdp_min_size=128),
        task="synthetic",
    )


@pytest.mark.parametrize("aot", [True, False], ids=["aot", "lazy-jit"])
def test_train_3_steps_zero_retraces_strict(eight_devices, tmp_path, aot):
    """Acceptance: a 3-step CPU train run under strict guards finishes with
    ZERO retraces after warm-up and zero implicit transfers — for both the
    AOT warm-start path (Compiled steps) and the lazy jit path (first call
    is the warm-up compile)."""
    mdir = str(tmp_path / ("aot" if aot else "jit"))
    t = _tiny_trainer(metrics_dir=mdir, aot_warmup=aot)
    history = t.run()
    assert len(history) == 1

    with open(os.path.join(mdir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f if line.strip()]
    kinds = [r["record"] for r in records]
    assert kinds[0] == "run_meta"
    assert "recompile" not in kinds
    assert "implicit_transfer" not in kinds
    assert t.guards.violations == 0
    assert len([r for r in records if r["record"] == "step"]) == 3

    # the audits ran and passed
    (shard_rec,) = [r for r in records if r["record"] == "sharding_audit"]
    assert shard_rec["ok"]
    if aot:
        (don_rec,) = [r for r in records if r["record"] == "donation_audit"]
        assert don_rec["ok"] and don_rec["name"] == "train_step"
    # the guarded steps really were exercised
    assert t.guards.wrapped["train_step"].calls == 3
    assert t.guards.wrapped["eval_step"].calls >= 1


def test_trainer_guards_off_unwrapped(eight_devices):
    from pytorch_distributed_training_tpu.analysis.guards import GuardedCall

    t = _tiny_trainer(guards="off")
    t.run()
    assert not isinstance(t.train_step, GuardedCall)


# ------------------------------------------- serve acceptance (two buckets)


def test_serve_two_bucket_session_zero_retraces_strict():
    """Acceptance: a multi-request serve session spanning two prompt
    buckets — each bucket serving several requests through slot reuse —
    retraces nothing after each program's single warm-up compile, under
    strict guards (a retrace or implicit transfer would fail the loop)."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.serve import (
        EngineConfig,
        InferenceServer,
    )
    from pytorch_distributed_training_tpu.serve.server import wait_until
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((2, 8), jnp.int32))[
        "params"
    ]
    gs, sink = _guards("strict")
    server = InferenceServer(
        model, params,
        EngineConfig(num_slots=2, prompt_buckets=(4, 8), max_new_tokens=4),
        queue_depth=16, registry=gs.registry, guards=gs,
    ).start()
    try:
        rng = np.random.default_rng(3)
        lengths = [3, 6, 2, 7, 4, 5]  # alternating buckets, reused slots
        reqs = [
            server.submit(
                rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=4,
            )
            for n in lengths
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        )
    finally:
        server.close()

    assert all(r.status == "done" for r in reqs)
    stats = server.stats()
    assert stats["compiled_prefill_buckets"] == [4, 8]
    assert stats["guard_mode"] == "strict"
    assert stats["guard_recompiles"] == 0
    assert stats["guard_implicit_transfers"] == 0
    assert not sink.of("recompile") and not sink.of("implicit_transfer")
    # both buckets + decode really went through guarded entry points
    for name in ("serve_prefill_b4", "serve_prefill_b8", "serve_decode"):
        assert gs.wrapped[name].calls >= 2, name


def test_serve_retrace_violation_fails_loop_and_records():
    """Negative: force a retrace of a guarded serve program mid-session
    (shrink the resident cache behind the compiled decode step's back) and
    assert the violation is recorded AND the strict loop fails closed —
    every waiter's done event still fires."""
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.serve import (
        EngineConfig,
        InferenceServer,
    )
    from pytorch_distributed_training_tpu.serve.server import wait_until
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((2, 8), jnp.int32))[
        "params"
    ]
    gs, sink = _guards("strict")
    server = InferenceServer(
        model, params,
        EngineConfig(num_slots=1, prompt_buckets=(4,), max_new_tokens=4),
        queue_depth=16, registry=gs.registry, guards=gs,
    ).start()
    prompt = np.arange(1, 4, dtype=np.int32)
    try:
        first = server.submit(prompt, max_new_tokens=4)
        assert wait_until(lambda: first.done.is_set(), timeout=120)
        assert first.status == "done"

        # sabotage: shrink the resident KV state so the warmed programs see
        # a NEW shape -> guarded retrace. Paged layout (default): drop a
        # page from the [num_pages, page_size, heads * head_dim] pools;
        # dense layout: drop the trailing sequence position (axis 2 of the
        # [slots, 1, cache_len, heads, head_dim] leaves).
        engine = server.engine
        engine._cache = jax.tree.map(
            lambda g: (
                g[:-1] if g.ndim == 3 else g[:, :, :-1] if g.ndim == 5 else g
            ),
            engine._cache,
        )
        second = server.submit(prompt, max_new_tokens=4)
        assert wait_until(lambda: second.done.is_set(), timeout=120)
        assert second.status in ("cancelled", "expired", "error")
        assert gs.recompile_violations >= 1
        assert sink.of("recompile")
    finally:
        server.close(drain=False)
