"""The train step's named scopes (`train/step.py::TRAIN_SCOPES`) in the
compiled program: the trainer's AOT warm start audits the step and keeps a
`program_scopes` record of it in the process
(`analysis/spmd/manifest.py::PROGRAM_SCOPES`), which is what the
benchmark's `train.*_ms` readers join with a device trace. The scopes are
metadata only: the compiled step is the same program without them."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
    PROGRAM_SCOPES,
)
from pytorch_distributed_training_tpu.train import step as step_mod
from pytorch_distributed_training_tpu.train.step import TRAIN_SCOPES

# 3 accumulated micro-batches unroll the scan, 12 keep the rolled loop
ACCUMS = [3, 12]
MICRO = 8

# `%name = <shape> op(...), ..., metadata={op_name="..."`
_OP_NAME = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.-]+)\s*=.*"
    r"metadata=\{[^}]*op_name=\"(?P<op>[^\"]*)\"")


def _op_names(text: str) -> dict:
    return {m.group("name"): m.group("op")
            for m in map(_OP_NAME.match, text.splitlines()) if m}


@pytest.fixture(scope="module", params=ACCUMS, ids=lambda a: f"accum{a}")
def warmed(request, eight_devices):
    """(accumulation count, the warm start's new records, the compiled
    step's text) of the tiny recipe on the 8-device data mesh."""
    from pytorch_distributed_training_tpu.parallel import ShardingPolicy
    from pytorch_distributed_training_tpu.train.loop import Trainer
    from pytorch_distributed_training_tpu.utils.config import (
        MeshConfig,
        TrainConfig,
        model_preset,
    )

    accum = request.param
    trainer = Trainer(
        model_preset("tiny", compute_dtype="float32"),
        TrainConfig(
            num_epochs=1, global_batch_size=MICRO * accum,
            micro_batch_size=MICRO, eval_batch_size=8, log_every=0,
            bf16=False, train_size=MICRO * accum, eval_size=8,
            guards="record"),
        MeshConfig(data=8), ShardingPolicy(), task="synthetic",
    )
    before = list(PROGRAM_SCOPES)
    trainer._warm_start()
    new = [r for r in PROGRAM_SCOPES if not any(r is b for b in before)]
    return accum, new, trainer.train_step.as_text()


def test_warm_start_keeps_one_train_step_record(warmed):
    _, new, _ = warmed
    assert [r["name"] for r in new] == ["train_step"]
    assert new[0]["record"] == "program_scopes"
    assert set(new[0]["scopes"]) == set(TRAIN_SCOPES)


def test_each_scope_reaches_the_compiled_step(warmed):
    accum, (rec,), text = warmed
    scopes = rec["scopes"]
    assert scopes["train.grad"] and scopes["train.optimizer"]
    # every scope names instructions of the compiled program: the fused
    # ones too (a fusion's body carries its parts' scopes)
    for scope in TRAIN_SCOPES:
        assert f'{scope}/' in text, scope
    ops = _op_names(text)
    if accum > 4:
        # rolled: the carry's adds run as instructions of the loop's body
        assert scopes["train.accumulate"]
        for name in scopes["train.accumulate"]:
            assert "while/body" in ops[name].split("train.accumulate")[0], (
                name, ops[name])
    for name in scopes["train.grad"]:
        # the scope wraps value_and_grad from OUTSIDE: a plain segment
        # above the forward (jvp) and backward (transpose) of the model
        assert "train.grad" in ops[name].split("/"), ops[name]


def test_no_instruction_sits_under_two_scopes(warmed):
    _, (rec,), text = warmed
    listed = [n for names in rec["scopes"].values() for n in names]
    assert len(listed) == len(set(listed))
    for name, op in _op_names(text).items():
        segments = op.split("/")
        assert sum(s in segments for s in TRAIN_SCOPES) <= 1, (name, op)


def _program(accum: int) -> tuple:
    """The compiled step's text, and everything of it but its metadata:
    without the source-location tables ahead of the first computation and
    each instruction's `metadata={...}`."""
    from pytorch_distributed_training_tpu.models import (
        BertForSequenceClassification,
    )
    from pytorch_distributed_training_tpu.train.optim import adamw_with_schedule
    from pytorch_distributed_training_tpu.train.state import create_train_state
    from pytorch_distributed_training_tpu.utils.config import (
        TrainConfig,
        model_preset,
    )

    tcfg = TrainConfig()
    model = BertForSequenceClassification(
        model_preset("tiny", compute_dtype="float32"))
    tx, _ = adamw_with_schedule(tcfg, 100)
    seq = 16
    example = {k: jnp.ones((2, seq), jnp.int32)
               for k in ("input_ids", "attention_mask", "token_type_ids")}
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, jax.random.key(0, impl=tcfg.prng_impl), example))
    batch = {k: jax.ShapeDtypeStruct((accum, 2, seq), jnp.int32)
             for k in ("input_ids", "attention_mask", "token_type_ids")}
    batch["labels"] = jax.ShapeDtypeStruct((accum, 2), jnp.int32)
    text = step_mod.make_train_step(grad_accum_steps=accum).lower(
        state, batch).compile().as_text()
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if re.match(r"(ENTRY )?%", line))
    return text, re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines[first:]))


@pytest.mark.parametrize("accum", ACCUMS)
def test_scopes_change_only_metadata(accum, monkeypatch):
    with_scopes, program = _program(accum)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name in TRAIN_SCOPES
                      else real(name)))
    without, program_without = _program(accum)
    assert "train.grad" in with_scopes and "train.grad" not in without
    assert program == program_without
