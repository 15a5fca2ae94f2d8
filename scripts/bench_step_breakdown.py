"""Decompose bert-large MRPC step time: dropout, accum carry, metrics.

Times jitted train-step variants on synthetic data (chained, device_get at
the end, per NOTES.md timing rules). All variants consume the SAME
global batch (96) so samples/sec are comparable.
"""

import functools
import time

import jax
import jax.numpy as jnp
import optax

from pytorch_distributed_training_tpu.comms.mesh import build_mesh
from pytorch_distributed_training_tpu.models import BertForSequenceClassification
from pytorch_distributed_training_tpu.parallel import ShardingPolicy, state_shardings
from pytorch_distributed_training_tpu.parallel.sharding import shard_state
from pytorch_distributed_training_tpu.train.optim import adamw_with_schedule
from pytorch_distributed_training_tpu.train.state import create_train_state
from pytorch_distributed_training_tpu.train.step import _classification_loss
from pytorch_distributed_training_tpu.utils.config import TrainConfig, model_preset

GLOBAL = 96
SEQ = 128
ITERS = 20


def build(dropout: float):
    mcfg = model_preset(
        "bert-large-cased", hidden_dropout=dropout, attention_dropout=dropout
    )
    model = BertForSequenceClassification(mcfg)
    tcfg = TrainConfig(global_batch_size=GLOBAL, micro_batch_size=32)
    tx, _ = adamw_with_schedule(tcfg, total_steps=1000)
    example = {
        "input_ids": jnp.ones((2, SEQ), jnp.int32),
        "attention_mask": jnp.ones((2, SEQ), jnp.int32),
        "token_type_ids": jnp.zeros((2, SEQ), jnp.int32),
    }
    state = create_train_state(model, tx, jax.random.key(42, impl="rbg"), example)
    mesh = build_mesh()
    shardings = state_shardings(state, ShardingPolicy(), mesh)
    return shard_state(state, shardings), shardings, mesh


def make_step(shardings, mesh, *, accum, accum_dtype, grad_norm, deterministic):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from pytorch_distributed_training_tpu.comms.mesh import TRAIN_BATCH_PSPEC

    def train_step(state, batch):
        base_rng = jax.random.fold_in(state.dropout_rng, state.step)

        def loss_for(p, micro, rng):
            loss, _ = _classification_loss(
                state, p, micro, None if deterministic else rng
            )
            return loss

        if accum == 1:
            micro = jax.tree.map(lambda x: x[0], batch)
            loss, grads = jax.value_and_grad(loss_for)(
                state.params, micro, base_rng
            )
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            def micro_grads(carry, micro):
                grads_acc, (loss_acc, cnt) = carry
                rng = jax.random.fold_in(base_rng, cnt.astype(jnp.int32))
                loss, grads = jax.value_and_grad(loss_for)(
                    state.params, micro, rng
                )
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(accum_dtype), grads_acc, grads
                )
                return (grads_acc, (loss_acc + loss, cnt + 1.0)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), state.params
            )
            (grads, (loss_sum, _)), _ = jax.lax.scan(
                micro_grads,
                (zeros, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))),
                batch,
            )
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / accum, grads
            )
            loss = loss_sum / accum
        new_state = state.apply_gradients(grads)
        metrics = {"loss": loss}
        if grad_norm:
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    return jax.jit(
        train_step,
        donate_argnums=(0,),
        in_shardings=(shardings, NamedSharding(mesh, TRAIN_BATCH_PSPEC)),
        out_shardings=(shardings, NamedSharding(mesh, P())),
    )


def bench(name, state, step, batch):
    state, m = step(state, batch)  # compile
    jax.block_until_ready(state.params)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            state, m = step(state, batch)
        _ = float(jax.device_get(m["loss"]))
        best = min(best, (time.perf_counter() - t0) / ITERS)
    sps = GLOBAL / best
    print(f"{name:44s} {best*1e3:7.2f} ms/step  {sps:6.1f} samples/s", flush=True)
    return state


def batch_for(accum, mesh):
    from pytorch_distributed_training_tpu.comms.ingest import make_global_batch
    from pytorch_distributed_training_tpu.comms.mesh import TRAIN_BATCH_PSPEC
    import numpy as np

    rng = np.random.default_rng(0)
    micro = GLOBAL // accum
    b = {
        "input_ids": rng.integers(0, 28996, (accum, micro, SEQ)).astype(np.int32),
        "attention_mask": np.ones((accum, micro, SEQ), np.int32),
        "token_type_ids": np.zeros((accum, micro, SEQ), np.int32),
        "labels": rng.integers(0, 2, (accum, micro)).astype(np.int32),
    }
    return make_global_batch(mesh, b, pspec=TRAIN_BATCH_PSPEC)


if __name__ == "__main__":
    print(f"backend={jax.default_backend()} global={GLOBAL} seq={SEQ}")
    state, shardings, mesh = build(0.1)
    b3 = batch_for(3, mesh)
    b1 = batch_for(1, mesh)

    cases = [
        ("A 32x3 fp32-acc +gradnorm (prod)", dict(accum=3, accum_dtype=jnp.float32, grad_norm=True, deterministic=False), b3),
        ("B 32x3 fp32-acc no-gradnorm", dict(accum=3, accum_dtype=jnp.float32, grad_norm=False, deterministic=False), b3),
        ("C 32x3 bf16-acc no-gradnorm", dict(accum=3, accum_dtype=jnp.bfloat16, grad_norm=False, deterministic=False), b3),
        ("D 96x1 no-scan no-gradnorm", dict(accum=1, accum_dtype=jnp.float32, grad_norm=False, deterministic=False), b1),
        ("E 32x3 fp32-acc NO dropout", dict(accum=3, accum_dtype=jnp.float32, grad_norm=False, deterministic=True), b3),
        ("F 96x1 no-scan NO dropout", dict(accum=1, accum_dtype=jnp.float32, grad_norm=False, deterministic=True), b1),
    ]
    for name, kw, batch in cases:
        step = make_step(shardings, mesh, **kw)
        state = bench(name, state, step, batch)
