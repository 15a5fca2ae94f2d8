"""Stage-axis measurement (VERDICT r1 #3): does GSPMD layer-sharding over
the `stage` axis pipeline, or serialize?

Runs the scan-stacked trunk on the 8-device CPU mesh in two shapes with
the SAME chip count: pure DP (data=8) vs DP x stage (data=4, stage=2).
Equal per-sample math => equal step time IF stages overlapped; stage time
~2x DP time means devices holding other stages idle (no schedule).

CPU-mesh wall clock is noisy but the serialization signal is ~2x.
"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.comms.ingest import make_global_batch
from pytorch_distributed_training_tpu.comms.mesh import (
    TRAIN_BATCH_PSPEC,
    build_mesh,
)
from pytorch_distributed_training_tpu.models import BertForSequenceClassification
from pytorch_distributed_training_tpu.parallel import (
    ShardingPolicy,
    state_shardings,
)
from pytorch_distributed_training_tpu.parallel.sharding import shard_state
from pytorch_distributed_training_tpu.train.optim import adamw_with_schedule
from pytorch_distributed_training_tpu.train.state import create_train_state
from pytorch_distributed_training_tpu.train.step import make_train_step
from pytorch_distributed_training_tpu.utils.config import (
    MeshConfig,
    TrainConfig,
    model_preset,
)

GLOBAL, MICRO, SEQ, ITERS = 64, 16, 128, 2


def run(name, mesh_cfg, policy):
    mesh = build_mesh(mesh_cfg)
    mcfg = model_preset(
        "tiny", compute_dtype="float32", scan_layers=True,
        hidden_dropout=0.0, attention_dropout=0.0,
        hidden_size=256, num_layers=8, num_heads=4, intermediate_size=1024,
        vocab_size=8192,
    )
    model = BertForSequenceClassification(mcfg)
    tcfg = TrainConfig(global_batch_size=GLOBAL, micro_batch_size=MICRO)
    tx, _ = adamw_with_schedule(tcfg, 100)
    example = {
        "input_ids": jnp.ones((2, SEQ), jnp.int32),
        "attention_mask": jnp.ones((2, SEQ), jnp.int32),
        "token_type_ids": jnp.zeros((2, SEQ), jnp.int32),
    }
    state = create_train_state(model, tx, jax.random.key(0), example)
    shardings = state_shardings(state, policy, mesh)
    state = shard_state(state, shardings)
    step = make_train_step(
        grad_accum_steps=tcfg.grad_accum_steps, mesh=mesh,
        state_shardings=shardings,
    )
    rng = np.random.default_rng(0)
    accum = tcfg.grad_accum_steps
    b = {
        "input_ids": rng.integers(0, 8192, (accum, MICRO, SEQ)).astype(np.int32),
        "attention_mask": np.ones((accum, MICRO, SEQ), np.int32),
        "token_type_ids": np.zeros((accum, MICRO, SEQ), np.int32),
        "labels": rng.integers(0, 2, (accum, MICRO)).astype(np.int32),
    }
    batch = make_global_batch(mesh, b, pspec=TRAIN_BATCH_PSPEC)
    state, m = step(state, batch)
    jax.block_until_ready(state.params)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            state, m = step(state, batch)
        float(jax.device_get(m["loss"]))
        best = min(best, (time.perf_counter() - t0) / ITERS)
    print(f"{name:32s} {best*1e3:9.1f} ms/step", flush=True)
    return best





def run_gpipe(name, mesh_cfg, n_micro=8):
    """Trunk-only fwd+bwd: GPipe schedule vs the same-chip DP trunk."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_training_tpu.ops.attention import (
        make_attention_bias,
    )
    from pytorch_distributed_training_tpu.parallel.pipeline import (
        gpipe_apply,
        gpipe_trunk_fn,
    )

    mesh = build_mesh(mesh_cfg)
    mcfg = model_preset(
        "tiny", compute_dtype="float32", scan_layers=True,
        hidden_dropout=0.0, attention_dropout=0.0,
        hidden_size=256, num_layers=8, num_heads=4, intermediate_size=1024,
        vocab_size=8192,
    )
    model = BertForSequenceClassification(mcfg)
    ids = jnp.ones((4, SEQ), jnp.int32)
    params = model.init(jax.random.key(0), ids)["params"]
    stacked = params["bert"]["layers_scan"]["layer"]
    rng = np.random.default_rng(0)
    mb = GLOBAL // n_micro
    xs = jnp.asarray(
        rng.normal(size=(n_micro, mb, SEQ, mcfg.hidden_size)), jnp.float32
    )
    biases = jnp.zeros((n_micro, mb, 1, 1, SEQ), jnp.float32)
    layer_fn = gpipe_trunk_fn(mcfg)
    n_stages = mesh.shape["stage"]
    stream = P(None, ("data", "fsdp"))

    if n_stages > 1:
        def loss(p, x):
            return jnp.sum(
                gpipe_apply(mesh, layer_fn, p, x, biases,
                            stream_spec=stream)
            )
    else:
        # DP baseline: the same total work as one flat batch, rows
        # sharded over all 8 devices (no microbatch split needed)
        xs = xs.reshape(GLOBAL, SEQ, mcfg.hidden_size)
        biases = jnp.zeros((GLOBAL, 1, 1, SEQ), jnp.float32)
        stream = P(("data", "fsdp"))

        def loss(p, x):
            def body(h, lp):
                return layer_fn(lp, h, biases), None

            out, _ = jax.lax.scan(body, x, p)
            return jnp.sum(out)

    stacked_sh = jax.device_put(
        stacked,
        jax.tree.map(
            lambda _: NamedSharding(
                mesh, P("stage") if n_stages > 1 else P()
            ),
            stacked,
        ),
    )
    xs_sh = jax.device_put(xs, NamedSharding(mesh, stream))
    g = jax.jit(jax.grad(loss, argnums=(0, 1)))
    o = g(stacked_sh, xs_sh)
    jax.block_until_ready(o)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            o = g(stacked_sh, xs_sh)
        jax.block_until_ready(o)
        best = min(best, (time.perf_counter() - t0) / ITERS)
    print(f"{name:32s} {best*1e3:9.1f} ms/step", flush=True)
    return best


if __name__ == "__main__":
    import sys as _sys

    if "--gpipe" in _sys.argv:
        t_dp = run_gpipe("trunk dp8 (data=8)", MeshConfig(data=8))
        t_g2 = run_gpipe("gpipe stage2 (data=4, stage=2)",
                         MeshConfig(data=4, stage=2))
        t_g4 = run_gpipe("gpipe stage4 (data=2, stage=4)",
                         MeshConfig(data=2, stage=4))
        print(f"gpipe2/dp8 = {t_g2 / t_dp:.2f}x   "
              f"gpipe4/dp8 = {t_g4 / t_dp:.2f}x")
    else:
        t_dp = run("dp8 (data=8)", MeshConfig(data=8), ShardingPolicy())
        t_s2 = run("stage2 (data=4, stage=2)", MeshConfig(data=4, stage=2),
                   ShardingPolicy(stage=True))
        t_s4 = run("stage4 (data=2, stage=4)", MeshConfig(data=2, stage=4),
                   ShardingPolicy(stage=True))
        print(f"stage2/dp8 = {t_s2 / t_dp:.2f}x   stage4/dp8 = {t_s4 / t_dp:.2f}x")
