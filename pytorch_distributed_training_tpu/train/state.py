"""Training state: one donated pytree carrying everything a step mutates.

The reference's mutable training state is spread across the DDP module, the
torch optimizer, the LR scheduler, and the AMP scaler, glued by
``accelerator.prepare`` (reference test_data_parallelism.py:125-135). Here it
is a single immutable pytree — params + optimizer state + step + the base
dropout RNG key — threaded through a jitted step with donated buffers, so
XLA updates it in place in HBM.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct


@struct.dataclass
class TrainState:
    step: jnp.ndarray  # int32 scalar, counts optimizer updates
    params: Any
    opt_state: Any
    dropout_rng: jax.Array
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    # Non-parameter model state mutated by the forward pass: today the
    # "quant" collection of delayed int8 activation amaxes (ops/quant.py).
    # None for models without such state (None is an empty pytree, so every
    # existing step/sharding/checkpoint path is unchanged); otherwise the
    # step threads it through its accumulation scan and writes it back.
    quant: Any = None

    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt_state = self.tx.update(
            grads, self.opt_state, self.params
        )
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt_state,
        )


def create_train_state(
    model,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    example_batch: dict,
) -> TrainState:
    """Initialize params (jitted: one program instead of an eager dispatch
    per initializer op) and optimizer state."""
    init_rng, dropout_rng = jax.random.split(rng)

    def _init(r, batch):
        return model.init(
            r,
            batch["input_ids"],
            batch.get("attention_mask"),
            batch.get("token_type_ids"),
        )

    variables = jax.jit(_init)(init_rng, example_batch)
    params = variables["params"]
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        dropout_rng=dropout_rng,
        apply_fn=model.apply,
        tx=tx,
        # delayed-quant amaxes observed on the init dummy batch; real
        # calibration (train.step.calibrate_quant) overwrites before step 0
        quant=variables.get("quant"),
    )
