"""Jitted train/eval steps with structural gradient accumulation.

This file replaces the reference's entire hot loop (reference
test_data_parallelism.py:140-150; test_model_parallelism.py:283-299) with two
compiled functions:

- ``train_step(state, batch)`` — batch leaves are [accum, micro_batch, ...];
  a ``lax.scan`` over the accumulation axis computes fp32 gradients per
  microbatch and accumulates them in the carry, then ONE optimizer update
  fires at the end. This is the TPU-structural equivalent of the reference's
  ``model.no_sync()`` allreduce suppression (test_model_parallelism.py:
  292-294): the cross-replica psum happens once per global batch because the
  accumulated gradient is only materialized once — no flags, no off-by-one.
  (The reference steps on ``step % accum == 0``, which fires on the very
  first microbatch — SURVEY.md §2c-1. Here every update sees exactly
  ``accum`` microbatches by construction.)
- ``eval_step(state, batch)`` — forward + argmax, returning the confusion
  counts needed for accuracy/F1 under a validity mask. Static shapes force
  padding the last eval batch; masked counts keep the metric bit-honest
  (fixing the reference's uneven-last-batch gather skew, SURVEY.md §2c-6)
  and nothing bigger than a handful of scalars crosses device→host.

Loss is computed in fp32 off bf16 activations; gradients accumulate in fp32
by default (``accum_dtype`` — TrainConfig.grad_accum_dtype — can trade carry
bandwidth for bf16 rounding in the microbatch sum; the optimizer update is
fp32 either way). Jit donates ``state`` so params/optimizer state update in
place in HBM.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_training_tpu.comms.mesh import BATCH_AXES, TRAIN_BATCH_PSPEC
from pytorch_distributed_training_tpu.train.state import TrainState


def _sink_zeros(quant):
    """Zero-valued "quant_sink" collection matching ``quant``'s delayed-
    gradient sites (the leaves named ``dy_amax``) — None when the model
    has none. The sinks are the cotangent channel that carries the
    backward's observed dy amaxes out (ops/quant.py
    ``int8_dense_delayed_grads``); their STRUCTURE is static, so this
    also serves as the trace-time "is delayed_grads on?" predicate."""
    if quant is None:
        return None
    from flax import traverse_util

    flat = traverse_util.flatten_dict(quant)
    sinks = {
        k[:-1] + ("sink",): jnp.zeros_like(v)
        for k, v in flat.items()
        if k[-1] == "dy_amax"
    }
    return traverse_util.unflatten_dict(sinks) if sinks else None


def _merge_dy_amaxes(quant, sink_grads):
    """Write the backward's observed dy amaxes (the sink gradients) into
    the ``dy_amax`` leaves of the carried quant collection."""
    from flax import traverse_util

    q = traverse_util.flatten_dict(quant)
    s = traverse_util.flatten_dict(sink_grads)
    merged = {
        k: (s[k[:-1] + ("sink",)] if k[-1] == "dy_amax" else v)
        for k, v in q.items()
    }
    return traverse_util.unflatten_dict(merged)


def _apply(state: TrainState, params, micro, dropout_rng, quant=None,
           apply_fn=None, sinks=None):
    """Model forward → (output, new_quant). ``quant`` is the delayed-int8
    amax collection (ops/quant.py); when present the apply is mutable over
    it and the updated collection comes back for the caller to carry. None
    (every non-delayed model) leaves the apply exactly as before.
    ``apply_fn`` overrides ``state.apply_fn`` (the pipeline trainer
    evaluates through the serial trunk — same params, no schedule).
    ``sinks`` feeds the "quant_sink" collection for delayed-gradient
    models (built as zeros here when not supplied — callers pass their
    own only to differentiate w.r.t. it)."""
    fn = state.apply_fn if apply_fn is None else apply_fn
    rngs = {"dropout": dropout_rng} if dropout_rng is not None else None
    kwargs = dict(deterministic=dropout_rng is None, rngs=rngs)
    if quant is not None:
        variables = {"params": params, "quant": quant}
        if sinks is None:
            sinks = _sink_zeros(quant)
        if sinks is not None:
            variables["quant_sink"] = sinks
        out, updated = fn(
            variables,
            micro["input_ids"],
            micro.get("attention_mask"),
            micro.get("token_type_ids"),
            mutable=["quant"],
            **kwargs,
        )
        return out, updated["quant"]
    return (
        fn(
            {"params": params},
            micro["input_ids"],
            micro.get("attention_mask"),
            micro.get("token_type_ids"),
            **kwargs,
        ),
        None,
    )


def calibrate_quant(state: TrainState, micro, *,
                    objective: str = "classification",
                    loss_scale: float = 1.0) -> TrainState:
    """Populate delayed-int8 amaxes from ONE real microbatch (step-0 scales).

    Delayed scaling quantizes with the previous microbatch's amax; before
    the first step there is none (init observed a dummy batch of ones), so
    run one deterministic forward with the quant collection mutable and keep
    the observed amaxes. With delayed GRADIENT scaling
    (``quant_delayed_grads``) one backward also runs, reading the dy
    amaxes out of the sink gradients; ``loss_scale`` should match the
    training step's per-microbatch loss scaling (1/grad_accum_steps) so
    the calibrated dy magnitudes match what training's backward sees.
    No-op for models without delayed quant."""
    if state.quant is None:
        return state

    def _cal(st, m):
        q = _apply(st, st.params, m, None, st.quant)[1]
        sinks0 = _sink_zeros(q)
        if sinks0 is not None:
            forward_loss = _LOSS_FNS[objective]

            def f(sinks):
                loss, _ = forward_loss(st, st.params, m, None, q,
                                       sinks=sinks)
                return loss * loss_scale

            q = _merge_dy_amaxes(q, jax.grad(f)(sinks0))
        return q

    from pytorch_distributed_training_tpu.ops.quant import dy_calibration_mode

    with dy_calibration_mode():
        # trace-time switch: the calibration backward quantizes dy with
        # fresh DYNAMIC scales — with zero carried amaxes every
        # downstream site would otherwise differentiate through saturated
        # garbage cotangents and record garbage observations
        new_q = jax.jit(_cal)(state, micro)
    # keep every amax leaf on its ORIGINAL sharding: under the pipeline
    # policies the [num_layers] dim is stage-sharded, and the train step's
    # in_shardings reject the jit default (replicated) placement
    new_q = jax.tree.map(
        lambda new, old: (
            jax.device_put(new, old.sharding)
            if isinstance(getattr(old, "sharding", None), jax.sharding.Sharding)
            else new
        ),
        new_q,
        state.quant,
    )
    return state.replace(quant=new_q)


def _classification_loss(state: TrainState, params, micro, dropout_rng,
                         quant=None, sinks=None):
    """Mean masked softmax-CE over one microbatch, in fp32."""
    logits, new_quant = _apply(
        state, params, micro, dropout_rng, quant, sinks=sinks
    )
    labels = micro["labels"]
    valid = micro.get("valid")
    if valid is None:
        valid = jnp.ones_like(labels, jnp.float32)
    valid = valid.astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    )
    denom = jnp.maximum(valid.sum(), 1.0)
    loss = (ce * valid).sum() / denom
    return loss, (logits, new_quant)


def _lm_shift_and_mask(micro):
    """Next-token targets + per-position validity for causal LM batches.

    Position t predicts token t+1. Shift via ``roll`` (not slicing) so every
    tensor keeps the full [B, S] shape — slicing the sharded sequence dim
    makes the SPMD partitioner fully rematerialize the logits grad on the
    pad. The rolled-in last position is masked out, as are pad targets
    (attention_mask) and padded eval rows (valid).
    """
    ids = micro["input_ids"]
    targets = jnp.roll(ids, -1, axis=1)
    mask = micro.get("attention_mask")
    mask = (
        jnp.ones_like(ids, jnp.float32)
        if mask is None
        else jnp.roll(mask, -1, axis=1).astype(jnp.float32)
    )
    mask = mask.at[:, -1].set(0.0)
    valid = micro.get("valid")
    if valid is not None:
        mask = mask * valid.astype(jnp.float32)[:, None]
    return targets, mask


def _causal_lm_loss(state: TrainState, params, micro, dropout_rng,
                    quant=None, sinks=None):
    """Mean next-token CE per valid target position, in fp32."""
    logits, new_quant = _apply(
        state, params, micro, dropout_rng, quant, sinks=sinks
    )
    targets, mask = _lm_shift_and_mask(micro)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    )
    loss = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, (logits, new_quant)


_LOSS_FNS = {
    "classification": _classification_loss,
    "causal_lm": _causal_lm_loss,
}


def _fsdp_gather(state_shardings):
    """ZeRO-3 stated in the program: params sharded over ``fsdp`` are
    gathered (their spec with the ``fsdp`` axis dropped) for the forward
    and backward, so activations stay batch-sharded whatever the
    partitioner's propagation would have preferred. Left to propagation,
    Shardy shards the embedding lookups' OUTPUT on hidden (the table's
    fsdp dim) and then pays an all-to-all plus a full rematerialization
    to get back to the batch layout. Identity when nothing is laid out
    over ``fsdp``.

    The constraint transposes to itself, so each gradient is first asked
    for in the gathered layout and then resharded for the accumulator.
    XLA:TPU fuses that pair into ONE ring reduce-scatter (its
    all-reduce-scatter fusion; the full-size sum never reaches HBM): in
    the bert-large fsdp=4 step compiled for a v5e 2x2, 82 reduce-scatters
    carry the gradients and only 3 all-reduces remain, for the loss and
    the small replicated leaves. Constraining the cotangent to the sharded
    spec instead (custom_vjp) compiled to the same reductions plus an
    all-to-all and five times as many all-gathers, so it was not kept."""
    if state_shardings is None:
        return lambda params: params

    def keep(entry):
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a not in (None, "fsdp"))
        return axes[0] if len(axes) == 1 else (axes or None)

    def drop(sharding):
        return NamedSharding(sharding.mesh, P(*map(keep, sharding.spec)))

    gathered = jax.tree.map(drop, state_shardings.params)
    if gathered == state_shardings.params:
        return lambda params: params
    return lambda params: jax.lax.with_sharding_constraint(params, gathered)


#: the train step's own ``jax.named_scope``s, each opened OUTSIDE any
#: transformation so that its name survives differentiation as a plain
#: segment of every instruction's ``op_name`` (inside ``value_and_grad`` it
#: would come out as ``jvp(train.grad)``): one micro-batch's forward and
#: backward, the fp32 carry add, and the optimizer update with the gradient
#: norm. ``train_manifest`` lists them as its ``trace_scopes``, so the
#: warm start's audit writes which compiled instructions fall under each.
GRAD_SCOPE = "train.grad"
ACCUMULATE_SCOPE = "train.accumulate"
OPTIMIZER_SCOPE = "train.optimizer"
TRAIN_SCOPES = (GRAD_SCOPE, ACCUMULATE_SCOPE, OPTIMIZER_SCOPE)


def make_train_step(
    *,
    grad_accum_steps: int,
    mesh: Optional[Mesh] = None,
    state_shardings=None,
    objective: str = "classification",
    accum_dtype: str = "float32",
    chain_steps: int = 1,
    log_grad_norm: bool = True,
    unroll_accum: Optional[bool] = None,
) -> Callable:
    """Build the jitted train step.

    ``batch`` leaves: [grad_accum_steps, micro_batch, ...] (microbatch axis
    first so ``lax.scan`` walks it). With ``mesh`` given, inputs are
    constrained so the micro-batch dim shards over (data, fsdp) and the
    optimizer update runs under the provided state shardings — XLA inserts
    the per-boundary gradient AllReduce over ICI.

    ``chain_steps > 1`` returns a driver over PRE-PLACED batches with an
    extra leading [chain_steps] dim: ONE dispatch executes that many
    optimizer steps back-to-back on device (lax.scan over the per-step
    body). Host dispatch latency — a few ms per call through a remote
    runtime — amortizes across the chain; ``loss`` comes back as the MEAN
    over the chain (so epoch averages weight every step equally, matching
    chain_steps=1 artifacts) while other metrics report the LAST step
    (per-step metrics would force device->host syncs, defeating the
    point). The per-step numerics are identical to chain_steps=1.
    """

    forward_loss = _LOSS_FNS[objective]
    acc_dtype = jnp.dtype(accum_dtype)

    # The 1/accum scale is folded into the microbatch loss, so the summed
    # carry IS the mean gradient — no separate full-gradient scaling pass
    # after the scan (one read+write of every gradient, ~3 ms/step on
    # bert-large). Backward scales d(loss)/d(logits) by 1/accum at the
    # top, identical math to scaling the summed gradient.
    inv_accum = 1.0 / grad_accum_steps
    gather_fsdp = _fsdp_gather(state_shardings)

    def train_step(state: TrainState, batch):
        base_rng = jax.random.fold_in(state.dropout_rng, state.step)

        def micro_grads(carry, micro):
            grads_acc, loss_acc, quant = carry
            step_rng = jax.random.fold_in(base_rng, loss_acc[1].astype(jnp.int32))
            sinks0 = _sink_zeros(quant)

            if sinks0 is not None:
                # delayed dy scaling: the sinks' GRADIENTS are the
                # backward's observed dy amaxes (ops/quant.py) — read
                # them out and carry them with the fwd amaxes. The dy
                # observed here includes the 1/accum loss scaling, which
                # is exactly the magnitude next microbatch's backward
                # sees, so the carried scale is self-consistent.
                def loss_fn(p, sinks):
                    loss, (_, new_quant) = forward_loss(
                        state, gather_fsdp(p), micro, step_rng, quant,
                        sinks=sinks,
                    )
                    return loss * inv_accum, new_quant

                with jax.named_scope(GRAD_SCOPE):
                    (loss, new_quant), (grads, sink_grads) = jax.value_and_grad(
                        loss_fn, argnums=(0, 1), has_aux=True
                    )(state.params, sinks0)
                new_quant = _merge_dy_amaxes(new_quant, sink_grads)
            else:

                def loss_fn(p):
                    loss, (_, new_quant) = forward_loss(
                        state, gather_fsdp(p), micro, step_rng, quant
                    )
                    return loss * inv_accum, new_quant

                with jax.named_scope(GRAD_SCOPE):
                    (loss, new_quant), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(state.params)
            with jax.named_scope(ACCUMULATE_SCOPE):
                grads = jax.tree.map(
                    lambda a, g: a + g.astype(acc_dtype), grads_acc, grads
                )
            return (
                (grads, (loss_acc[0] + loss, loss_acc[1] + 1.0), new_quant),
                None,
            )

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dtype), state.params
        )
        # Small accumulation counts unroll fully by default: XLA folds the
        # zeros init into the first microbatch's gradients and schedules
        # across iterations (~3 ms/step on the 3-step bert-large recipe);
        # large counts keep the rolled loop for compile-time/code-size
        # sanity. ``unroll_accum`` overrides — unrolling lets XLA overlap
        # microbatch LIFETIMES, which raises peak activation memory
        # (gpt2-medium at micro 8 OOMs unrolled, fits rolled).
        # The delayed-quant amax collection rides the same carry (each
        # microbatch quantizes with the previous one's scales); None for
        # every other model — an empty pytree in the carry.
        unroll = (
            grad_accum_steps <= 4 if unroll_accum is None else unroll_accum
        )
        (grads, (loss_sum, _), final_quant), _ = jax.lax.scan(
            micro_grads,
            (
                zero_grads,
                (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                state.quant,
            ),
            batch,
            unroll=unroll,
        )
        # Gradients go to the optimizer in the CARRY dtype — fused_adamw
        # upcasts per-element in-register, so a tree-wide astype here would
        # only materialize a full fp32 copy of every gradient (~3 ms/step
        # on bert-large with a bf16 carry). Optimizer math is fp32 either
        # way (train/fused_adamw.py).
        with jax.named_scope(OPTIMIZER_SCOPE):
            new_state = state.apply_gradients(grads).replace(quant=final_quant)
            metrics = {
                "loss": loss_sum,  # sum of 1/accum-scaled losses == mean loss
            }
            if log_grad_norm:
                # one extra read of every gradient leaf (~0.7 GB on bert-large)
                metrics["grad_norm"] = jnp.sqrt(
                    sum(
                        jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)
                    )
                )
        return new_state, metrics

    if chain_steps > 1:
        single_step = train_step

        def train_step(state: TrainState, batches):  # noqa: F811
            # scan carries the metrics DICT as a pytree — no parallel key
            # list to keep in sync with whatever single_step emits
            state, stacked = jax.lax.scan(single_step, state, batches)
            out = {k: v[-1] for k, v in stacked.items()}
            # chain-mean loss: an epoch average built from these then
            # weights every optimizer step equally, not just chain tails
            out["loss"] = stacked["loss"].mean()
            return state, out

    donate = (0,)
    if mesh is None:
        return jax.jit(train_step, donate_argnums=donate)
    # With context parallelism the loader shards sequence dims per-leaf
    # (comms.ingest._leaf_spec); None lets jit inherit that committed layout
    # instead of forcing a replicated-on-seq reshard.
    if mesh.shape.get("seq", 1) > 1:
        batch_sharding = None
    else:
        pspec = TRAIN_BATCH_PSPEC
        if chain_steps > 1:  # extra leading [chain_steps] dim, unsharded
            pspec = P(None, *pspec)
        batch_sharding = NamedSharding(mesh, pspec)
    return jax.jit(
        train_step,
        donate_argnums=donate,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
    )


def make_eval_step(
    *,
    mesh: Optional[Mesh] = None,
    state_shardings=None,
    objective: str = "classification",
    apply_fn=None,
) -> Callable:
    """Build the jitted eval step → replicated scalar counts.

    classification: {"correct", "total", "tp", "fp", "fn"} summed over the
    (masked) batch — host-side ``MetricAccumulator`` folds batches; positive
    class for binary F1 is label 1 (GLUE/MRPC convention).
    causal_lm: {"nll_sum", "token_count", "token_correct"} — folds into
    ``LMMetricAccumulator`` (eval loss / perplexity / token accuracy).

    ``apply_fn`` evaluates through a DIFFERENT apply than training's over
    the same params — the pipeline trainer's serial-trunk eval (the GPipe
    param tree is identical to the serial scan model's by design), which
    frees eval batches from the n_micro × data-shard divisibility the
    schedule needs and skips the fill/drain bubble per eval batch.
    """

    def lm_eval_step(state: TrainState, batch):
        # eval quantizes with training's latest amaxes, unmutated (the
        # updated collection from this forward is discarded)
        logits = _apply(
            state, state.params, batch, None, state.quant, apply_fn
        )[0].astype(jnp.float32)
        targets, mask = _lm_shift_and_mask(batch)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        preds = jnp.argmax(logits, axis=-1)
        return {
            "nll_sum": (ce * mask).sum(),
            "token_count": mask.sum(),
            "token_correct": ((preds == targets) * mask).sum(),
        }

    def eval_step(state: TrainState, batch):
        logits, _ = _apply(
            state, state.params, batch, None, state.quant, apply_fn
        )
        preds = jnp.argmax(logits.astype(jnp.float32), axis=-1)
        labels = batch["labels"]
        valid = batch.get("valid")
        if valid is None:
            valid = jnp.ones_like(labels)
        valid = valid.astype(jnp.float32)
        correct = ((preds == labels) * valid).sum()
        pos_pred = (preds == 1) * valid
        pos_label = (labels == 1) * valid
        return {
            "correct": correct,
            "total": valid.sum(),
            "tp": (pos_pred * pos_label).sum(),
            "fp": (pos_pred * (1.0 - pos_label)).sum(),
            "fn": ((1.0 - pos_pred) * pos_label).sum(),
        }

    from pytorch_distributed_training_tpu.train.metrics import (
        LMMetricAccumulator,
        MetricAccumulator,
    )

    if objective == "causal_lm":
        fn, keys = lm_eval_step, LMMetricAccumulator.FIELDS
    else:
        fn, keys = eval_step, MetricAccumulator.FIELDS
    if mesh is None:
        return jax.jit(fn)
    if mesh.shape.get("seq", 1) > 1:
        batch_sharding = None  # inherit the loader's seq-sharded layout
    else:
        batch_sharding = NamedSharding(mesh, P(BATCH_AXES))
    replicated = NamedSharding(mesh, P())
    return jax.jit(
        fn,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings={k: replicated for k in keys},
    )
