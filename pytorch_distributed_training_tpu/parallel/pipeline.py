"""GPipe-scheduled pipeline parallelism over the ``stage`` mesh axis.

GSPMD layer-sharding (ShardingPolicy(stage=True)) places contiguous layer
blocks on stage slices but runs them SERIALLY — devices holding other
stages idle while one block executes (measured 1.68x/3.09x a same-chip DP
step at stage 2/4, scripts/bench_stage.py). This module adds the missing
*schedule*: microbatches stream through the stages shard_map-style, so at
steady state every stage computes a different microbatch concurrently —
the real generalization of the reference's 2-stage ConcatBert split
(reference test_model_parallelism.py:40-89, which also ran its stages
serially: bert_2 waited on bert_1's `.to(second_device)` activations).

Mechanics (classic GPipe fill/drain, expressed functionally):

- Inside ``shard_map`` over (``stage``,), each device holds its layer
  block: the scan-stacked params' leading [L] dim pre-sharded to
  [L/n_stages] per device.
- A ``lax.scan`` walks ``n_micro + n_stages - 1`` ticks. Each tick, every
  stage runs its block on its current activation, then the results rotate
  one hop around the ring (``ppermute``) — stage 0 feeds fresh
  microbatches in, the last stage's outputs land in the collection
  buffer. Fill/drain ticks compute garbage that is never read (the output
  index is clamped and masked), trading ``(n_stages-1)/n_micro`` bubble
  waste for full overlap — GPipe's standard deal.
- The whole thing is differentiable: the backward of ``ppermute`` is the
  reverse rotation, so ``jax.grad`` of a pipelined forward IS the
  pipelined backward schedule (fill/drain mirrored), with GPipe's
  keep-all-microbatch-activations memory profile; wrap ``layer_fn`` in
  ``jax.checkpoint`` for the 1F1B-ish memory trade.

Dropout rng streaming: ``gpipe_apply`` optionally consumes one PRNG key
per microbatch (streamed alongside the activations like ``bias``); inside
the schedule each stage folds in its stage index and each layer its local
layer index, so every (microbatch, layer) dropout site draws from a
distinct stream — and because the keys are a pure function of the primal
inputs, ``jax.grad``/remat regenerate bit-identical masks in the backward.

Delayed-int8 amax streaming (``stacked_quant``): the flax "quant"
collection's [num_layers]-leading amaxes shard over the stage axis like
the params, and each stage carries its slice across ticks — every
pipeline microbatch quantizes with the previous one's observations at
that site, the schedule-level twin of the standard step's accumulation
carry (train/step.py). 1F1B additionally stashes the scales each forward
tick used so its backward recompute quantizes identically; with a
data-sharded stream, in-flight scales are shard-local (tighter) and the
carried-out amax is the cross-shard max.
``GPipeClassifier`` packages the whole thing as an init/apply-compatible
stand-in for ``BertForSequenceClassification(scan_layers=True)``: same
parameter tree (checkpoints and ``ShardingPolicy(stage=True)`` shardings
carry over unchanged; ``models/relayout.py`` converts to/from the
unscanned layout), embeddings/pooler/head outside the pipelined trunk —
the trainable generalization of the reference's ConcatBert split
(reference test_model_parallelism.py:40-89), which also kept embeddings
with stage 0 and the pooler/classifier with the last stage.
"""

from __future__ import annotations

import functools
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def gpipe_apply(
    mesh: Mesh,
    layer_fn: Callable,
    stacked_params,
    microbatches,
    bias,
    *,
    axis: str = "stage",
    stream_spec: P | None = None,
    mb_keys=None,
    rng_impl=None,
    stacked_quant=None,
):
    """Run ``layer_fn`` stacked-layer trunk over microbatches, pipelined.

    Args:
        mesh: mesh whose ``axis`` dimension is the pipeline (size >= 1).
        layer_fn: ``(layer_params, x, bias) -> x`` for ONE layer, where
            ``layer_params`` is one slice of ``stacked_params`` minus the
            leading layer dim. With ``mb_keys`` given, the signature is
            ``(layer_params, x, bias, rng) -> x`` instead. With
            ``stacked_quant`` given, the per-layer quant subtree is the
            LAST argument and the return is ``(x, new_quant_layer)``.
        stacked_params: pytree with leading [num_layers] dim on every
            leaf; num_layers must divide by the stage count.
        microbatches: [n_micro, mb, ...] activations entering layer 0.
        bias: per-microbatch side input broadcast to every layer
            ([n_micro, ...]), e.g. the attention bias.
        stream_spec: PartitionSpec for the microbatch stream's dims
            (applied to both ``microbatches`` and ``bias``) — e.g.
            ``P(None, ("data", "fsdp"))`` to keep the batch dim
            data-sharded through the pipeline. Default: replicated.
        mb_keys: optional [n_micro, key_words] uint32 PRNG key data, one
            key per microbatch (``jax.random.key_data`` of folded keys).
            Each tick derives ``fold_in(key[mb], stage)`` and the local
            layer scan folds in the layer index, giving every
            (microbatch, global layer) a distinct dropout stream that the
            backward regenerates exactly (keys are primal-deterministic).
        rng_impl: the key impl (``jax.random.key_impl`` of the source
            key) — required with ``mb_keys`` to rewrap the raw key data.
        stacked_quant: optional delayed-int8 amax collection with the same
            leading [num_layers] dim (ops/quant.py). Sharded over the
            stage axis like the params; each stage carries its slice
            across ticks, so every pipeline microbatch quantizes with the
            amaxes the PREVIOUS microbatch observed at that site — the
            schedule-level generalization of the standard step's
            accumulation-scan carry. Fill/drain ticks (garbage inputs)
            mask their updates.

    Returns:
        [n_micro, mb, ...] activations after the last layer — identical
        (up to float reassociation) to running the layers sequentially.
        With ``stacked_quant``: ``(activations, new_stacked_quant)``.
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if num_layers % n_stages:
        raise ValueError(
            f"{num_layers} layers not divisible by {n_stages} stages"
        )
    if n_micro < n_stages:
        raise ValueError(
            f"need n_micro >= n_stages for a useful pipeline "
            f"(got {n_micro} < {n_stages})"
        )
    if mb_keys is not None and rng_impl is None:
        raise ValueError("mb_keys requires rng_impl (jax.random.key_impl)")
    has_quant = stacked_quant is not None

    # mesh axes the microbatch stream is sharded over (for per-shard
    # dropout-key folding inside the manual region)
    shard_axes: tuple = ()
    if stream_spec is not None:
        for entry in stream_spec:
            if entry is None:
                continue
            shard_axes += entry if isinstance(entry, tuple) else (entry,)

    local_block = _make_local_block(layer_fn, num_layers // n_stages)

    def inner(params_local, xs, biases, *rest):
        # params_local: [L/S, ...]; xs/biases carry the FULL microbatch
        # stream on every stage (replicated) — only stage 0 reads xs.
        from pytorch_distributed_training_tpu.ops import dispatch

        with dispatch.manual_region():
            return _inner_body(params_local, xs, biases, *rest)

    def _inner_body(params_local, xs, biases, *rest):
        rest = list(rest)
        keys = rest.pop(0) if mb_keys is not None else None
        q0 = rest.pop(0) if has_quant else None
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs, q = carry
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False
            )
            x = jnp.where(stage == 0, mb_in, buf)
            b_idx = jnp.clip(t - stage, 0, n_micro - 1)
            b = jax.lax.dynamic_index_in_dim(
                biases, b_idx, axis=0, keepdims=False
            )
            key = None
            if keys is not None:
                kd = jax.lax.dynamic_index_in_dim(
                    keys, b_idx, axis=0, keepdims=False
                )
                key = jax.random.fold_in(
                    jax.random.wrap_key_data(kd, impl=rng_impl), stage
                )
                if shard_axes:
                    # the microbatch stream is data-sharded (stream_spec):
                    # every shard must draw a DISTINCT dropout stream, same
                    # contract as every ops/dispatch shard_map wrapper
                    from pytorch_distributed_training_tpu.ops import dispatch

                    key = jax.random.fold_in(
                        key, dispatch.linear_device_index(shard_axes, mesh)
                    )
            y, new_q = local_block(params_local, x, b, key, q)
            if has_quant:
                # this stage computed microbatch f = t - stage; amaxes
                # observed on fill/drain garbage must not leak forward.
                # stop_gradient: the amax chain is observation-only (the
                # quantizer's custom vjp zeroes its cotangent anyway), and
                # GPipe's jax.grad backward must not be asked to
                # differentiate the carry — or transpose the cross-shard
                # pmax below, which has no AD rule.
                f_act = jnp.logical_and(t - stage >= 0, t - stage < n_micro)
                q = jax.tree.map(
                    lambda old, new: jnp.where(
                        f_act, jax.lax.stop_gradient(new), old
                    ),
                    q,
                    new_q,
                )
            # last stage finished microbatch t - (n_stages - 1)
            out_t = t - (n_stages - 1)
            write = jnp.logical_and(
                stage == n_stages - 1,
                jnp.logical_and(out_t >= 0, out_t < n_micro),
            )
            prev = jax.lax.dynamic_index_in_dim(
                outs, jnp.clip(out_t, 0, n_micro - 1), 0, keepdims=False
            )
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(write, y, prev),
                jnp.clip(out_t, 0, n_micro - 1),
                0,
            )
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs, q), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs, q_out), _ = jax.lax.scan(
            tick,
            (buf0, outs0, q0),
            jnp.arange(n_micro + n_stages - 1, dtype=jnp.int32),
        )
        # only the LAST stage's outs buffer is real; expose a leading
        # per-stage dim so the caller can select it.
        if has_quant:
            if shard_axes:
                # with a data-sharded stream each shard observed its own
                # rows' absmax (tighter scales in-flight); the CARRIED-OUT
                # amax must cover the whole microbatch — max across shards
                # (the out-spec would otherwise keep one shard's copy)
                q_out = jax.tree.map(
                    lambda a: jax.lax.pmax(a, shard_axes), q_out
                )
            return outs[None], q_out
        return outs[None]

    stream = stream_spec if stream_spec is not None else P()
    stacked_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    in_specs = [stacked_spec, stream, stream]
    args = [stacked_params, microbatches, bias]
    if mb_keys is not None:
        in_specs.append(P())  # keys are tiny; replicate to every stage
        args.append(mb_keys)
    out_specs = P(axis, *stream)
    if has_quant:
        in_specs.append(jax.tree.map(lambda _: P(axis), stacked_quant))
        args.append(stacked_quant)
        out_specs = (
            out_specs,
            jax.tree.map(lambda _: P(axis), stacked_quant),
        )
    out = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )(*args)
    if has_quant:
        return out[0][-1], out[1]
    return out[-1]


def _make_local_block(layer_fn: Callable, layers_per_stage: int):
    """One stage's layer scan, shared by both schedules.

    ``layer_fn`` arity follows the caller's configuration: a trailing rng
    argument when dropout keys stream, a trailing per-layer quant subtree
    (returned updated as ``(x, new_quant)``) when delayed int8 threads.
    Returns ``(out, new_quant_or_None)``.
    """

    def local_block(params_local, x, b, key=None, q_local=None):
        layer_idx = jnp.arange(layers_per_stage, dtype=jnp.int32)
        if q_local is None:
            if key is None:

                def body(h, lp):
                    return layer_fn(lp, h, b), None

                out, _ = jax.lax.scan(body, x, params_local)
            else:

                def body(h, lp_i):
                    lp, li = lp_i
                    return (
                        layer_fn(lp, h, b, jax.random.fold_in(key, li)),
                        None,
                    )

                out, _ = jax.lax.scan(body, x, (params_local, layer_idx))
            return out, None
        if key is None:

            def body(h, lp_q):
                lp, ql = lp_q
                return layer_fn(lp, h, b, ql)  # -> (h', new_ql)

            out, new_q = jax.lax.scan(body, x, (params_local, q_local))
        else:

            def body(h, lp_q_i):
                lp, ql, li = lp_q_i
                return layer_fn(lp, h, b, jax.random.fold_in(key, li), ql)

            out, new_q = jax.lax.scan(
                body, x, (params_local, q_local, layer_idx)
            )
        return out, new_q

    return local_block


def one_f_one_b_grads(
    mesh: Mesh,
    layer_fn: Callable,
    head_fn: Callable,
    stacked_params,
    head_params,
    xs,
    biases,
    labels,
    *,
    axis: str = "stage",
    stream_spec: P | None = None,
    mb_keys=None,
    rng_impl=None,
    stacked_quant=None,
):
    """1F1B-scheduled pipeline TRAINING pass → (loss, grads, input cotangents).

    Where :func:`gpipe_apply` is a forward whose backward ``jax.grad``
    derives (keeping every microbatch's activations alive — O(n_micro)
    memory), this runs the classic one-forward-one-backward schedule: the
    per-microbatch loss is computed INSIDE the last stage the moment that
    microbatch's forward finishes, so its backward starts immediately and
    interleaves with the remaining forwards. Peak activation stash per
    stage is bounded by the STAGE count (a [2·n_stages] circular buffer of
    block inputs; the block's internals recompute in the backward tick,
    the same trade ``cfg.remat`` makes under GPipe) instead of the
    microbatch count — the property that lets deep pipelines raise
    n_micro (smaller bubble) without growing memory. Total ticks:
    ``n_micro + 2(n_stages-1)`` vs GPipe's ``2(n_micro + n_stages - 1)``
    for forward+backward — F and B share ticks at steady state.

    Args (beyond :func:`gpipe_apply`'s):
        head_fn: ``(head_params, y, labels_mb) -> scalar loss`` for ONE
            microbatch — pooler/classifier/CE evaluated at the last stage
            (``(hp, y, lab, rng)`` when ``mb_keys`` is given). With a
            sharded ``stream_spec`` it sees only the LOCAL rows of the
            microbatch, so use SUM-based losses scaled by the GLOBAL row
            count — the engine psums loss and parameter gradients across
            the stream shards (unlike :func:`gpipe_apply`, whose grads
            form OUTSIDE shard_map where GSPMD inserts the reductions).
        head_params: its param pytree (replicated to every stage).
        labels: [n_micro, mb] integer labels streamed with the batch.
        stacked_quant: optional delayed-int8 amax collection ([L]-leading,
            ops/quant.py), threaded as in :func:`gpipe_apply` — PLUS a
            per-slot stash of the scales each forward tick actually used,
            so the backward tick's block recompute quantizes with the
            exact same scales (the carry has advanced by up to
            ``2(S-1)`` ticks in between). ``layer_fn`` then takes the
            per-layer quant subtree last and returns ``(x, new_quant)``.

    Returns:
        (loss_sum, trunk_grads [L, ...], head_grads, d_xs [n_micro, ...])
        — ``d_xs`` are the cotangents at the trunk input, for the caller
        to feed the embedding backward (embeddings live outside the
        pipeline, as in the reference's ConcatBert split). With
        ``stacked_quant``, a fifth element: the updated [L] amaxes.

    The schedule (stage s, tick t; S = n_stages):
        forward of microbatch f = t - s;   backward of b = t - 2(S-1) + s.
        The last stage's F and B of the same microbatch share a tick (its
        head vjp bridges them); cotangents hop the reverse ring. Inactive
        (fill/drain) F/B ticks compute on garbage and mask their writes —
        bubble fraction ``2(S-1) / (n_micro + 2(S-1))``.
    """
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if num_layers % n_stages:
        raise ValueError(
            f"{num_layers} layers not divisible by {n_stages} stages"
        )
    if n_micro < n_stages:
        raise ValueError(
            f"need n_micro >= n_stages for a useful pipeline "
            f"(got {n_micro} < {n_stages})"
        )
    if mb_keys is not None and rng_impl is None:
        raise ValueError("mb_keys requires rng_impl (jax.random.key_impl)")
    stash_size = 2 * n_stages  # max residual lifetime is 2(S-1) ticks
    has_quant = stacked_quant is not None

    shard_axes: tuple = ()
    if stream_spec is not None:
        for entry in stream_spec:
            if entry is None:
                continue
            shard_axes += entry if isinstance(entry, tuple) else (entry,)

    layers_per_stage = num_layers // n_stages
    local_block = _make_local_block(layer_fn, layers_per_stage)

    def inner(params_local, head_p, xs_, biases_, labels_, *rest):
        from pytorch_distributed_training_tpu.ops import dispatch

        with dispatch.manual_region():
            return _inner_body(
                params_local, head_p, xs_, biases_, labels_, *rest
            )

    def _inner_body(params_local, head_p, xs_, biases_, labels_, *rest):
        rest = list(rest)
        keys = rest.pop(0) if mb_keys is not None else None
        q0 = rest.pop(0) if has_quant else None
        stage = jax.lax.axis_index(axis)
        last = n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        def derive_key(mb_idx):
            if keys is None:
                return None
            kd = jax.lax.dynamic_index_in_dim(
                keys, mb_idx, axis=0, keepdims=False
            )
            key = jax.random.fold_in(
                jax.random.wrap_key_data(kd, impl=rng_impl), stage
            )
            if shard_axes:
                from pytorch_distributed_training_tpu.ops import dispatch

                key = jax.random.fold_in(
                    key, dispatch.linear_device_index(shard_axes, mesh)
                )
            return key

        def masked_add(acc, upd, active):
            m = active.astype(jnp.float32)
            return jax.tree.map(
                lambda a, u: a + (u * m).astype(a.dtype), acc, upd
            )

        def tick(carry, t):
            fbuf, bbuf, stash, stash_q, tg, hg, loss_sum, dxs, q = carry

            # ---------------- forward of microbatch f = t - stage
            mb_f = t - stage
            f_act = jnp.logical_and(mb_f >= 0, mb_f < n_micro)
            mb_f_c = jnp.clip(mb_f, 0, n_micro - 1)
            x_in = jnp.where(
                stage == 0,
                jax.lax.dynamic_index_in_dim(xs_, mb_f_c, 0, keepdims=False),
                fbuf,
            )
            b_f = jax.lax.dynamic_index_in_dim(
                biases_, mb_f_c, 0, keepdims=False
            )
            key_f = derive_key(mb_f_c)
            y, new_q = local_block(params_local, x_in, b_f, key_f, q)
            # stash the block INPUT (internals recompute in the B tick)
            slot_f = mb_f_c % stash_size
            prev_slot = jax.lax.dynamic_index_in_dim(
                stash, slot_f, 0, keepdims=False
            )
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_act, x_in, prev_slot), slot_f, 0
            )
            if has_quant:
                # stash the PRE-update amaxes (the scales this forward
                # actually quantized with) for the backward recompute,
                # then advance the carry with the fresh observations
                def _stash_q(sq, qv):
                    prev = jax.lax.dynamic_index_in_dim(
                        sq, slot_f, 0, keepdims=False
                    )
                    return jax.lax.dynamic_update_index_in_dim(
                        sq, jnp.where(f_act, qv, prev), slot_f, 0
                    )

                stash_q = jax.tree.map(_stash_q, stash_q, q)
                q = jax.tree.map(
                    lambda old, new: jnp.where(f_act, new, old), q, new_q
                )

            # last stage: head F+B for mb_f right now (bridges F into B)
            lab_f = jax.lax.dynamic_index_in_dim(
                labels_, mb_f_c, 0, keepdims=False
            )
            if key_f is None:
                hfn = lambda hp, yy: head_fn(hp, yy, lab_f)  # noqa: E731
            else:
                # distinct from the layer folds 0..layers_per_stage-1
                head_key = jax.random.fold_in(key_f, layers_per_stage)
                hfn = lambda hp, yy: head_fn(  # noqa: E731
                    hp, yy, lab_f, head_key
                )
            (loss_mb, (dhp, dy)) = jax.value_and_grad(
                hfn, argnums=(0, 1)
            )(head_p, y)
            head_act = jnp.logical_and(f_act, stage == last)
            hg = masked_add(hg, dhp, head_act)
            loss_sum = loss_sum + jnp.where(head_act, loss_mb, 0.0)

            # ---------------- backward of microbatch b = t - 2(S-1) + stage
            mb_b = t - 2 * (n_stages - 1) + stage
            b_act = jnp.logical_and(mb_b >= 0, mb_b < n_micro)
            mb_b_c = jnp.clip(mb_b, 0, n_micro - 1)
            slot_b = mb_b_c % stash_size
            x_b = jax.lax.dynamic_index_in_dim(stash, slot_b, 0, keepdims=False)
            b_b = jax.lax.dynamic_index_in_dim(
                biases_, mb_b_c, 0, keepdims=False
            )
            key_b = derive_key(mb_b_c)
            g_in = jnp.where(stage == last, dy, bbuf).astype(y.dtype)
            q_b = (
                jax.tree.map(
                    lambda sq: jax.lax.dynamic_index_in_dim(
                        sq, slot_b, 0, keepdims=False
                    ),
                    stash_q,
                )
                if has_quant
                else None
            )

            def block_f(p, x):
                return local_block(p, x, b_b, key_b, q_b)[0]

            _, block_vjp = jax.vjp(block_f, params_local, x_b)
            dp, dx = block_vjp(g_in)
            tg = masked_add(tg, dp, b_act)
            dxs = jax.lax.dynamic_update_index_in_dim(
                dxs,
                jnp.where(
                    jnp.logical_and(b_act, stage == 0),
                    dx,
                    jax.lax.dynamic_index_in_dim(
                        dxs, mb_b_c, 0, keepdims=False
                    ),
                ),
                mb_b_c,
                0,
            )

            fbuf = jax.lax.ppermute(y, axis, fwd_perm)
            bbuf = jax.lax.ppermute(dx, axis, bwd_perm)
            return (
                fbuf, bbuf, stash, stash_q, tg, hg, loss_sum, dxs, q
            ), None

        zero_x = jnp.zeros_like(xs_[0])
        carry0 = (
            zero_x,  # fwd hop buffer
            zero_x,  # bwd hop buffer (cotangents share x's shape)
            jnp.zeros((stash_size, *zero_x.shape), zero_x.dtype),
            jax.tree.map(
                lambda l: jnp.zeros((stash_size, *l.shape), l.dtype), q0
            ),  # per-slot amax stash (None -> empty pytree without quant)
            jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_local
            ),
            jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), head_p
            ),
            jnp.zeros((), jnp.float32),
            jnp.zeros(xs_.shape, xs_.dtype),
            q0,
        )
        n_ticks = n_micro + 2 * (n_stages - 1)
        (_, _, _, _, tg, hg, loss_sum, dxs, q_out), _ = jax.lax.scan(
            tick, carry0, jnp.arange(n_ticks, dtype=jnp.int32)
        )
        if shard_axes:
            # the stream is batch-sharded and the grads formed INSIDE this
            # manual region: sum the per-shard contributions (row-level
            # outputs like dxs stay sharded). Quant amaxes are NOT summed:
            # every stream shard observed its own rows' absmax — take the
            # max so the carried scale covers the whole microbatch, the
            # same semantics as the unsharded absmax.
            tg = jax.lax.psum(tg, shard_axes)
            hg = jax.lax.psum(hg, shard_axes)
            loss_sum = jax.lax.psum(loss_sum, shard_axes)
            if has_quant:
                q_out = jax.tree.map(
                    lambda a: jax.lax.pmax(a, shard_axes), q_out
                )
        # per-stage results that are only real on ONE stage get a leading
        # stage dim; the caller selects (same trick as gpipe_apply's outs)
        out = (
            tg,
            jax.tree.map(lambda g: g[None], hg),
            loss_sum[None],
            dxs[None],
        )
        if has_quant:
            out = out + (q_out,)
        return out

    stream = stream_spec if stream_spec is not None else P()
    stacked_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    head_spec = jax.tree.map(lambda _: P(), head_params)
    label_spec = P(*stream) if stream_spec is not None else P()
    in_specs = [stacked_spec, head_spec, stream, stream, label_spec]
    args = [stacked_params, head_params, xs, biases, labels]
    if mb_keys is not None:
        in_specs.append(P())
        args.append(mb_keys)
    out_specs = (
        jax.tree.map(lambda _: P(axis), stacked_params),
        jax.tree.map(lambda _: P(axis), head_params),
        P(axis),
        P(axis, *stream),
    )
    if has_quant:
        in_specs.append(jax.tree.map(lambda _: P(axis), stacked_quant))
        args.append(stacked_quant)
        out_specs = out_specs + (
            jax.tree.map(lambda _: P(axis), stacked_quant),
        )
    res = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )(*args)
    tg, hg, loss, dxs = res[:4]
    # head grads / loss are real on the LAST stage; dxs on stage 0
    out = (
        loss[-1],
        tg,
        jax.tree.map(lambda g: g[-1], hg),
        dxs[0],
    )
    if has_quant:
        out = out + (res[4],)
    return out


def gpipe_trunk_fn(cfg, *, with_dropout: bool = False,
                   with_quant: bool = False):
    """``layer_fn`` for ``gpipe_apply`` from this framework's BertLayer —
    one post-LN encoder layer (models/bert.py). ``with_dropout`` switches
    to the rng signature (training mode: the streamed per-(tick, stage,
    layer) key drives the layer's dropout sites); ``with_quant`` appends
    the per-layer delayed-int8 amax subtree (ops/quant.py) as the last
    argument and returns ``(x, new_quant)`` — the schedules thread it
    through their tick carries. ``cfg.remat`` wraps the layer in
    jax.checkpoint (GPipe's memory trade)."""
    from pytorch_distributed_training_tpu.models.bert import BertLayer

    layer = BertLayer(cfg)

    if with_quant:

        def q_apply(layer_params, x, bias, quant, rng):
            y, mut = layer.apply(
                {"params": layer_params, "quant": quant}, x, bias,
                rng is None,
                rngs={"dropout": rng} if rng is not None else None,
                mutable=["quant"],
            )
            return y, mut["quant"]

        if with_dropout:

            def fn(layer_params, x, bias, rng, ql):
                return q_apply(layer_params, x, bias, ql, rng)

        else:

            def fn(layer_params, x, bias, ql):
                return q_apply(layer_params, x, bias, ql, None)

    elif with_dropout:

        def fn(layer_params, x, bias, rng):
            return layer.apply(
                {"params": layer_params}, x, bias, False,
                rngs={"dropout": rng},
            )

    else:

        def fn(layer_params, x, bias):
            return layer.apply({"params": layer_params}, x, bias, True)

    if cfg.remat:
        fn = jax.checkpoint(fn)
    return fn


def make_1f1b_train_step(
    config,
    mesh: Mesh,
    state_shardings,
    *,
    n_micro: int,
    grad_accum_steps: int,
    accum_dtype: str = "float32",
    batch_axes=("data", "fsdp"),
):
    """Jitted classifier train step whose trunk runs the 1F1B schedule.

    The ``--mp-mode 1f1b`` twin of the Trainer's standard step
    (train/step.py) for ``BertForSequenceClassification(scan_layers=True)``
    param trees: embeddings forward outside the pipeline (``jax.vjp``
    bridges its backward from the schedule's input cotangents), the
    pooler/classifier head INSIDE the last stage so each microbatch's
    backward starts the moment its forward finishes, gradient accumulation
    as the usual microbatch scan. Metrics additionally report
    ``pipeline_bubble`` — the schedule's idle fraction
    ``2(S-1)/(n_micro + 2(S-1))``.

    Memory vs GPipe (``--mp-mode pipeline``): GPipe's jax.grad backward
    keeps every microbatch's activations alive (O(n_micro) stash per
    stage); this keeps a [2·n_stages] circular buffer of block INPUTS and
    recomputes block internals per backward tick — O(n_stages), so
    n_micro (bubble) scales without memory growth.
    """
    import optax
    from jax.sharding import NamedSharding

    from pytorch_distributed_training_tpu.comms.mesh import TRAIN_BATCH_PSPEC
    from pytorch_distributed_training_tpu.models.bert import (
        BertEmbeddings,
        default_position_ids,
    )
    from pytorch_distributed_training_tpu.ops.attention import (
        make_attention_bias,
    )

    cfg = config
    if cfg.causal:
        raise ValueError("make_1f1b_train_step is an encoder-classifier step")
    if not cfg.scan_layers:
        raise ValueError(
            "make_1f1b_train_step requires scan_layers=True (the schedule "
            "shards the stacked layer dim over the stage axis)"
        )
    if getattr(cfg, "quant_delayed_grads", False):
        raise ValueError(
            "quant_delayed_grads is unsupported under the 1F1B schedule "
            "(the sink-gradient channel is not threaded through the tick "
            "vjp); use plain quant_delayed"
        )
    n_stages = mesh.shape["stage"]
    emb = BertEmbeddings(cfg)
    pool = _PoolerHead(cfg)
    clf = _ClassifierHead(cfg)
    acc_dtype = jnp.dtype(accum_dtype)
    bubble = 2 * (n_stages - 1) / (n_micro + 2 * (n_stages - 1))
    dropout_on = cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0
    # delayed int8: the trunk amaxes stream through the schedule's tick
    # carry (heads have no quant sites — plain nn.Dense, models/bert.py)
    delayed = bool(getattr(cfg, "quant_delayed", False))
    layer_fn = gpipe_trunk_fn(
        cfg, with_dropout=dropout_on, with_quant=delayed
    )
    stream_spec = P(None, tuple(batch_axes))

    def make_head_fn(mb_rows_global):
        # SUM-based (engine psums across stream shards — head_fn only sees
        # local rows): per-row CE / (global rows per pipeline microbatch ×
        # n_micro × accum) reconstructs the global-batch mean loss exactly
        denom = mb_rows_global * n_micro * grad_accum_steps

        def head_fn(hp, y, lab, key=None):
            rngs = {"dropout": key} if key is not None else None
            pooled = pool.apply(
                {"params": {"pooler": hp["pooler"]}}, y, key is None,
                rngs=rngs,
            )
            logits = clf.apply(
                {"params": {"classifier": hp["classifier"]}},
                pooled, key is None, rngs=rngs,
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), lab
            )
            return ce.sum() / denom

        return head_fn

    def train_step(state, batch):
        base_rng = jax.random.fold_in(state.dropout_rng, state.step)

        def micro_grads(carry, micro):
            grads_acc, loss_acc, quant = carry
            step_rng = jax.random.fold_in(
                base_rng, loss_acc[1].astype(jnp.int32)
            )
            params = state.params
            ids = micro["input_ids"]
            batch_rows = ids.shape[0]
            mb = batch_rows // n_micro
            tt = micro.get("token_type_ids")
            if tt is None:
                tt = jnp.zeros_like(ids)
            pos = default_position_ids(cfg, ids)
            mask = micro.get("attention_mask")
            bias = make_attention_bias(mask)
            if bias is None:
                bias = jnp.zeros((batch_rows, 1, 1, ids.shape[1]), jnp.float32)

            emb_rng = jax.random.fold_in(step_rng, 0)
            pipe_rng = jax.random.fold_in(step_rng, 1)

            def emb_fwd(emb_params):
                return emb.apply(
                    {"params": emb_params}, ids, tt, pos, not dropout_on,
                    rngs={"dropout": emb_rng} if dropout_on else None,
                )

            x, emb_vjp = jax.vjp(emb_fwd, params["bert"]["embeddings"])
            xs = x.reshape(n_micro, mb, *x.shape[1:])
            biases = bias.reshape(n_micro, mb, *bias.shape[1:])
            labels = micro["labels"].reshape(n_micro, mb)
            mb_keys = rng_impl = None
            if dropout_on:
                keys = jax.vmap(
                    lambda i: jax.random.fold_in(pipe_rng, i)
                )(jnp.arange(n_micro, dtype=jnp.int32))
                mb_keys = jax.random.key_data(keys)
                rng_impl = jax.random.key_impl(pipe_rng)

            res = one_f_one_b_grads(
                mesh, layer_fn, make_head_fn(mb),
                params["bert"]["layers_scan"]["layer"],
                {
                    "pooler": params["bert"]["pooler"],
                    "classifier": params["classifier"],
                },
                xs, biases, labels,
                stream_spec=stream_spec,
                mb_keys=mb_keys, rng_impl=rng_impl,
                stacked_quant=(
                    quant["bert"]["layers_scan"]["layer"]
                    if delayed
                    else None
                ),
            )
            loss, tg, hg, dxs = res[:4]
            if delayed:
                quant = {
                    **quant,
                    "bert": {
                        **quant["bert"],
                        "layers_scan": {"layer": res[4]},
                    },
                }
            (d_emb,) = emb_vjp(
                dxs.reshape(batch_rows, *x.shape[1:]).astype(x.dtype)
            )
            grads = {
                "bert": {
                    "embeddings": d_emb,
                    "layers_scan": {"layer": tg},
                    "pooler": hg["pooler"],
                },
                "classifier": hg["classifier"],
            }
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(acc_dtype), grads_acc, grads
            )
            return (
                grads_acc,
                (loss_acc[0] + loss, loss_acc[1] + 1.0),
                quant,
            ), None

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dtype), state.params
        )
        (grads, (loss_sum, _), final_quant), _ = jax.lax.scan(
            micro_grads,
            (
                zero_grads,
                (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                state.quant,
            ),
            batch,
            unroll=grad_accum_steps <= 4,
        )
        new_state = state.apply_gradients(grads).replace(quant=final_quant)
        return new_state, {
            "loss": loss_sum,
            "pipeline_bubble": jnp.float32(bubble),
        }

    return jax.jit(
        train_step,
        donate_argnums=(0,),
        in_shardings=(
            state_shardings,
            NamedSharding(mesh, TRAIN_BATCH_PSPEC),
        ),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
    )


class _PoolerHead(nn.Module):
    """Standalone wrapper registering the same ``pooler`` param subtree
    the full model's ``pool_cls`` does (models/bert.py)."""

    config: "object"

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        from pytorch_distributed_training_tpu.models.bert import pool_cls

        return pool_cls(self.config, x, deterministic)


class _ClassifierHead(nn.Module):
    """Standalone wrapper registering the same ``classifier`` subtree the
    full model's ``classify`` does (models/bert.py)."""

    config: "object"

    @nn.compact
    def __call__(self, pooled, deterministic: bool = True):
        from pytorch_distributed_training_tpu.models.bert import classify

        return classify(self.config, pooled, deterministic)


class GPipeClassifier:
    """``BertForSequenceClassification(scan_layers=True)`` twin whose trunk
    runs through the GPipe schedule — the *trainable* pipeline.

    init/apply-compatible with ``create_train_state`` and the shared
    ``Trainer``: ``init`` delegates to the real flax model, so the
    parameter tree (and therefore ``ShardingPolicy(stage=True)`` shardings,
    orbax checkpoints, and ``models/relayout.py`` conversions) is identical
    to the serial scan-stacked model. ``apply`` splits the batch into
    ``n_micro`` pipeline microbatches (a pure reshape — row→microbatch
    assignment is semantically free for a per-row loss), runs embeddings
    outside the pipeline, streams the microbatches through
    ``gpipe_apply`` with per-microbatch dropout keys, then applies the
    pooler + classifier head. Mirrors the reference ConcatBert's split
    (embeddings with stage 0, pooler/classifier after the last stage,
    reference test_model_parallelism.py:40-89) but with the stages
    actually overlapping and ``jax.grad`` giving the backward schedule.

    Dropout caveat: flax folds RNGs per module *path*, and here each layer
    is applied standalone — masks therefore differ from the serial model's
    stream for the same seed (seed-level variation, same statistics). At
    dropout 0 / deterministic the logits match the serial model exactly
    (pinned by tests/test_pipeline.py).
    """

    def __init__(self, config, mesh: Mesh, n_micro: int,
                 *, batch_axes=("data", "fsdp")):
        if not config.scan_layers:
            raise ValueError("GPipeClassifier requires scan_layers=True "
                             "(the stage axis shards the stacked layer dim)")
        if config.causal:
            raise ValueError("GPipeClassifier is an encoder-classifier trunk")
        if getattr(config, "quant_delayed_grads", False):
            raise ValueError(
                "quant_delayed_grads is unsupported under the GPipe "
                "schedule (the sink-gradient channel is not threaded "
                "through jax.grad of the pipeline); use plain quant_delayed"
            )
        self.config = config
        self.mesh = mesh
        self.n_micro = int(n_micro)
        self.batch_axes = tuple(batch_axes)
        from pytorch_distributed_training_tpu.models.bert import (
            BertEmbeddings,
            BertForSequenceClassification,
        )

        self._inner = BertForSequenceClassification(config)
        self._emb = BertEmbeddings(config)
        self._pool = _PoolerHead(config)
        self._head = _ClassifierHead(config)

    def init(self, rngs, *args, **kwargs):
        return self._inner.init(rngs, *args, **kwargs)

    @property
    def serial_apply(self):
        """Apply the SAME params through the serial scan trunk (no pipeline
        schedule). The param tree is identical by design, so this is free —
        the Trainer evaluates through it (train.step.make_eval_step
        ``apply_fn``), which removes the eval-batch n_micro × data-shard
        divisibility constraint and the per-eval-batch fill/drain bubble."""
        return self._inner.apply

    def apply(
        self,
        variables,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        position_ids=None,
        deterministic: bool = True,
        rngs=None,
        mutable=False,
    ):
        from pytorch_distributed_training_tpu.models.bert import (
            default_position_ids,
        )
        from pytorch_distributed_training_tpu.ops.attention import (
            make_attention_bias,
        )

        cfg = self.config
        n = self.n_micro
        batch = input_ids.shape[0]
        if batch % n:
            raise ValueError(
                f"micro-batch size {batch} not divisible by "
                f"n_micro={n} pipeline microbatches"
            )
        dshard = 1
        for a in self.batch_axes:
            dshard *= self.mesh.shape.get(a, 1)
        if (batch // n) % dshard:
            raise ValueError(
                f"pipeline microbatch size {batch // n} (= {batch}/{n}) "
                f"must divide over the data axes "
                f"({'x'.join(self.batch_axes)} = {dshard}) — lower "
                f"n_micro or raise the micro-batch size"
            )
        params = variables["params"]
        bert = params["bert"]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        if position_ids is None:
            position_ids = default_position_ids(cfg, input_ids)
        x = self._emb.apply(
            {"params": bert["embeddings"]},
            input_ids, token_type_ids, position_ids, deterministic,
            rngs=rngs,
        )
        bias = make_attention_bias(attention_mask)
        if bias is None:
            bias = jnp.zeros((batch, 1, 1, x.shape[1]), jnp.float32)
        xs = x.reshape(n, batch // n, *x.shape[1:])
        biases = bias.reshape(n, batch // n, *bias.shape[1:])

        dropout_on = not deterministic and (
            cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0
        )
        mb_keys = rng_impl = None
        if dropout_on:
            if not rngs or "dropout" not in rngs:
                raise ValueError("training with dropout needs rngs['dropout']")
            base = rngs["dropout"]
            keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
                jnp.arange(n, dtype=jnp.int32)
            )
            mb_keys = jax.random.key_data(keys)
            rng_impl = jax.random.key_impl(base)
        # delayed int8 (ops/quant.py): thread the trunk amaxes through the
        # schedule's tick carry — every pipeline microbatch quantizes with
        # the previous one's observations, per stage. Heads have no quant
        # sites (plain nn.Dense, models/bert.py).
        quant = variables.get("quant") if cfg.quant_delayed else None
        trunk_q = (
            quant["bert"]["layers_scan"]["layer"]
            if quant is not None
            else None
        )
        layer_fn = gpipe_trunk_fn(
            cfg, with_dropout=dropout_on, with_quant=trunk_q is not None
        )
        out = gpipe_apply(
            self.mesh,
            layer_fn,
            bert["layers_scan"]["layer"],
            xs,
            biases,
            stream_spec=P(None, self.batch_axes),
            mb_keys=mb_keys,
            rng_impl=rng_impl,
            stacked_quant=trunk_q,
        )
        if trunk_q is not None:
            out, new_trunk_q = out
        x = out.reshape(batch, *out.shape[2:])
        pooled = self._pool.apply(
            {"params": {"pooler": bert["pooler"]}}, x, deterministic,
            rngs=rngs,
        )
        logits = self._head.apply(
            {"params": {"classifier": params["classifier"]}},
            pooled, deterministic, rngs=rngs,
        )
        if mutable:
            # flax apply contract (train/step.py::_apply): (out, updated)
            if trunk_q is None:
                raise ValueError(
                    "mutable=['quant'] apply needs a 'quant' collection in "
                    "variables and quant_delayed=True on the config"
                )
            new_quant = {
                **quant,
                "bert": {
                    **quant["bert"],
                    "layers_scan": {"layer": new_trunk_q},
                },
            }
            return logits, {"quant": new_quant}
        return logits
