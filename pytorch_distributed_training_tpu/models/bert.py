"""In-repo BERT encoder family (flax.linen), TPU-first.

The reference always rides HuggingFace's torch BERT
(``AutoModelForSequenceClassification("bert-large-cased")``, reference
test_data_parallelism.py:112; three ``bert-base-cased`` instances,
test_model_parallelism.py:230-238). This framework owns the model: a pure
functional flax implementation whose parameter layout is deliberately
HF-mappable (see ``models.hf_loader``) so pretrained checkpoints load when a
hub cache is available, while everything else — dtype policy, attention
implementation, remat, sharding — is native to this framework.

TPU design notes:
- bf16 compute / fp32 params policy (the fp16-AMP replacement, SURVEY.md §2b):
  every Dense/Embed takes ``dtype=compute_dtype, param_dtype=param_dtype``;
  softmax and LayerNorm statistics stay fp32.
- Q/K/V/O projections are ``DenseGeneral`` straight to/from
  [heads, head_dim] — one reshape-free matmul each, MXU-friendly.
- ``config.remat`` wraps each layer in ``jax.checkpoint`` to trade FLOPs for
  HBM on long sequences / big batches.
- RoBERTa is the same trunk with pad-offset learned positions and no token
  types (``config.roberta_style``); GPT-2 reuses the attention stack with
  ``causal=True`` (see ``models.gpt2``).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.ops.layer_norm import (
    FusedDropoutAddLayerNorm,
    FusedLayerNorm,
)
from pytorch_distributed_training_tpu.ops.attention import (
    dot_product_attention,
    make_attention_bias,
)
from pytorch_distributed_training_tpu.ops.dropout import Dropout
from pytorch_distributed_training_tpu.ops.paged_attention import paged_attention
from pytorch_distributed_training_tpu.ops.quant import quantize_kv
from pytorch_distributed_training_tpu.utils.config import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)

def _ln(cfg: "ModelConfig", name: str) -> FusedLayerNorm:
    """LayerNorm with fp32 stats emitting the compute dtype directly (the
    fused Pallas kernel on TPU; identical jnp math elsewhere)."""
    return FusedLayerNorm(
        epsilon=cfg.layer_norm_eps, param_dtype=_pdtype(cfg),
        out_dtype=_dtype(cfg), impl=cfg.layernorm_impl, name=name,
    )



class BertEmbeddings(nn.Module):
    config: ModelConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids, position_ids, deterministic):
        cfg = self.config
        kw = dict(dtype=_dtype(cfg), param_dtype=_pdtype(cfg))
        embed_init = nn.initializers.normal(stddev=0.02)
        words = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=embed_init,
            name="word_embeddings", **kw,
        )(input_ids)
        positions = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size,
            embedding_init=embed_init, name="position_embeddings", **kw,
        )(position_ids)
        x = words + positions
        if cfg.type_vocab_size:
            # RoBERTa has a SIZE-1 type table (HF parity) while pair tasks
            # feed segment ids {0,1}: clamp explicitly instead of relying
            # on XLA's silent OOB-gather clamp. The constant embedding adds
            # no segment signal — random-init RoBERTa therefore learns
            # pair tasks noticeably slower than BERT (measured on the
            # synthetic recipe: the segment cue is the easiest feature,
            # NOTES.md round-4 RoBERTa section).
            types = jnp.clip(token_type_ids, 0, cfg.type_vocab_size - 1)
            x = x + nn.Embed(
                cfg.type_vocab_size, cfg.hidden_size, embedding_init=embed_init,
                name="token_type_embeddings", **kw,
            )(types)
        x = _ln(cfg, "norm")(x)
        return Dropout(cfg.hidden_dropout, cfg.dropout_impl)(
            x, deterministic=deterministic
        )


def dense_general(cfg: ModelConfig, features, axis, name, kw):
    """nn.DenseGeneral or its int8-MXU twin (ops/quant.py), switched by
    ``cfg.matmul_impl``. Parameter layout is identical either way, so the
    switch never touches checkpoints or the HF loader."""
    if cfg.matmul_impl == "native":
        return nn.DenseGeneral(features, axis=axis, name=name, **kw)
    if cfg.matmul_impl not in ("int8", "int8_full"):
        raise ValueError(
            f"matmul_impl must be native/int8/int8_full, got "
            f"{cfg.matmul_impl!r}"
        )
    from pytorch_distributed_training_tpu.ops.quant import QuantDenseGeneral

    feats = features if isinstance(features, tuple) else (features,)
    ax = axis if isinstance(axis, tuple) else (axis,)
    return QuantDenseGeneral(
        features=feats, axis=ax,
        mode="full" if cfg.matmul_impl == "int8_full" else "fwd",
        delayed=cfg.quant_delayed,
        delayed_grads=cfg.quant_delayed_grads,
        dtype=kw["dtype"], param_dtype=kw["param_dtype"],
        kernel_init=kw["kernel_init"], name=name,
    )


class BertSelfAttention(nn.Module):
    config: ModelConfig

    @nn.compact
    def __call__(self, x, attention_bias, deterministic):
        cfg = self.config
        kw = dict(dtype=_dtype(cfg), param_dtype=_pdtype(cfg),
                  kernel_init=nn.initializers.normal(stddev=0.02))
        # Three separate projections, NOT a fused [h, 3h] qkv matmul: the
        # fused form measured ~2 ms/step SLOWER on v5e (XLA pipelines the
        # three column matmuls + their consumers better than one wide one
        # followed by slices; tried 2026-07, see NOTES.md).
        heads_shape = (cfg.num_heads, cfg.head_dim)
        q = dense_general(cfg, heads_shape, -1, "query", kw)(x)
        k = dense_general(cfg, heads_shape, -1, "key", kw)(x)
        v = dense_general(cfg, heads_shape, -1, "value", kw)(x)
        if cfg.decode:
            if cfg.kv_layout == "paged":
                out = self._paged_attend(q, k, v, attention_bias)
            else:
                out = self._cached_attend(q, k, v, attention_bias)
        else:
            dropout_rng = None
            if not deterministic and cfg.attention_dropout > 0.0:
                dropout_rng = self.make_rng("dropout")

            def core(q, k, v, bias, rng):
                return dot_product_attention(
                    q, k, v, bias,
                    impl=cfg.attention_impl,
                    dropout_rng=rng,
                    dropout_rate=cfg.attention_dropout,
                    deterministic=deterministic,
                    causal=cfg.causal,
                    dropout_impl=cfg.dropout_impl,
                )

            if cfg.attention_remat and cfg.attention_impl == "reference":
                # recompute scores/probs in the backward instead of storing
                # [B, N, S, S] probs residuals: the recompute is one small
                # einsum+softmax while the saved-probs path paid fp32
                # residual copies (measured +1.9 ms/step on bert-large;
                # bit-identical numerics — the dropout mask regenerates
                # from the same rng). Pallas flash / ring bring their own
                # backward structure, so only the XLA einsum impl opts in.
                core = jax.checkpoint(core)
            out = core(q, k, v, attention_bias, dropout_rng)
        return dense_general(cfg, cfg.hidden_size, (-2, -1), "out", kw)(out)

    def _cached_attend(self, q, k, v, attention_bias):
        """Autoregressive attention over the KV cache (generation path).

        Flax "cache" collection pattern: the cache buffers are created at
        their FULL [batch, max_len, heads, head_dim] size during ``init``
        (call the model once with a max_len-shaped dummy input), and every
        subsequent ``apply(..., mutable=["cache"])`` writes the current
        chunk at ``cache_index`` and attends causally over the filled
        prefix. Works for multi-token prefill chunks and 1-token decode
        steps alike. Deterministic (no dropout) — generation never trains.
        """
        cfg = self.config
        if not cfg.causal:
            raise ValueError("decode=True requires a causal model")
        batch, chunk, heads, head_dim = q.shape
        is_init = not self.has_variable("cache", "cached_key")
        ck = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros(k.shape, k.dtype),
        )
        cv = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros(v.shape, v.dtype),
        )
        ci = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if is_init:
            # init trace: buffers take the dummy input's (max_len) shape;
            # attend output only fixes parameter shapes, values unused
            return q
        idx = ci.value
        max_len = ck.value.shape[1]
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k.astype(ck.value.dtype), (0, idx, 0, 0)
        )
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v.astype(cv.value.dtype), (0, idx, 0, 0)
        )
        ci.value = idx + chunk
        scale = head_dim ** -0.5
        scores = jnp.einsum(
            "bsnd,btnd->bnst", q, ck.value,
            preferred_element_type=jnp.float32,
        ) * scale
        # causal-over-cache mask: key position t visible to chunk row i iff
        # t <= idx + i (rows are global positions idx..idx+chunk-1)
        q_pos = idx + jax.lax.broadcasted_iota(jnp.int32, (chunk, max_len), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, max_len), 1)
        neg = jnp.finfo(jnp.float32).min
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, neg)
        if attention_bias is not None:
            scores = scores + attention_bias.astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1).astype(cv.value.dtype)
        return jnp.einsum("bnst,btnd->bsnd", probs, cv.value)

    def _paged_attend(self, q, k, v, attention_bias):
        """Autoregressive attention over PAGED KV (cfg.kv_layout="paged").

        Same flax "cache" collection pattern as ``_cached_attend``, but the
        K/V buffers are page POOLS shared by every sequence in the batch:
        ``k_pages``/``v_pages`` [num_pages, page_size, heads * head_dim]
        (lane-dense: a token's heads folded into the minor axis, so the
        pool's default TPU layout is the row-major one the write and the
        gather run in — ops/paged_attention.py), addressed through a
        per-sequence ``block_table`` [batch, W] and ``context_len``
        [batch]. The serving engine owns page placement
        (serve/paged_cache.py) and injects block_table/context_len as traced
        operands per call; only the pools are engine-resident state.

        Contract with the engine:
        - prefill (chunk > 1, paged_multiquery=False): the sequence is
          FRESH (context_len == 0) and its block table row covers the
          chunk; K/V is scattered into its pages and attention is
          intra-chunk causal — bitwise the dense cache formula at idx == 0.
        - decode (chunk == 1): one token appended at ``context_len``, then
          ops/paged_attention gathers the whole context through the block
          table. Idle batch rows park on the reserved null page 0: their
          writes land there and their outputs are garbage the host ignores
          (no lax.select freeze needed — page structure isolates them).
        - multi-token query (paged_multiquery=True): the chunk is appended
          at ``context_len`` of an EXISTING context (speculative verify /
          chunked-prefill continuation) and attends causally over prior
          pages plus itself through the 4-D-query paged_attention path.
        """
        cfg = self.config
        if not cfg.causal:
            raise ValueError("decode=True requires a causal model")
        if cfg.kv_num_pages < 2:
            raise ValueError(
                "kv_layout='paged' needs kv_num_pages >= 2 (page 0 is the "
                f"reserved null page), got {cfg.kv_num_pages}"
            )
        batch, chunk, heads, head_dim = q.shape
        page_size = cfg.kv_page_size
        is_init = not self.has_variable("cache", "k_pages")
        # int8 pool storage: pages quantize on write (symmetric absmax over
        # head_dim) against fp32 scale pools [num_pages, page_size, heads]
        # that live beside the block tables in the same cache node, so the
        # engine's with_tables/strip_tables walk, donation and sharding all
        # carry them automatically. Reads dequantize in-kernel
        # (ops/paged_attention.py); the allocator never sees dtypes.
        quant_kv = cfg.kv_cache_dtype == "int8"
        pool_dtype = jnp.int8 if quant_kv else k.dtype
        pool_shape = (cfg.kv_num_pages, page_size, heads * head_dim)
        kp = self.variable(
            "cache", "k_pages", lambda: jnp.zeros(pool_shape, pool_dtype)
        )
        vp = self.variable(
            "cache", "v_pages", lambda: jnp.zeros(pool_shape, pool_dtype)
        )
        if quant_kv:
            ks = self.variable(
                "cache", "k_scales",
                lambda: jnp.zeros(
                    (cfg.kv_num_pages, page_size, heads), jnp.float32
                ),
            )
            vs = self.variable(
                "cache", "v_scales",
                lambda: jnp.zeros(
                    (cfg.kv_num_pages, page_size, heads), jnp.float32
                ),
            )
        # Placeholder shapes only: the engine always supplies real
        # block_table/context_len values per call (serve/paged_cache.py
        # with_tables); they are never engine-resident.
        bt = self.variable(
            "cache", "block_table",
            lambda: jnp.zeros((batch, 1), jnp.int32),
        )
        cl = self.variable(
            "cache", "context_len", lambda: jnp.zeros((batch,), jnp.int32)
        )
        if is_init:
            return q
        idx = cl.value  # [batch]
        # Scatter this chunk's K/V through the block table: token position
        # idx+j lives at page bt[b, (idx+j)//P], offset (idx+j)%P.
        pos = idx[:, None] + jax.lax.broadcasted_iota(
            jnp.int32, (batch, chunk), 1
        )
        page_ids = jnp.take_along_axis(bt.value, pos // page_size, axis=1)
        offs = pos % page_size
        if quant_kv:
            # quantize-on-write: the scale entries scatter through the SAME
            # (page, offset) indices as their values, so a token's int8
            # lanes and its fp32 scales can never drift apart
            k_new, ksc = quantize_kv(k)
            v_new, vsc = quantize_kv(v)
            ks.value = ks.value.at[page_ids, offs].set(ksc)
            vs.value = vs.value.at[page_ids, offs].set(vsc)
            pool_kw = dict(k_scales=ks.value, v_scales=vs.value)
        else:
            k_new = k.astype(kp.value.dtype)
            v_new = v.astype(vp.value.dtype)
            pool_kw = {}
        # ONE write for decode (chunk 1), prefill, chunked prefill and
        # verify alike: each token's heads fold into the pool's lane axis
        kp.value = kp.value.at[page_ids, offs].set(
            k_new.reshape(batch, chunk, heads * head_dim)
        )
        vp.value = vp.value.at[page_ids, offs].set(
            v_new.reshape(batch, chunk, heads * head_dim)
        )
        cl.value = idx + chunk
        scale = head_dim ** -0.5
        if chunk == 1:
            if attention_bias is not None:
                raise ValueError(
                    "paged decode steps take no attention bias (padding is "
                    "expressed through context_len)"
                )
            out = paged_attention(
                q[:, 0], kp.value, vp.value, bt.value, idx + 1,
                scale=scale, **pool_kw,
            )
            return out[:, None]
        if cfg.paged_multiquery:
            # Multi-token query over an existing context: the chunk's rows
            # sit at positions idx..idx+chunk-1 and see everything written
            # up to themselves (lengths inclusive of the chunk). Used by
            # the engine's speculative-verify and chunked-prefill programs.
            if attention_bias is not None:
                raise ValueError(
                    "paged multiquery attention takes no attention bias "
                    "(padding is expressed through context_len)"
                )
            return paged_attention(
                q, kp.value, vp.value, bt.value, idx + chunk,
                scale=scale, **pool_kw,
            )
        # Prefill: fresh sequence (idx == 0 by engine contract), so the
        # visible context IS this chunk — attend intra-chunk with the exact
        # dense-cache formula (fp32 scores, finfo.min mask, fp32 softmax)
        # so paged prefill stays bitwise against the dense path. Under int8
        # pools the fresh K/V stays in compute dtype here (only the STORED
        # pages quantize), so prefill logits — and the first sampled token —
        # are exact whatever the pool dtype.
        kc = k if quant_kv else k.astype(kp.value.dtype)
        vc = v if quant_kv else v.astype(vp.value.dtype)
        scores = jnp.einsum(
            "bsnd,btnd->bnst", q, kc, preferred_element_type=jnp.float32
        ) * scale
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        neg = jnp.finfo(jnp.float32).min
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, neg)
        if attention_bias is not None:
            scores = scores + attention_bias.astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1).astype(vc.dtype)
        return jnp.einsum("bnst,btnd->bsnd", probs, vc)


class BertLayer(nn.Module):
    """Post-LN transformer block (BERT convention)."""

    config: ModelConfig

    @nn.compact
    def __call__(self, x, attention_bias, deterministic):
        cfg = self.config
        kw = dict(dtype=_dtype(cfg), param_dtype=_pdtype(cfg),
                  kernel_init=nn.initializers.normal(stddev=0.02))
        def tail(name, site):
            # Dropout -> residual add -> LN as ONE fused op (Pallas kernel
            # on TPU with the keep-mask regenerated in-kernel; jax.random
            # dropout + reference LN elsewhere). site splits the PRNG
            # stream between the block's two tails.
            return FusedDropoutAddLayerNorm(
                epsilon=cfg.layer_norm_eps, rate=cfg.hidden_dropout,
                param_dtype=_pdtype(cfg), out_dtype=_dtype(cfg),
                impl=cfg.layernorm_impl, site=site,
                dropout_impl=cfg.dropout_impl, name=name,
            )

        attn_out = BertSelfAttention(cfg, name="attention")(
            x, attention_bias, deterministic
        )
        x = tail("attention_norm", 0)(attn_out, x, deterministic)

        h = dense_general(cfg, cfg.intermediate_size, -1, "mlp_up", kw)(x)
        h = nn.gelu(h, approximate=cfg.gelu_approximate)
        h = dense_general(cfg, cfg.hidden_size, -1, "mlp_down", kw)(h)
        return tail("mlp_norm", 1)(h, x, deterministic)


def default_position_ids(cfg: ModelConfig, input_ids):
    """Position ids per model family: RoBERTa counts non-pad tokens offset
    past the pad id; BERT uses plain arange. Shared by every trunk (single
    encoder AND the branch ensemble) so family semantics can't drift."""
    batch, seq = input_ids.shape
    # roberta positions run pad_token_id+1 .. seq+pad_token_id (HF offset)
    max_pos = seq + cfg.pad_token_id + 1 if cfg.roberta_style else seq
    if max_pos > cfg.max_position_embeddings:
        raise ValueError(
            f"sequence length {seq} needs position ids up to {max_pos - 1} "
            f"but max_position_embeddings is {cfg.max_position_embeddings}"
        )
    if cfg.roberta_style:
        mask = (input_ids != cfg.pad_token_id).astype(jnp.int32)
        return jnp.cumsum(mask, axis=-1) * mask + cfg.pad_token_id
    return jnp.broadcast_to(
        jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq)
    )


def remat_policy(cfg: ModelConfig):
    """Map ``cfg.remat_policy`` to a ``jax.checkpoint`` policy (None =
    save nothing = classic full remat). Shared by both model families."""
    name = getattr(cfg, "remat_policy", "nothing")
    if name == "nothing":
        return None
    import jax

    # name validity is enforced once, in ModelConfig.__post_init__ — a
    # KeyError here means a config bypassed the dataclass constructor
    return {
        "dots": jax.checkpoint_policies.dots_saveable,
        "weight_dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[name]


def _layer_cls(cfg: ModelConfig):
    """BertLayer, remat-wrapped when configured — the ONE place the
    nn.remat/static_argnums contract with BertLayer.__call__ is encoded."""
    if cfg.remat:
        return nn.remat(
            BertLayer, static_argnums=(3,), policy=remat_policy(cfg)
        )
    return BertLayer


def run_layers(cfg: ModelConfig, x, attention_bias, deterministic):
    """The python-loop trunk body (layer_0..layer_{N-1}), shared by
    BertEncoderModel's non-scan path and each ensemble branch. Must be called
    from inside an ``@nn.compact`` ``__call__`` (submodules register in the
    caller's scope, keeping the flat ``layer_i`` param names)."""
    for i in range(cfg.num_layers):
        x = _layer_cls(cfg)(cfg, name=f"layer_{i}")(
            x, attention_bias, deterministic
        )
    return x


def pool_cls(cfg: ModelConfig, x, deterministic):
    """CLS pooling head: [roberta pre-dropout →] dense('pooler') → tanh.

    RobertaClassificationHead applies dropout BEFORE its dense (dropout →
    dense → tanh → dropout → out_proj); BERT's pooler does not. Keeping the
    distinction here — shared by all classifiers — regularizes fine-tuning
    identically to the respective HF heads."""
    cls = x[:, 0]
    if cfg.roberta_style:
        cls = Dropout(cfg.hidden_dropout, cfg.dropout_impl)(
            cls, deterministic=deterministic
        )
    pooled = nn.Dense(
        cfg.hidden_size, dtype=x.dtype, param_dtype=_pdtype(cfg),
        kernel_init=nn.initializers.normal(stddev=0.02), name="pooler",
    )(cls)
    return jnp.tanh(pooled)


def classify(cfg: ModelConfig, pooled, deterministic):
    """dropout → fp32 dense('classifier') → logits, shared by all heads."""
    pooled = Dropout(cfg.hidden_dropout, cfg.dropout_impl)(
        pooled, deterministic=deterministic
    )
    return nn.Dense(
        cfg.num_labels, dtype=jnp.float32, param_dtype=_pdtype(cfg),
        kernel_init=nn.initializers.normal(stddev=0.02), name="classifier",
    )(pooled.astype(jnp.float32))


class _ScanBlock(nn.Module):
    """One layer in (carry, x) scan form for ``nn.scan`` stacking."""

    config: ModelConfig
    deterministic: bool

    @nn.compact
    def __call__(self, x, attention_bias):
        cfg = self.config
        x = _layer_cls(cfg)(cfg, name="layer")(
            x, attention_bias, self.deterministic
        )
        return x, None


class BertEncoderModel(nn.Module):
    """Embeddings + N layers + pooler → (sequence_output, pooled_output)."""

    config: ModelConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        position_ids=None,
        deterministic: bool = True,
    ):
        cfg = self.config
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        if position_ids is None:
            position_ids = default_position_ids(cfg, input_ids)

        x = BertEmbeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, position_ids, deterministic
        )
        bias = make_attention_bias(attention_mask)

        if cfg.scan_layers:
            # Layers stacked on a leading [num_layers] param dim and walked
            # with ONE traced body (lax.scan): near-constant compile time in
            # depth, and the layer dim becomes shardable — the mesh ``stage``
            # axis splits it into contiguous layer blocks per stage slice,
            # the GSPMD generalization of the reference ConcatBert's 2-stage
            # layer split (test_model_parallelism.py:40-89, where stage
            # transfer was a hand-written ``.to(second_device)`` at :62-63).
            scan = nn.scan(
                _ScanBlock,
                # "quant": per-layer delayed-int8 amaxes stack on the same
                # leading [num_layers] dim as the params (no-op otherwise)
                variable_axes={"params": 0, "quant": 0, "quant_sink": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,),
                length=cfg.num_layers,
            )
            x, _ = scan(cfg, deterministic, name="layers_scan")(x, bias)
        else:
            x = run_layers(cfg, x, bias, deterministic)

        return x, pool_cls(cfg, x, deterministic)


class BertForSequenceClassification(nn.Module):
    """Trunk + dropout + classifier head → logits [batch, num_labels].

    Loss lives in the train step (functional style), not the module — unlike
    the reference where CE loss is computed inside ``forward``
    (test_model_parallelism.py:153-156).
    """

    config: ModelConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        position_ids=None,
        deterministic: bool = True,
    ):
        cfg = self.config
        _, pooled = BertEncoderModel(cfg, name="bert")(
            input_ids, attention_mask, token_type_ids, position_ids,
            deterministic,
        )
        return classify(cfg, pooled, deterministic)
