"""GPT-2 causal language model (flax.linen), TPU-first.

The reference repo has no decoder models — this family exists for the
driver's extra config "GPT-2-medium causal-LM fine-tune, FSDP-style param
sharding" (/root/repo/BASELINE.json configs[4]). Architecture follows GPT-2:
pre-LN transformer blocks, learned absolute positions, tanh-approximate GELU,
final LayerNorm, and a weight-tied LM head (logits = h @ wte.T).

Reuses this framework's attention stack (``BertSelfAttention`` with
``config.causal=True`` → causal masking inside the swappable attention op)
and the same dtype policy (params fp32, compute bf16, LayerNorm/softmax
fp32). ``config.scan_layers`` stacks blocks on a leading [num_layers] dim
(lax.scan trunk) exactly like the encoder, so the stage/FSDP sharding rules
apply unchanged.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.models.bert import (
    BertSelfAttention,
    _dtype,
    _ln,
    _pdtype,
    dense_general,
)
from pytorch_distributed_training_tpu.ops.attention import make_attention_bias
from pytorch_distributed_training_tpu.ops.dropout import Dropout
from pytorch_distributed_training_tpu.utils.config import ModelConfig


def _mlp_body(mdl: "GPT2Block", h, deterministic):
    """The block's MLP tail (mlp_up → gelu → mlp_down → dropout) as a
    module-first function so ``remat_mlp`` can wrap it in a LIFTED
    ``nn.remat`` without changing parameter names/paths: children created
    here register in the block's own scope. Structural (plain
    jax.checkpoint, no saveable policies) — the TPU compiler crashed on
    checkpoint POLICIES at gpt2-medium scale in r3 (NOTES.md), while
    plain-remat subgraphs compile fine; rematerializing ONLY the MLP drops
    the [B,S,4·hidden] gelu residuals (the biggest per-layer activations)
    for one extra mlp_up matmul in the backward."""
    cfg = mdl.config
    kw = dict(dtype=_dtype(cfg), param_dtype=_pdtype(cfg),
              kernel_init=nn.initializers.normal(stddev=0.02))
    h = dense_general(cfg, cfg.intermediate_size, -1, "mlp_up", kw)(h)
    h = nn.gelu(h, approximate=True)  # GPT-2 uses the tanh approximation
    h = dense_general(cfg, cfg.hidden_size, -1, "mlp_down", kw)(h)
    return Dropout(cfg.hidden_dropout, cfg.dropout_impl)(
        h, deterministic=deterministic
    )


class GPT2Block(nn.Module):
    """Pre-LN transformer block (GPT-2 convention — LN before each sublayer,
    unlike BERT's post-LN ``BertLayer``)."""

    config: ModelConfig

    @nn.compact
    def __call__(self, x, attention_bias, deterministic):
        cfg = self.config
        h = _ln(cfg, "ln_1")(x)
        h = BertSelfAttention(cfg, name="attention")(
            h, attention_bias, deterministic
        )
        h = Dropout(cfg.hidden_dropout, cfg.dropout_impl)(h, deterministic=deterministic)
        x = x + h

        h = _ln(cfg, "ln_2")(x)
        mlp = (
            nn.remat(_mlp_body, static_argnums=(2,))
            if cfg.remat_mlp
            else _mlp_body
        )
        h = mlp(self, h, deterministic)
        return x + h


def _gpt2_layer_cls(cfg: ModelConfig):
    """GPT2Block, remat-wrapped when configured — same nn.remat/static_argnums
    contract as bert._layer_cls (GPT2Block.__call__ shares BertLayer's
    signature, with ``deterministic`` at position 3)."""
    if cfg.remat:
        from pytorch_distributed_training_tpu.models.bert import remat_policy

        return nn.remat(
            GPT2Block, static_argnums=(3,), policy=remat_policy(cfg)
        )
    return GPT2Block


class _GPT2ScanBlock(nn.Module):
    config: ModelConfig
    deterministic: bool

    @nn.compact
    def __call__(self, x, attention_bias):
        x = _gpt2_layer_cls(self.config)(self.config, name="block")(
            x, attention_bias, self.deterministic
        )
        return x, None


class GPT2LMModel(nn.Module):
    """wte+wpe embeddings → N pre-LN blocks → ln_f → tied-head logits.

    Signature matches the encoder classifiers (token_type_ids accepted and
    ignored) so train/eval steps and the Trainer drive either family
    unchanged.
    """

    config: ModelConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,  # unused; uniform model signature
        position_ids=None,
        deterministic: bool = True,
    ):
        cfg = self.config
        batch, seq = input_ids.shape
        if seq > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings} — the position-embedding "
                f"gather would silently clamp (NaN/garbage logits); raise "
                f"max_position_embeddings for long-context runs"
            )
        if position_ids is None:
            if cfg.decode:
                # generation: positions continue from the cached index
                # (same flax "cache" pattern as the attention KV buffers)
                is_init = not self.has_variable("cache", "pos_index")
                pi = self.variable(
                    "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
                )
                offset = jnp.zeros((), jnp.int32) if is_init else pi.value
                if not is_init:
                    pi.value = offset + seq
                position_ids = offset + jnp.broadcast_to(
                    jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq)
                )
            else:
                position_ids = jnp.broadcast_to(
                    jnp.arange(seq, dtype=jnp.int32)[None, :], (batch, seq)
                )
        embed_init = nn.initializers.normal(stddev=0.02)
        wte = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=embed_init,
            dtype=_dtype(cfg), param_dtype=_pdtype(cfg), name="wte",
        )
        wpe = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size,
            embedding_init=embed_init, dtype=_dtype(cfg),
            param_dtype=_pdtype(cfg), name="wpe",
        )
        x = wte(input_ids) + wpe(position_ids)
        x = Dropout(cfg.hidden_dropout, cfg.dropout_impl)(x, deterministic=deterministic)

        # padding bias (causal masking is applied inside attention via
        # cfg.causal; GPT-2 training batches are usually dense so
        # attention_mask may be None)
        bias = make_attention_bias(attention_mask)

        if cfg.scan_layers:
            scan = nn.scan(
                _GPT2ScanBlock,
                # "quant": per-layer delayed-int8 amaxes (ops/quant.py)
                variable_axes={"params": 0, "quant": 0, "quant_sink": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,),
                length=cfg.num_layers,
            )
            x, _ = scan(cfg, deterministic, name="layers_scan")(x, bias)
        else:
            for i in range(cfg.num_layers):
                x = _gpt2_layer_cls(cfg)(cfg, name=f"block_{i}")(
                    x, bias, deterministic
                )

        x = _ln(cfg, "ln_f")(x)
        # Tied LM head: logits share the input embedding matrix (GPT-2
        # convention). bf16 operands with fp32 MXU accumulation — the same
        # policy as every other matmul; a full-fp32 vocab matmul runs at
        # half MXU rate and the [B,S,V] logits dominate the LM step.
        logits = jax.lax.dot_general(
            x,
            wte.embedding.astype(_dtype(cfg)),
            (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return logits
