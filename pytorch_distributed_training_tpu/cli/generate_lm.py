"""Text-generation entry point for the causal-LM family (KV-cache decode).

The reference repo has no inference side at all; this completes the GPT-2
family (models/gpt2.py + models/generate.py) with a CLI:

    python -m pytorch_distributed_training_tpu.cli.generate_lm \
        --model gpt2-medium --checkpoint-dir /ckpts/run1 \
        --vocab encoder.json --merges merges.txt \
        --prompt "The quick brown" --max-new-tokens 32 --temperature 0.8

Batch mode: ``--prompt-file prompts.txt`` reads one prompt per line,
generates the whole file as ONE ragged right-padded batch (per-row
prompt lengths and position offsets — models/generate.py), and prints
every row's continuation.

Weights come from a framework checkpoint (``--checkpoint-dir``, the trainer's
save format), an HF GPT-2 checkpoint directory (``--hf-checkpoint``), or
random init (demo mode — still useful for smoke-testing the decode path).
Tokenization uses the in-repo byte-level BPE when ``--vocab``/``--merges``
are given, else the lossless raw-byte fallback (data/bpe.py).

The model/tokenizer loading helpers (``build_tokenizer``,
``load_model_and_params``) are shared with the serving CLI
(cli/serve_lm.py) so both entry points resolve checkpoints identically.
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

# jax is imported where it is used: fleet_lm shares add_model_args and must
# stay jax-free (a chip belongs to one process — its replicas)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_model_args(p)
    p.add_argument("--prompt", default="The quick brown fox")
    p.add_argument("--prompt-file", default=None,
                   help="one prompt per line; generates the whole file as a "
                        "single ragged batch and prints every row")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 = sampling")
    p.add_argument("--top-k", type=int, default=40)
    p.add_argument("--stop-at-eot", action=argparse.BooleanOptionalAction,
                   default=True)
    return p


def add_model_args(p: argparse.ArgumentParser) -> None:
    """Model/checkpoint/tokenizer flags shared by generate_lm and serve_lm."""
    p.add_argument("--model", default="gpt2-medium")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None,
                   help="framework checkpoint directory (trainer format)")
    p.add_argument("--hf-checkpoint", default=None,
                   help="HF GPT-2 checkpoint directory (torch weights)")
    p.add_argument("--vocab", default=None, help="encoder.json path")
    p.add_argument("--merges", default=None, help="merges.txt path")


def build_tokenizer(args):
    from pytorch_distributed_training_tpu.data.bpe import (
        ByteLevelBPETokenizer,
        ByteTokenizer,
    )
    from pytorch_distributed_training_tpu.utils.logging import log0

    if args.vocab and args.merges:
        return ByteLevelBPETokenizer(args.vocab, args.merges)
    log0("no --vocab/--merges: using raw-byte fallback tokenizer")
    return ByteTokenizer()


def load_model_and_params(args, tok):
    """Resolve ``(model, params, ckpt_step)`` from the CLI's checkpoint
    flags (``ckpt_step`` is None for HF/random weights — serving reports
    it as the boot ``weights_step``).

    Matches the checkpoint's trunk layout: train_lm defaults to the scanned
    trunk, and generate()/DecodeEngine re-lay scanned params out — the user
    never has to know how the checkpoint was trained. The step is resolved
    ONCE so the layout probe and the restore read the same checkpoint even
    if a training run is writing new steps concurrently — and it prefers
    the newest VERIFIED step (manifest integrity, train/manifest.py) so an
    inference process never boots on a torn publish; a manifest-less
    legacy directory falls back to the raw latest step.
    """
    import jax

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.utils.config import model_preset
    from pytorch_distributed_training_tpu.utils.logging import log0

    scanned = False
    ckpt_step = None
    ckpt = None
    if args.checkpoint_dir and not args.hf_checkpoint:
        from pytorch_distributed_training_tpu.train import checkpoint as ckpt

        ckpt_step = ckpt.verified_latest_step(args.checkpoint_dir)
        if ckpt_step is None:
            ckpt_step = ckpt.latest_step(args.checkpoint_dir)
            if ckpt_step is not None:
                log0(
                    f"no integrity-verified checkpoint under "
                    f"{args.checkpoint_dir} (legacy save?); loading latest "
                    f"step {ckpt_step} unverified"
                )
        if ckpt_step is None:
            raise SystemExit(f"no checkpoint under {args.checkpoint_dir}")
        scanned = ckpt.saved_params_scanned(args.checkpoint_dir, step=ckpt_step)
    mcfg = model_preset(args.model, scan_layers=scanned)
    if not mcfg.causal:
        raise SystemExit(f"--model {args.model} is not a causal preset")
    if tok.vocab_size > mcfg.vocab_size:
        raise SystemExit(
            f"tokenizer vocab {tok.vocab_size} exceeds model vocab "
            f"{mcfg.vocab_size}"
        )
    from pytorch_distributed_training_tpu.models import latent_moe, sambay

    own_family = {latent_moe.LatentMoEConfig: latent_moe.LatentMoELM,
                  sambay.SambaYConfig: sambay.SambaYLM}.get(type(mcfg))
    if own_family is not None:
        if getattr(args, "weights_dtype", None) == "bfloat16":
            # created in the resident type: a model sized to the chip has
            # no room for a float32 tree beside its bfloat16 one
            mcfg = dataclasses.replace(mcfg, param_dtype="bfloat16")
        model = own_family(mcfg)
    else:
        model = GPT2LMModel(mcfg)

    if args.hf_checkpoint:
        from pytorch_distributed_training_tpu.models.hf_loader import (
            load_gpt2_lm,
        )

        params = load_gpt2_lm(args.hf_checkpoint, mcfg)
    elif args.checkpoint_dir:
        abstract = jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), np.ones((1, 8), np.int32)
            )
        )["params"]
        params = ckpt.restore_params(
            args.checkpoint_dir, params_like=abstract, step=ckpt_step
        )
    else:
        log0("no checkpoint given: generating from RANDOM weights (demo)")
        init = lambda key: model.init(  # noqa: E731
            key, np.ones((1, 8), np.int32))["params"]
        if own_family is not None:
            # one compiled program: op by op, init would run the expert
            # layers' loops (or a scan over tokens) and compile every
            # distinct shape on its own
            init = jax.jit(init)
        params = init(jax.random.key(args.seed))
    return model, params, ckpt_step


def _trim_eot(ids: np.ndarray, tok, stop_at_eot: bool) -> np.ndarray:
    if stop_at_eot and getattr(tok, "eot_id", None) is not None:
        stops = np.where(ids == tok.eot_id)[0]
        if len(stops):
            return ids[: stops[0]]
    return ids


def main(argv=None):
    """Generate and print continuations. Returns the continuation text —
    a str for ``--prompt``, a list[str] (one per line) for
    ``--prompt-file``."""
    args = build_parser().parse_args(argv)

    import jax

    from pytorch_distributed_training_tpu.models.generate import generate
    from pytorch_distributed_training_tpu.train.compile import (
        enable_compile_cache,
    )

    enable_compile_cache()
    tok = build_tokenizer(args)
    if args.prompt_file:
        with open(args.prompt_file) as f:
            prompts = [line.rstrip("\n") for line in f if line.strip()]
        if not prompts:
            raise SystemExit(f"no prompts in {args.prompt_file}")
    else:
        prompts = [args.prompt]

    rows = [tok.text_ids(p) for p in prompts]
    if any(len(r) == 0 for r in rows):
        raise SystemExit("empty prompt after tokenization")
    lengths = np.asarray([len(r) for r in rows], np.int32)
    width = int(lengths.max())
    prompt_ids = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        prompt_ids[i, : len(r)] = r

    model, params, _step = load_model_and_params(args, tok)

    out = generate(
        model,
        params,
        prompt_ids,
        max_new_tokens=args.max_new_tokens,
        prompt_lengths=lengths,
        temperature=args.temperature,
        top_k=args.top_k,
        rng=jax.random.key(args.seed),
        eot_id=getattr(tok, "eot_id", None) if args.stop_at_eot else None,
    )
    out = np.asarray(out)
    texts = []
    for i, prompt in enumerate(prompts):
        ids = _trim_eot(out[i, width:], tok, args.stop_at_eot)
        text = tok.decode(ids)
        texts.append(text)
        print(prompt + text)
    return texts if args.prompt_file else texts[0]


if __name__ == "__main__":
    main()
