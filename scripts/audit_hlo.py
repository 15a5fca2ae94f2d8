"""Audit the collective footprint (and fusions) of a compiled train step.

Subsumes the old ``dump_hlo.py``: compiles the production train step (or
reads an existing HLO dump with ``--hlo-file``), writes the full text to
``--out``, and reports every collective the SPMD partitioner inserted —
kind, payload/moved bytes, group sizes, ICI vs DCN split — through
``analysis/spmd/hlo.py``'s extractor and cost model.

Usage:
  python scripts/audit_hlo.py [micro] [--model NAME] [--seq N]
      [--global-batch N]      # compile the production step (build_step)
  python scripts/audit_hlo.py --hlo-file /tmp/step_hlo.txt
      [--world-size N]        # audit an existing dump, jax-free
  --json                      # machine-readable summary on stdout
  --check                     # exit 1 unless the footprint conforms to
                              # the mesh-derived train manifest
  --expect KINDS              # comma-separated allowed kinds overriding
                              # the mesh-derived manifest (e.g.
                              # --expect all-gather,reduce-scatter)
  --max-bytes N               # payload-bytes ceiling for --check
  --fusions                   # also print one representative instruction
                              # per named-fusion family (dump_hlo's job)
  --serve-tp N                # compile the tensor-parallel serve programs
                              # (paged decode + spec verify, tiny LM) over
                              # an N-way model-axis mesh and audit each
                              # against serve_tp_manifest; same --json /
                              # --check contract as the train-step audit
  --int8                      # with --serve-tp: build the int8 variant
                              # (weight-only int8 matmuls + int8 KV pages)
                              # so the audit checks the sharded quantized
                              # programs against the dtype-aware manifest
                              # (weight-bytes floor priced at 1 B/elem)
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pytorch_distributed_training_tpu.analysis.spmd.hlo import (  # noqa: E402
    COLLECTIVE_KINDS,
    extract_collectives,
    summarize_collectives,
)
from pytorch_distributed_training_tpu.analysis.spmd.manifest import (  # noqa: E402
    CommManifest,
    train_manifest,
)


GLOBAL, SEQ = 96, 128


def build_step(micro, model_name="bert-large-cased", seq=None, global_batch=None):
    """(jitted train step, sharded state, one global batch) of the
    production recipe on the current mesh; ATTN / MATMUL / QUANT_DELAYED
    in the environment pick the variants."""
    import os as _os

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.comms.mesh import build_mesh
    from pytorch_distributed_training_tpu.models import (
        BertForSequenceClassification,
    )
    from pytorch_distributed_training_tpu.parallel import (
        ShardingPolicy,
        state_shardings,
    )
    from pytorch_distributed_training_tpu.parallel.sharding import shard_state
    from pytorch_distributed_training_tpu.train.optim import (
        adamw_with_schedule,
    )
    from pytorch_distributed_training_tpu.train.state import create_train_state
    from pytorch_distributed_training_tpu.train.step import make_train_step
    from pytorch_distributed_training_tpu.utils.config import (
        TrainConfig,
        model_preset,
    )

    _attn = {"attention_impl": _os.environ["ATTN"]} if _os.environ.get("ATTN") else {}
    if _os.environ.get("MATMUL"):
        _attn["matmul_impl"] = _os.environ["MATMUL"]
    if _os.environ.get("QUANT_DELAYED") == "1":
        # the shipping bench config: delayed int8 activation scaling
        if not str(_attn.get("matmul_impl", "")).startswith("int8"):
            # same contract as train_dp's CLI guard: a silently-bf16 trace
            # labeled "delayed int8" is worse than an error
            raise SystemExit("QUANT_DELAYED=1 requires MATMUL=int8|int8_full")
        _attn["quant_delayed"] = True
    global_batch = global_batch or GLOBAL
    seq = seq or SEQ
    mesh = build_mesh()
    from pytorch_distributed_training_tpu.ops.dispatch import set_kernel_mesh

    set_kernel_mesh(mesh)  # multi-chip: keep the Pallas kernel path active
    mcfg = model_preset(model_name, dropout_impl="kernel", **_attn)
    if mcfg.causal:
        from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel

        model = GPT2LMModel(mcfg)
        objective = "causal_lm"
    else:
        model = BertForSequenceClassification(mcfg)
        objective = "classification"
    tcfg = TrainConfig(
        global_batch_size=global_batch, micro_batch_size=micro,
        max_seq_length=seq,
        grad_accum_dtype="bfloat16", adam_mu_dtype="bfloat16",
        adam_nu_dtype="bfloat16",
    )
    tx, _ = adamw_with_schedule(tcfg, total_steps=1000)
    example = {
        "input_ids": jnp.ones((2, seq), jnp.int32),
        "attention_mask": jnp.ones((2, seq), jnp.int32),
        "token_type_ids": jnp.zeros((2, seq), jnp.int32),
    }
    state = create_train_state(model, tx, jax.random.key(42, impl="rbg"), example)
    shardings = state_shardings(state, ShardingPolicy(), mesh)
    state = shard_state(state, shardings)
    step = make_train_step(
        grad_accum_steps=tcfg.grad_accum_steps, mesh=mesh,
        state_shardings=shardings, objective=objective,
        accum_dtype=tcfg.grad_accum_dtype,
    )
    import numpy as np
    from pytorch_distributed_training_tpu.comms.ingest import make_global_batch
    from pytorch_distributed_training_tpu.comms.mesh import TRAIN_BATCH_PSPEC

    rng = np.random.default_rng(0)
    accum = tcfg.grad_accum_steps
    b = {
        "input_ids": rng.integers(
            0, mcfg.vocab_size, (accum, micro, seq)
        ).astype(np.int32),
        "attention_mask": np.ones((accum, micro, seq), np.int32),
        "token_type_ids": np.zeros((accum, micro, seq), np.int32),
        "labels": rng.integers(0, 2, (accum, micro)).astype(np.int32),
    }
    batch = make_global_batch(mesh, b, pspec=TRAIN_BATCH_PSPEC)
    from pytorch_distributed_training_tpu.train.step import calibrate_quant

    # no-op unless the config carries delayed-quant state
    state = calibrate_quant(state, jax.tree.map(lambda x: x[0], batch))
    return step, state, batch


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("micro", nargs="?", type=int, default=32)
    p.add_argument("--model", default="bert-large-cased")
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--global-batch", type=int, default=None)
    p.add_argument("--hlo-file", default=None,
                   help="audit this HLO text instead of compiling")
    p.add_argument("--out", default="/tmp/step_hlo.txt",
                   help="where the full HLO text is written when compiling")
    p.add_argument("--world-size", type=int, default=None,
                   help="device count for iota replica groups "
                        "(default: jax.device_count() when compiling)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--check", action="store_true")
    p.add_argument("--expect", default=None,
                   help="comma-separated allowed collective kinds")
    p.add_argument("--max-bytes", type=int, default=None)
    p.add_argument("--fusions", action="store_true")
    p.add_argument("--serve-tp", type=int, default=None,
                   help="audit the tensor-parallel serve programs over an "
                        "N-way model-axis mesh instead of the train step")
    p.add_argument("--int8", action="store_true",
                   help="with --serve-tp: audit the int8 serve variant "
                        "(weight-only int8 + int8 KV pages)")
    return p.parse_args(argv)


def _fusion_families(txt):
    """One representative instruction per named-fusion family."""
    fams = {}
    for m in re.finditer(
        r"^\s*%?((?:[a-z_]+)fusion)\.(\d+)\s.*?(?=^\s*%|\Z)",
        txt,
        re.M | re.S,
    ):
        fams.setdefault(m.group(1), m.group(0)[:1500])
    return fams


def _serve_tp_audit(args):
    """Compile-and-audit the sharded serve programs standalone.

    Builds the tiny-LM paged serve engine twice (spec off -> hot program
    is ``serve_decode``; spec on -> ``serve_verify``) at ``--serve-tp N``
    with warmup on, which compiles each hot program under the tensor-
    parallel mesh and runs the production compile-time comm audit against
    ``serve_tp_manifest``. The audit records ARE the report — the same
    code path a serving replica runs, not a re-implementation."""
    tp = args.serve_tp
    # the audit must REPORT deviations (and let --check set the exit
    # code), not die on the strict guard's first violation
    os.environ["PDT_TPU_GUARDS"] = "record"
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={tp}"
            ).strip()

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.serve import (
        EngineConfig,
        InferenceServer,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )
    from pytorch_distributed_training_tpu.utils.config import model_preset

    if jax.device_count() < tp:
        raise SystemExit(
            f"--serve-tp {tp} needs {tp} devices, have "
            f"{jax.device_count()} (on CPU set JAX_PLATFORMS=cpu so the "
            f"script can force virtual devices)"
        )

    class _Sink:
        def __init__(self):
            self.records = []

        def emit(self, record):
            self.records.append(record)

    mcfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(mcfg)
    params = model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32)
    )["params"]

    dtype_kw = (
        {"weights_dtype": "int8", "kv_dtype": "int8"} if args.int8 else {}
    )
    audits = []
    for spec_k in (0, 3):
        registry = MetricsRegistry()
        sink = _Sink()
        registry.attach_sink(sink)
        # construction alone compiles + audits: warmup=True runs every
        # bucket and the hot decode/verify program before any request
        InferenceServer(
            model, params,
            EngineConfig(
                num_slots=2, prompt_buckets=(8,), max_new_tokens=8,
                kv_layout="paged", sampling="device", page_size=4,
                spec_k=spec_k, warmup=True, tp=tp, **dtype_kw,
            ),
            queue_depth=2, registry=registry,
        )
        audits += [
            r for r in sink.records if r.get("record") == "comm_audit"
        ]

    ok = bool(audits) and all(a["ok"] for a in audits)
    if args.json:
        print(json.dumps({"serve_tp": tp, "int8": bool(args.int8),
                          "ok": ok, "audits": audits},
                         indent=2, default=str))
    else:
        for a in audits:
            print(f"{a['name']}: "
                  f"{sum(s['count'] for s in a['by_kind'].values())} "
                  f"collectives ({a['total_bytes']} payload B, "
                  f"{a['total_moved_bytes']} moved B)")
            for kind, slot in sorted(a["by_kind"].items()):
                print(f"  {kind:20s} x{slot['count']:<4d} "
                      f"{slot['bytes']:>12d} B payload  "
                      f"{slot['moved_bytes']:>12d} B moved")
            verdict = "CONFORMS" if a["ok"] else "DEVIATES"
            print(f"manifest {a['manifest']!r}: {verdict}")
            for d in a.get("deviations", ()):
                print(f"  - {d}")
        if not audits:
            print("no comm_audit records emitted (unexpected)")
    if args.check and not ok:
        return 1
    return 0


def main(argv=None):
    args = _parse_args(argv)
    if args.serve_tp:
        return _serve_tp_audit(args)
    manifest = None
    if args.hlo_file:
        with open(args.hlo_file) as f:
            txt = f.read()
        world_size = args.world_size
    else:
        import jax

        step, state, batch = build_step(
            args.micro, model_name=args.model,
            seq=args.seq, global_batch=args.global_batch,
        )
        txt = step.lower(state, batch).compile().as_text()
        with open(args.out, "w") as f:
            f.write(txt)
        print(f"HLO written: {args.out} ({len(txt)} bytes)", file=sys.stderr)
        world_size = args.world_size or jax.device_count()
        from pytorch_distributed_training_tpu.comms.mesh import build_mesh

        manifest = train_manifest(build_mesh(), max_bytes=args.max_bytes)
    if args.expect is not None:
        allowed = tuple(k for k in args.expect.split(",") if k)
        for k in allowed:
            if k not in COLLECTIVE_KINDS:
                raise SystemExit(
                    f"--expect: unknown kind {k!r} "
                    f"(must be among {COLLECTIVE_KINDS})"
                )
        manifest = CommManifest(
            "cli-expect", allowed=allowed, max_bytes=args.max_bytes
        )

    collectives = extract_collectives(txt, world_size=world_size)
    summary = summarize_collectives(collectives)
    deviations = manifest.check(summary) if manifest is not None else []

    if args.json:
        print(json.dumps({
            "summary": summary,
            "manifest": manifest.to_record() if manifest else None,
            "deviations": deviations,
            "collectives": [
                {"name": c.name, "kind": c.kind, "dtype": c.dtype,
                 "bytes": c.bytes, "group_size": c.group_size,
                 "line": c.line, "asynchronous": c.asynchronous}
                for c in collectives
            ],
        }, indent=2))
    else:
        print(f"collectives: {summary['count']} "
              f"({summary['total_bytes']} payload B, "
              f"{summary['total_moved_bytes']} moved B, "
              f"~{summary['est_time_s'] * 1e3:.3f} ms)")
        for kind, slot in sorted(summary["by_kind"].items()):
            print(f"  {kind:20s} x{slot['count']:<4d} "
                  f"{slot['bytes']:>12d} B payload  "
                  f"{slot['moved_bytes']:>12d} B moved")
        if manifest is not None:
            verdict = "CONFORMS" if not deviations else "DEVIATES"
            print(f"manifest {manifest.name!r}: {verdict}")
            for d in deviations:
                print(f"  - {d}")
    if args.fusions:
        for fam, body in _fusion_families(txt).items():
            print(f"\n===== {fam} =====\n{body}\n")
    if args.check and deviations:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
