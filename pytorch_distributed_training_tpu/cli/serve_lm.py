"""Serving entry point: continuous-batching LM inference (serve/).

Turns a trained causal-LM checkpoint into a request server:

    # stdin/JSONL mode (default): one request per line, token events out
    echo '{"prompt": "The quick brown", "max_new_tokens": 16}' | \
    python -m pytorch_distributed_training_tpu.cli.serve_lm \
        --model gpt2-medium --checkpoint-dir /ckpts/run1 \
        --vocab encoder.json --merges merges.txt --num-slots 8

    # localhost HTTP mode: POST /generate streams JSONL token events;
    # GET /healthz, GET /stats
    python -m pytorch_distributed_training_tpu.cli.serve_lm \
        --http-port 8000 --num-slots 8 --metrics-dir /tmp/serve_metrics

Engine shape knobs: ``--num-slots`` fixed decode slots (the continuous
batch), ``--prompt-buckets`` comma-separated prefill lengths (one
compiled prefill per bucket; prompts pad up to the smallest fitting
bucket), ``--max-new-tokens-cap`` bounds the KV cache (largest bucket +
cap). Admission knobs: ``--queue-depth`` (beyond it, submissions are
REJECTED with a backpressure error — JSONL ``error`` event / HTTP 429 —
never queued unboundedly), ``--deadline-s`` default per-request deadline
(queued requests past it expire without burning prefill).

``--metrics-dir`` streams per-request ``serve_request`` records (TTFT,
TPOT, queue wait) through telemetry/; fold them into a percentile table
with ``scripts/summarize_metrics.py``.

Latent expert presets (``--model glm-5.2-share16``: latent attention with
a learned sparse indexer, routed experts of which this chip holds a share;
``--model dots3-note-share8``: full layers that each choose with their own
indexer beside window layers of another latent width, headwise attention
gates; ``models/latent_moe.py``) serve through the same server, tick,
allocator, prefix cache and sampling. They need, at the published widths,
``--weights-dtype bfloat16``; ``--prefill-chunk`` and ``--prefix-cache``
work over their pools. They refuse, at start-up and by the flag's name:
``--tp``, ``--spec-k``, ``--weights-dtype int8`` and ``--kv-dtype int8``.
Size ``--num-pages`` yourself where prompts share prefixes (the default
reserves every slot a whole context).

State-space hybrid presets (``--model phi-4-mini-flash``: Mamba layers,
window attention, one full-attention layer whose K/V page pool seven cross
layers read, Gated Memory Units; ``models/sambay.py``) serve through the
same server, tick, allocator and sampling, with a recurrent state and a
window ring a slot beside the one page pool. ``--weights-dtype bfloat16``
at the published widths; ``--prefill-chunk`` works (a prompt's upper half
runs for its last token alone). They refuse, by the flag's name: ``--tp``,
``--spec-k``, ``--prefix-cache``, ``--weights-dtype int8``, ``--kv-dtype
int8``.

Live reload: with ``--checkpoint-dir`` the server exposes ``POST /swap``
(swap to a named step) and ``--hotswap-poll-s N`` additionally watches the
directory, hot-swapping each newly published manifest-verified step into
the running engine between ticks — no restart, in-flight requests keep
streaming, and a corrupt publish rolls back to the serving weights
(serve/hotswap.py).
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    from pytorch_distributed_training_tpu.cli.generate_lm import add_model_args

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_model_args(p)
    p.add_argument("--num-slots", type=int, default=4,
                   help="fixed decode slots (concurrent in-flight requests)")
    p.add_argument("--prompt-buckets", default="16,32,64,128",
                   help="comma-separated prompt-length buckets; one compiled "
                        "prefill program per bucket")
    p.add_argument("--max-new-tokens-cap", type=int, default=64,
                   help="per-request max_new_tokens ceiling; KV cache length "
                        "= largest bucket + this cap")
    p.add_argument("--queue-depth", type=int, default=16,
                   help="admission-queue depth; submissions beyond it are "
                        "rejected with a backpressure error")
    p.add_argument("--kv-layout", default="paged", choices=("paged",),
                   help="accepted for existing command lines; no other "
                        "value exists")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page; 128 matches the TPU lane "
                        "width for real deployments")
    p.add_argument("--num-pages", type=int, default=0,
                   help="total KV pages incl. the reserved null page "
                        "(0 = auto-size so every slot fits a worst-case "
                        "request; set lower to trade admission concurrency "
                        "for KV memory — page exhaustion backpressures)")
    p.add_argument("--sampling", default="device", choices=("device",),
                   help="accepted for existing command lines; no other "
                        "value exists")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: draft tokens proposed per "
                        "slot per tick (0 = off); each verify dispatch "
                        "scores k+1 positions and commits every accepted "
                        "one — same token stream, fewer dispatches")
    p.add_argument("--draft-checkpoint", default=None,
                   help="trainer-format checkpoint dir for a small DRAFT "
                        "model that proposes the speculative tokens; "
                        "without it --spec-k falls back to the built-in "
                        "n-gram (prompt-lookup) drafter")
    p.add_argument("--draft-model", default="gpt2-tiny",
                   help="model preset for --draft-checkpoint (the draft's "
                        "vocab must match the base model's)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked prefill: stream prompts into the paged KV "
                        "cache this many tokens per tick through one "
                        "compiled program (0 = one jitted prefill per "
                        "bucket); long prompts stop monopolising the tick "
                        "loop and new buckets stop triggering compiles")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor parallelism: shard THIS replica's engine "
                        "over N devices (attention heads + MLP hidden on "
                        "a model-axis mesh, paged KV pools split by "
                        "heads; streams stay bit-identical to tp=1). "
                        "Requires a model whose "
                        "num_heads/intermediate_size divide by N")
    p.add_argument("--weights-dtype", default="float32",
                   choices=("float32", "bfloat16", "int8"),
                   help="serving weight precision: float32 leaves the "
                        "tree as the preset or checkpoint gives it; "
                        "bfloat16 keeps every floating leaf resident in "
                        "bfloat16 (a latent expert preset is then created "
                        "in it: at 9.4 GB it has no room for a float32 "
                        "tree); int8 quantizes every "
                        "attention/MLP matmul weight at load (per-channel "
                        "scales, dequantized in-trace — activations and "
                        "logits stay fp32) at ~0.5x resident weight bytes")
    p.add_argument("--kv-dtype", default="float32",
                   choices=("float32", "int8"),
                   help="paged KV cache precision: int8 pools + fp32 "
                        "per-page-per-head scales beside the block tables "
                        "(~0.3x KV bytes/token at head_dim 16; allocator "
                        "and admission arithmetic unchanged)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="shared-KV prefix cache: finished prompts' pages "
                        "are indexed in a token-keyed trie and a matching "
                        "prompt prefix is served from the cache (refcounted "
                        "pages, copy-on-write at the divergence point) — "
                        "only the tail is prefilled, streams bit-identical "
                        "to cold prefill; a weight hot-swap flushes the "
                        "index")
    p.add_argument("--tenant-page-quota", type=float, default=0.0,
                   help="per-tenant PRIVATE-page ceiling as a fraction of "
                        "the page pool (0 = unlimited): requests carrying "
                        "a tenant are held at admission once their "
                        "tenant's non-shared footprint would exceed it — "
                        "shared prefix pages stay free, so no tenant can "
                        "monopolize the pool. Requires --prefix-cache")
    p.add_argument("--warmup", action="store_true",
                   help="compile every prefill bucket + the decode step "
                        "before serving (first request pays no compile; "
                        "also arms strict tick-wide transfer scoping from "
                        "the first tick)")
    p.add_argument("--lock-summary-s", type=float, default=0.0,
                   help="emit the lock_summary telemetry record every this "
                        "many seconds DURING the run (0 = shutdown-only; a "
                        "wedged process never reaches shutdown, so set this "
                        "on long-lived replicas)")
    p.add_argument("--deadline-s", type=float, default=0.0,
                   help="default per-request deadline (0 = none); queued "
                        "requests past it expire unserved")
    p.add_argument("--interactive-deadline-s", type=float, default=0.0,
                   help="SLO deadline for tier=interactive requests "
                        "(0 = fall back to --deadline-s)")
    p.add_argument("--batch-deadline-s", type=float, default=0.0,
                   help="SLO deadline for tier=batch requests "
                        "(0 = fall back to --deadline-s)")
    p.add_argument("--brownout-high", type=float, default=0.0,
                   help="enable the brownout ladder: escalate one level "
                        "(shed batch -> clamp max_new -> fail-fast "
                        "interactive) when queue pressure stays above this "
                        "fraction of capacity (0 = brownout off)")
    p.add_argument("--brownout-low", type=float, default=0.3,
                   help="de-escalate one level when pressure stays below "
                        "this fraction (hysteresis band with "
                        "--brownout-high)")
    p.add_argument("--brownout-clamp", type=int, default=16,
                   help="max_new_tokens cap applied at brownout level 2+")
    p.add_argument("--brownout-escalate-hold-s", type=float, default=0.5,
                   help="pressure must stay above --brownout-high this long "
                        "before each escalation")
    p.add_argument("--brownout-deescalate-hold-s", type=float, default=1.0,
                   help="pressure must stay below --brownout-low this long "
                        "before each recovery step")
    p.add_argument("--http-port", type=int, default=0,
                   help="serve HTTP on 127.0.0.1:<port> (0 = stdin/JSONL "
                        "mode)")
    p.add_argument("--http-host", default="127.0.0.1",
                   help="HTTP bind host (fleet replicas stay on localhost)")
    p.add_argument("--drain-timeout-s", type=float, default=10.0,
                   help="SIGTERM grace window: stop admitting, finish "
                        "in-flight requests up to this many seconds, then "
                        "exit 75 (resumable — a supervisor respawns without "
                        "counting a crash)")
    p.add_argument("--stall-timeout-s", type=float, default=10.0,
                   help="/healthz reports 'unhealthy' when the serve loop's "
                        "tick heartbeat is older than this (wedged loop "
                        "detection for routers/LBs)")
    p.add_argument("--hotswap-poll-s", type=float, default=0.0,
                   help="poll --checkpoint-dir every this many seconds and "
                        "hot-swap newly published, manifest-verified steps "
                        "into the running engine with no restart (0 = no "
                        "polling; POST /swap still works when a checkpoint "
                        "dir is given — the fleet coordinator drives it)")
    p.add_argument("--hotswap-verify", default="digest",
                   choices=("size", "digest"),
                   help="integrity level a step must pass before a live "
                        "swap admits it (digest re-hashes every file — the "
                        "safe default for weights about to serve traffic)")
    p.add_argument("--metrics-dir", default=None,
                   help="stream serve telemetry (JSONL) under this directory")
    p.add_argument("--flight-capacity", type=int, default=256,
                   help="engine flight-recorder ring size: last N tick "
                        "summaries dumped as a flight_dump record on "
                        "watchdog stall, fatal tick, SIGTERM drain and "
                        "GET /debug/flight")
    p.add_argument("--slo-windows", default="300,3600",
                   help="comma-separated burn-rate window lengths in "
                        "seconds (telemetry/slo.py slo_burn records)")
    p.add_argument("--slo-emit-s", type=float, default=5.0,
                   help="min seconds between slo_burn records")
    p.add_argument("--slo-burn-high", type=float, default=0.0,
                   help="brownout coupling: burn rate at/above this reads "
                        "as high-watermark pressure on the overload ladder "
                        "(0 = off, the default — queue pressure stays the "
                        "sole brownout signal)")
    p.add_argument("--replica-name", default=None,
                   help="replica identity stamped on spans/flight records "
                        "(fleet mode passes replica-<i>)")
    p.add_argument("--guards", default=None,
                   choices=("off", "record", "strict"),
                   help="runtime correctness guards (analysis/guards.py) "
                        "AND lock-discipline mode (analysis/concurrency): "
                        "strict (default) fails the serve loop on "
                        "recompile/implicit-transfer/lock-order "
                        "violations; pass --guards record to only emit "
                        "telemetry (the rollout opt-out), off to disable; "
                        "PDT_TPU_GUARDS overrides the default")
    return p


def _check_model_flags(args) -> None:
    """Exit at start-up, naming the flag, where the model's family lacks
    a serving path the flags ask for (the engine checks the same again)."""
    from pytorch_distributed_training_tpu.utils.config import model_preset

    try:
        check = getattr(model_preset(args.model), "check_serving", None)
        if check is not None:
            check(args)
    except (KeyError, ValueError) as e:
        raise SystemExit(f"serve_lm: {e.args[0]}")


def main(argv=None, in_stream=None, out_stream=None) -> dict:
    """Run the server until EOF (stdio mode) or interrupt (HTTP mode);
    returns the engine's final stats dict (machine-checkable in tests).
    Raises ``SystemExit`` (exit code 1) when the serve loop died."""
    args = build_parser().parse_args(argv)
    _check_model_flags(args)

    import jax

    from pytorch_distributed_training_tpu.cli.generate_lm import (
        build_tokenizer,
        load_model_and_params,
    )
    from pytorch_distributed_training_tpu.serve import (
        EngineConfig,
        InferenceServer,
        make_http_server,
        serve_stdio,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        get_registry,
    )
    from pytorch_distributed_training_tpu.telemetry.spans import setup_phase
    from pytorch_distributed_training_tpu.train.compile import (
        enable_compile_cache,
    )
    from pytorch_distributed_training_tpu.utils.logging import log0

    # entry to ready: everything up to the started server is set-up
    with setup_phase("serve_setup"):
        # first: the random init / checkpoint restore below already compiles
        log0(f"compile cache: {enable_compile_cache()}")
        # which chips this process holds (a fleet replica: the one it was given)
        log0(f"devices: {[(d.platform, d.device_kind, d.id) for d in jax.devices()]}")
        registry = get_registry()
        sink = None
        if args.metrics_dir:
            from pytorch_distributed_training_tpu.telemetry.sink import JsonlSink

            # before the load, so that its span is written too
            sink = JsonlSink(args.metrics_dir)
            registry.attach_sink(sink)
        tok = build_tokenizer(args)
        with setup_phase("serve_setup.load"):
            model, params, boot_step = load_model_and_params(args, tok)

        draft_model = draft_params = None
        spec_draft = "ngram"
        if args.spec_k > 0 and args.draft_checkpoint:
            # the draft lane reuses the full checkpoint-loading machinery on a
            # cloned namespace: verified-step resolution, scanned-trunk probes
            # and vocab checks all apply to the draft exactly as to the base
            draft_args = argparse.Namespace(**{
                **vars(args),
                "model": args.draft_model,
                "checkpoint_dir": args.draft_checkpoint,
                "hf_checkpoint": None,
            })
            draft_model, draft_params, _ = load_model_and_params(draft_args, tok)
            spec_draft = "model"

        if sink is not None:
            sink.emit({
                "record": "serve_meta",
                "model": args.model,
                "num_slots": args.num_slots,
                "prompt_buckets": args.prompt_buckets,
                "max_new_tokens_cap": args.max_new_tokens_cap,
                "queue_depth": args.queue_depth,
                "kv_layout": args.kv_layout,
                "page_size": args.page_size,
                "num_pages": args.num_pages,
                "sampling": args.sampling,
                "spec_k": args.spec_k,
                "spec_draft": spec_draft if args.spec_k > 0 else None,
                "prefill_chunk": args.prefill_chunk,
                "tp": args.tp,
                "weights_dtype": args.weights_dtype,
                "kv_dtype": args.kv_dtype,
                "prefix_cache": args.prefix_cache,
                "tenant_page_quota": args.tenant_page_quota,
            })

        config = EngineConfig(
            num_slots=args.num_slots,
            prompt_buckets=tuple(
                int(b) for b in args.prompt_buckets.split(",") if b.strip()
            ),
            max_new_tokens=args.max_new_tokens_cap,
            kv_layout=args.kv_layout,
            page_size=args.page_size,
            num_pages=args.num_pages,
            sampling=args.sampling,
            warmup=args.warmup,
            spec_k=args.spec_k,
            spec_draft=spec_draft,
            prefill_chunk=args.prefill_chunk,
            tp=args.tp,
            weights_dtype=args.weights_dtype,
            kv_dtype=args.kv_dtype,
            prefix_cache=args.prefix_cache,
            tenant_page_quota=args.tenant_page_quota,
            flight_capacity=args.flight_capacity,
        )
        from pytorch_distributed_training_tpu.analysis.concurrency import (
            get_lock_registry,
        )
        from pytorch_distributed_training_tpu.analysis.guards import (
            GuardSet,
            guard_mode_from_env,
        )

        # the serve CLI runs strict by default (PR 11): violations fail the
        # loop instead of just logging; --guards record is the opt-out. Lock
        # discipline follows the same mode — set before any server/engine
        # lock is created so off-mode skips instrumentation entirely.
        guard_mode = args.guards or guard_mode_from_env(default="strict")
        get_lock_registry().mode = guard_mode

        # per-tier burn-rate monitor: always on (one throttled slo_burn record
        # per emit interval); the brownout coupling below stays opt-in
        from pytorch_distributed_training_tpu.telemetry.slo import (
            BurnRateMonitor,
            SloConfig,
        )

        slo = BurnRateMonitor(
            SloConfig(
                windows_s=tuple(
                    float(w) for w in args.slo_windows.split(",") if w.strip()
                ),
                emit_interval_s=args.slo_emit_s,
            ),
            registry=registry,
        )

        brownout = None
        if args.brownout_high > 0:
            from pytorch_distributed_training_tpu.serve.queue import (
                BrownoutController,
            )

            brownout = BrownoutController(
                high_watermark=args.brownout_high,
                low_watermark=args.brownout_low,
                escalate_hold_s=args.brownout_escalate_hold_s,
                deescalate_hold_s=args.brownout_deescalate_hold_s,
                clamp_max_new=args.brownout_clamp,
                registry=registry,
                slo_monitor=slo if args.slo_burn_high > 0 else None,
                slo_burn_high=args.slo_burn_high,
            )
        tier_deadlines = {}
        if args.interactive_deadline_s > 0:
            tier_deadlines["interactive"] = args.interactive_deadline_s
        if args.batch_deadline_s > 0:
            tier_deadlines["batch"] = args.batch_deadline_s

        server = InferenceServer(
            model, params, config,
            queue_depth=args.queue_depth,
            default_deadline_s=args.deadline_s or None,
            tier_deadlines=tier_deadlines or None,
            brownout=brownout,
            registry=registry,
            guards=GuardSet(mode=guard_mode, registry=registry),
            stall_timeout_s=args.stall_timeout_s,
            weights_step=boot_step,
            draft_model=draft_model,
            draft_params=draft_params,
            slo=slo,
            replica_name=args.replica_name,
        ).start()
        # the engine placed its own copy; under --tp the tree that was
        # initialized or restored whole on device 0 would otherwise stay
        # resident there for the life of the process
        del params, draft_params

    lock_summary = None
    if args.lock_summary_s > 0:
        # in-run lock_summary cadence: a wedged replica still leaves its
        # contention/hold stats in the metrics stream (shutdown-only
        # emission below never fires for it)
        from pytorch_distributed_training_tpu.analysis.concurrency import (
            start_periodic_summary,
        )

        lock_summary = start_periodic_summary(
            args.lock_summary_s, registry=registry
        )

    if args.checkpoint_dir and not args.hf_checkpoint:
        # live reload: a continuously fine-tuning job publishes into the
        # same --checkpoint-dir and this replica picks verified steps up
        # with no restart (standalone mode polls; fleet mode drives the
        # POST /swap endpoint instead and leaves polling off)
        from pytorch_distributed_training_tpu.serve.hotswap import (
            HotSwapManager,
        )

        server.attach_hotswap(
            HotSwapManager(
                server, args.checkpoint_dir,
                poll_interval_s=args.hotswap_poll_s,
                verify_level=args.hotswap_verify,
                registry=registry,
                start_step=boot_step,
            ).start()
        )

    preempted = {"signal": None}
    try:
        if args.http_port:
            import signal as _signal
            import threading
            import time as _time

            try:
                httpd = make_http_server(
                    server, tok, host=args.http_host, port=args.http_port
                )
            except OSError as e:
                import errno

                if e.errno != errno.EADDRINUSE:
                    raise
                # the supervisor's free-port probe is TOCTOU by nature;
                # losing the bind race is not a crash. Exit 76 so the
                # fleet retries this replica on a fresh port without
                # burning a restart from its budget.
                from pytorch_distributed_training_tpu.serve.fleet import (
                    PORT_IN_USE_EXIT_CODE,
                )

                log0(
                    f"port {args.http_port} already in use; exiting "
                    f"{PORT_IN_USE_EXIT_CODE} for a fresh-port respawn"
                )
                server.close(drain=False)
                sys.exit(PORT_IN_USE_EXIT_CODE)
            log0(
                f"serving on http://{args.http_host}:"
                f"{httpd.server_address[1]} "
                f"(POST /generate, GET /healthz, GET /stats)"
            )

            # SIGTERM = preemption: the handler only flags (async-signal-
            # safe); the drain thread does the work while the MAIN thread
            # keeps accepting connections — /healthz must answer
            # "draining" (503) for the whole drain window so routers pull
            # this replica from rotation BEFORE the process dies.
            drain_requested = threading.Event()

            def _drain() -> None:
                drain_requested.wait()
                t0 = _time.monotonic()
                log0(
                    f"SIGTERM: draining (finish in-flight, admit nothing, "
                    f"deadline {args.drain_timeout_s:.1f}s)"
                )
                server.close(drain=True, timeout=args.drain_timeout_s)
                # black-box dump: what the engine was doing when the
                # preemption landed (the drain itself is the epilogue)
                server.engine.flight.dump("sigterm_drain")
                # let in-flight HTTP streams flush their final events
                deadline = _time.monotonic() + 2.0
                while (
                    httpd.active_streams and _time.monotonic() < deadline
                ):
                    _time.sleep(0.01)
                registry.emit({
                    "record": "preemption",
                    "scope": "serve",
                    "drain_s": _time.monotonic() - t0,
                })
                httpd.shutdown()

            drainer = threading.Thread(
                target=_drain, name="serve-drain", daemon=True
            )
            drainer.start()

            def _on_term(signum, frame):
                preempted["signal"] = signum
                drain_requested.set()

            _signal.signal(_signal.SIGTERM, _on_term)

            try:
                httpd.serve_forever()
            except KeyboardInterrupt:  # pragma: no cover - interactive stop
                pass
            finally:
                drain_requested.set()
                httpd.shutdown()
        else:
            served = serve_stdio(
                server, tok,
                in_stream if in_stream is not None else sys.stdin,
                out_stream if out_stream is not None else sys.stdout,
            )
            log0(f"stdio stream closed after {served} requests")
        from pytorch_distributed_training_tpu.ops import dispatch

        log0(dispatch.summary())
    finally:
        if lock_summary is not None:
            lock_summary.stop()
        server.close(drain=True)
        stats = server.stats()
        if sink is not None:
            sink.emit({"record": "serve_summary", **stats})
            # per-lock contention/hold/wait accounting for the whole run
            # (analysis/concurrency) — summarize_metrics' "locks" section
            from pytorch_distributed_training_tpu.analysis.concurrency import (
                get_lock_registry,
            )

            sink.emit(get_lock_registry().summary_record())
            sink.flush(fsync=True)
    if server.loop_dead():
        # every waiter already got its error event; the PROCESS must fail
        # too, or a stdio client's exit code (and a supervisor's restart
        # budget) reads a dead server as a clean run
        raise SystemExit("serve loop died (traceback above); exiting 1")
    if preempted["signal"] is not None:
        # graceful preemption drain: exit 75 (EX_TEMPFAIL) so a fleet
        # supervisor respawns this replica without burning a restart
        from pytorch_distributed_training_tpu.faults.preemption import (
            Preempted,
        )

        raise Preempted(preempted["signal"])
    return stats


if __name__ == "__main__":
    main()
