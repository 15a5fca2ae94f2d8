"""Continuous-batching inference subsystem (the north star's request path).

Training (train/) and one-shot batch generation (models/generate.py) leave
the repo with no way to SERVE a model; this package is that missing half:

- ``engine``  — slotted paged-KV decode: K/V page pools addressed through
                per-slot block tables (``paged_cache``), one jitted
                prefill per prompt-length bucket, one jitted decode step
                advancing every active slot per tick with the next token
                sampled in-trace (``sampling``), admit/evict between
                ticks (continuous batching, Orca-style);
- ``queue``   — bounded admission queue: ``BackpressureError`` at max
                depth, per-request deadlines, FIFO-within-bucket
                scheduling, weighted SLO tier lanes (interactive/batch)
                and the ``BrownoutController`` overload ladder (shed
                batch -> clamp output budgets -> fail-fast interactive,
                every step reversible);
- ``server``  — the serve-loop thread plus stdin/JSONL and localhost HTTP
                front-ends that stream tokens back per request; /healthz
                reports ready/draining/unhealthy with live load for
                routers and external LBs;
- ``router``  — health-checked request router over N replicas: circuit
                breakers with half-open probes, telemetry-driven
                least-loaded balancing, bounded retries for not-yet-
                streamed requests, optional tail-latency hedging,
                fail-fast 503 + Retry-After when the pool is down;
- ``fleet``   — replica-pool supervision: serve_lm subprocesses under the
                supervisor restart contract (crash -> backoff respawn
                within a budget; SIGTERM -> drain, exit 75, respawn free),
                plus the rolling-swap coordinator driving one-replica-at-
                a-time checkpoint rollouts, and a dynamic pool
                (``scale_up`` / ``retire_replica``) the autoscaler turns;
- ``autoscale`` — queue-driven pool sizing with hysteresis + cooldowns
                (grows via the spawn machinery, shrinks via the graceful
                SIGTERM/exit-75 drain — no in-flight request dies);
- ``trace``   — seeded open-loop traffic traces (Poisson base + burst
                episodes, heavy-tailed sizes, SLO tiers, optional
                multi-tenant shared-system-prompt mix) and their replay
                driver (tests/test_storm.py);
- ``prefix_cache`` — shared-KV prefix cache: a token-keyed trie over
                finished prompts' fully-written page runs; a matching
                request maps the shared pages into its block table
                (refcounted, copy-on-write at the divergence point) and
                prefills only the tail — cached streams stay bit-identical
                to cold prefill, and a weight hot-swap flushes the index;
- ``hotswap`` — zero-downtime checkpoint hot-swap: a manifest-verified
                watcher admits newly published steps (never twice, never
                backwards, poisoned steps blocklisted), the replica-side
                manager loads and swaps them live through the engine's
                between-tick trial/commit/rollback protocol, and
                ``publish_params_checkpoint`` is the publisher half of the
                contract.

Observability and failure handling ride the existing subsystems:
per-request TTFT/TPOT/queue-wait records and queue-depth/slot-occupancy
gauges go through ``telemetry/`` (``scripts/summarize_metrics.py``
renders the serving percentile table), prefill/decode dispatch is armed
under the ``faults/`` watchdog, and ``PDT_TPU_FAULT=slow_host:<f>x``
stretches tick time deterministically to drill deadline/backpressure
paths. ``benchmarks/run.py`` is the load generator (chip only).
"""

from pytorch_distributed_training_tpu.utils.lazy import lazy_exports

# resolved on first use: the fleet coordinator imports ``serve.fleet`` /
# ``serve.router`` and stays jax-free (utils/lazy.py)
_LAZY = {
    "AutoscaleConfig": "autoscale",
    "Autoscaler": "autoscale",
    "DecodeEngine": "engine",
    "EngineConfig": "engine",
    "BackpressureError": "queue",
    "BrownoutController": "queue",
    "GenRequest": "queue",
    "RequestQueue": "queue",
    "PrefixCache": "prefix_cache",
    "PrefixMatch": "prefix_cache",
    "FleetConfig": "fleet",
    "RollingSwapCoordinator": "fleet",
    "ServeFleet": "fleet",
    "TraceConfig": "trace",
    "TraceEvent": "trace",
    "generate_trace": "trace",
    "replay": "trace",
    "CheckpointWatcher": "hotswap",
    "HotSwapManager": "hotswap",
    "publish_params_checkpoint": "hotswap",
    "CircuitBreaker": "router",
    "Router": "router",
    "RouterConfig": "router",
    "make_router_http_server": "router",
    "InferenceServer": "server",
    "make_http_server": "server",
    "serve_stdio": "server",
}

__all__ = sorted(_LAZY)
__getattr__ = lazy_exports(__name__, _LAZY)
