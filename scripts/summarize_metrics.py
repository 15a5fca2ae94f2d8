"""Fold a telemetry JSONL stream into a per-epoch (or serving) table.

Reads the stream written by ``--metrics-dir`` (telemetry/sink.py) and prints
one row per epoch: throughput (samples/sec/chip), where the step time went
(data-wait %), and which host was slowest — the questions every perf PR has
so far answered by hand-assembling HISTORY_* artifacts.

Serving streams (cli/serve_lm.py ``--metrics-dir``) get their own table:
when ``serve_request`` records are present the summary carries a ``serve``
section — per-bucket rows with request counts and p50/p95/p99 over TTFT
(submit -> first token), TPOT (per-token decode latency) and total request
latency, plus aggregate tokens/sec, queue-wait percentiles and
expired/cancelled counts. Hot-swap streams (serve/hotswap.py) add a
``swap`` section: admissions, ok/failed swaps, rollbacks, blocklisted
steps, rollout convergence percentiles and the version-skew duration
(from the router's ``router_skew`` spans).

Traced streams (telemetry/spans.py) add a ``spans`` section — per-tier
per-phase (queue/prefill/decode) p50/p95 plus the structural counts that
gate the bench (orphan spans, incomplete traces) — an ``slo`` burn-rate
table from the latest ``slo_burn`` record, and a flight-recorder dump
inventory (``flight_dump`` records by reason).

    python scripts/summarize_metrics.py /path/to/metrics_dir
    python scripts/summarize_metrics.py /path/to/metrics.jsonl --json

``--json`` dumps the summary dict instead of the table (for scripts).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# the spans/slo sections lean on telemetry/spans.py for the structural
# verdicts; running as `python scripts/summarize_metrics.py` puts scripts/
# first on sys.path, so anchor the repo root explicitly
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_records(path: str) -> list[dict]:
    """Parse a metrics JSONL file (or a directory holding metrics.jsonl);
    skips unparseable lines (a torn final line from a crashed run) rather
    than failing the whole summary."""
    if os.path.isdir(path):
        path = os.path.join(path, "metrics.jsonl")
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"warning: skipping unparseable line: {line[:80]}",
                      file=sys.stderr)
    return records


def summarize(records: list[dict]) -> dict:
    """Fold the stream into {run, epochs: [per-epoch rows], compile}."""
    meta = next((r for r in records if r.get("record") == "run_meta"), {})
    steps_by_epoch: dict[int, list[dict]] = {}
    for r in records:
        if r.get("record") == "step":
            steps_by_epoch.setdefault(int(r.get("epoch", 0)), []).append(r)
    saves = [r for r in records if r.get("record") == "checkpoint_save"]
    restarts = [r for r in records if r.get("record") == "restart"]
    compiles = [r for r in records if r.get("record") == "compile"]
    guards = summarize_guards(records)

    epochs = []
    for r in records:
        if r.get("record") != "epoch":
            continue
        epoch = int(r.get("epoch", len(epochs)))
        steps = steps_by_epoch.get(epoch, [])
        total_step = sum(s.get("step_s", 0.0) for s in steps)
        total_wait = sum(s.get("data_wait_s", 0.0) for s in steps)
        straggler = r.get("straggler") or {}
        # prefetch pipeline health: occupancy histogram + stall counter out
        # of the epoch's telemetry window (present when --prefetch-depth>0)
        tel = r.get("telemetry") or {}
        occ = (tel.get("timers") or {}).get("data/prefetch_occupancy") or {}
        stalls = (tel.get("counters") or {}).get("data/prefetch_stalls")
        row = {
            "epoch": epoch,
            "steps": len(steps),
            "train_loss": r.get("train_loss"),
            "samples_per_sec_per_chip": r.get("samples_per_sec_per_chip"),
            "data_wait_pct": 100.0 * total_wait / total_step
            if total_step
            else None,
            "prefetch_occupancy_mean": occ.get("mean_s"),
            "prefetch_stalls": stalls,
            "slowest_host": straggler.get("slowest_host"),
            "wait_skew_s": straggler.get("wait_skew_s"),
            "accuracy": r.get("accuracy"),
            "eval_loss": r.get("eval_loss"),
        }
        epochs.append(row)
    compile_summary = None
    if compiles:
        last = compiles[-1]
        compile_summary = {
            "count": len(compiles),
            "total_s": sum(c.get("compile_s", 0.0) for c in compiles),
            "train_compile_s": last.get("train_compile_s"),
            "eval_compile_s": last.get("eval_compile_s"),
            "cache_hit": last.get("cache_hit"),
            "cache_dir": last.get("cache_dir"),
        }
    return {
        "run": {
            "mesh_shape": meta.get("mesh_shape"),
            "chip_count": meta.get("chip_count"),
            "jax_version": meta.get("jax_version"),
        },
        "epochs": epochs,
        "compile": compile_summary,
        "checkpoint_saves": len(saves),
        "restarts": len(restarts),
        "serve": summarize_serve(records),
        "fleet": summarize_fleet(records),
        "storm": summarize_storm(records),
        "swap": summarize_swap(records),
        "guards": guards,
        "locks": summarize_locks(records),
        "comm": summarize_comm(records),
        "spans": summarize_spans(records),
        "slo": summarize_slo(records),
        "flight": summarize_flight(records),
    }


def summarize_spans(records: list[dict]) -> dict | None:
    """Fold ``span`` records (telemetry/spans.py) into the tracing view:
    per-tier per-phase latency percentiles over the replica phase spans,
    plus the structural verdicts the bench gates on — orphan span count,
    incomplete trace count and phase-sum reconciliation failures. None
    when the stream holds no span records."""
    from pytorch_distributed_training_tpu.telemetry.spans import (
        REQUEST_PHASES,
        trace_coverage,
    )

    spans = [r for r in records if r.get("record") == "span"]
    if not spans:
        return None
    # tier rides the serve root's attrs; phase spans inherit it through
    # their trace (one serve span per replica attempt)
    tier_by_trace: dict[str, str] = {}
    for s in spans:
        if s.get("name") == "serve":
            tier = (s.get("attrs") or {}).get("tier")
            if tier:
                tier_by_trace.setdefault(str(s.get("trace")), str(tier))
    phases: dict[str, dict[str, list]] = {}
    for s in spans:
        if s.get("name") not in REQUEST_PHASES:
            continue
        tier = tier_by_trace.get(str(s.get("trace")), "?")
        phases.setdefault(tier, {p: [] for p in REQUEST_PHASES})
        phases[tier][s["name"]].append(s.get("dur_s"))
    coverage = trace_coverage(records)
    return {
        "spans": len(spans),
        "traces": coverage["traces"],
        "complete_traces": coverage["complete"],
        "incomplete_traces": len(coverage["incomplete"]),
        "orphan_spans": coverage["orphan_spans"],
        "phase_sum_bad": len(coverage["phase_sum_bad"]),
        "coverage": coverage["coverage"],
        "tiers": {
            tier: {
                phase: _pcts(vals)
                for phase, vals in phases[tier].items()
            }
            for tier in sorted(phases)
        },
        "components": sorted({
            s.get("component") or "?" for s in spans
        }),
        "hedges": sum(1 for s in spans if s.get("name") == "hedge"),
        "attempts": sum(1 for s in spans if s.get("name") == "attempt"),
    }


def summarize_slo(records: list[dict]) -> dict | None:
    """The latest ``slo_burn`` record per stream (the monitor emits
    cumulative window views, so the newest one IS the summary), reshaped
    into a per-tier per-window burn table. None when the stream holds no
    burn records."""
    burns = [r for r in records if r.get("record") == "slo_burn"]
    if not burns:
        return None
    last = burns[-1]
    tiers = {}
    for tier, windows in (last.get("tiers") or {}).items():
        tiers[tier] = {
            label: {
                "requests": w.get("requests"),
                "deadline_met": w.get("deadline_met"),
                "availability": w.get("availability"),
                "deadline_burn": w.get("deadline_burn"),
                "availability_burn": w.get("availability_burn"),
            }
            for label, w in windows.items()
        }
    return {
        "emissions": len(burns),
        "windows_s": last.get("windows_s"),
        "deadline_objective": last.get("deadline_objective"),
        "availability_objective": last.get("availability_objective"),
        "max_burn": last.get("max_burn"),
        "peak_burn": max(
            (r.get("max_burn") or 0.0 for r in burns), default=0.0
        ),
        "tiers": tiers,
    }


def summarize_flight(records: list[dict]) -> dict | None:
    """Inventory of flight-recorder dumps (telemetry/flight.py): how many
    rings were dumped, for which reasons, and the last tick each dump
    captured (the stalled tick when the reason is a watchdog). None when
    the stream holds no dumps."""
    dumps = [r for r in records if r.get("record") == "flight_dump"]
    if not dumps:
        return None
    by_reason: dict[str, int] = {}
    for r in dumps:
        reason = r.get("reason") or "?"
        by_reason[reason] = by_reason.get(reason, 0) + 1
    detail = []
    for r in dumps:
        entries = r.get("entries") or []
        detail.append({
            "component": r.get("component"),
            "reason": r.get("reason"),
            "depth": r.get("depth"),
            "dropped": r.get("dropped"),
            "last_tick": entries[-1].get("tick") if entries else None,
        })
    return {
        "dumps": len(dumps),
        "by_reason": by_reason,
        "detail": detail,
    }


def summarize_guards(records: list[dict]) -> dict | None:
    """Fold guard-layer records (analysis/guards.py) + the last
    ``lint_summary`` into one violations block; None when the stream holds
    no guard-layer records at all (guards off / pre-guard stream)."""
    recompiles = [r for r in records if r.get("record") == "recompile"]
    transfers = [
        r for r in records if r.get("record") == "implicit_transfer"
    ]
    donations = [r for r in records if r.get("record") == "donation_audit"]
    shardings = [r for r in records if r.get("record") == "sharding_audit"]
    lints = [r for r in records if r.get("record") == "lint_summary"]
    if not (recompiles or transfers or donations or shardings or lints):
        return None
    out: dict = {
        "recompiles": len(recompiles),
        "recompiled_fns": sorted({r.get("name") for r in recompiles}),
        "implicit_transfers": len(transfers),
        "donation_audits_failed": sum(
            1 for r in donations if r.get("ok") is False
        ),
        "sharding_audits_failed": sum(
            1 for r in shardings if r.get("ok") is False
        ),
    }
    if lints:
        last = lints[-1]
        out["lint"] = {
            "findings": last.get("findings"),
            "waived": last.get("waived"),
            "clean": last.get("clean"),
        }
    return out


def summarize_comm(records: list[dict]) -> dict | None:
    """Fold ``comm_audit`` records (analysis/spmd/manifest.py) into the
    collective-footprint view: one row per audited program (last audit
    per program wins — audits re-run on hot-swap/recompile) with
    collective counts by kind, payload/moved bytes and manifest verdict.
    None when the stream holds no comm records."""
    audits = [r for r in records if r.get("record") == "comm_audit"]
    if not audits:
        return None
    by_name: dict[str, dict] = {}
    for r in audits:
        by_name[r.get("name") or "?"] = r
    programs = {}
    for name in sorted(by_name):
        r = by_name[name]
        programs[name] = {
            "manifest": r.get("manifest"),
            "ok": r.get("ok"),
            "collectives": r.get("count"),
            "by_kind": {
                k: v.get("count") for k, v in (r.get("by_kind") or {}).items()
            },
            "total_bytes": r.get("total_bytes"),
            "total_moved_bytes": r.get("total_moved_bytes"),
            "est_time_s": r.get("est_time_s"),
            "deviations": r.get("deviations") or [],
            "error": r.get("error"),
        }
    return {
        "audits": len(audits),
        "programs": programs,
        "deviations": sum(len(p["deviations"]) for p in programs.values()),
        "clean": all(p["ok"] is not False for p in programs.values()),
    }


def summarize_locks(records: list[dict]) -> dict | None:
    """Fold the runtime lock registry's telemetry
    (``analysis/concurrency``) into the contention view: per-lock
    acquires/contention/hold stats aggregated across processes (each
    ``lock_summary`` is cumulative per pid — last record per pid wins,
    then pids sum), plus every ``lock_order_violation`` /
    ``lock_across_device`` event. None when the stream holds no lock
    records."""
    summaries = [r for r in records if r.get("record") == "lock_summary"]
    violations = [
        r for r in records if r.get("record") == "lock_order_violation"
    ]
    device_holds = [
        r for r in records if r.get("record") == "lock_across_device"
    ]
    if not (summaries or violations or device_holds):
        return None

    by_pid: dict = {}
    for r in summaries:     # cumulative per process: keep the newest
        by_pid[r.get("pid", 0)] = r
    locks: dict[str, dict] = {}
    for rec in by_pid.values():
        for name, s in (rec.get("locks") or {}).items():
            row = locks.setdefault(name, {
                "acquires": 0, "contentions": 0,
                "wait_total_s": 0.0, "wait_max_s": 0.0, "wait_p99_s": None,
                "hold_total_s": 0.0, "hold_max_s": 0.0, "hold_p99_s": None,
            })
            row["acquires"] += s.get("acquires", 0)
            row["contentions"] += s.get("contentions", 0)
            row["wait_total_s"] += s.get("wait_total_s", 0.0)
            row["wait_max_s"] = max(
                row["wait_max_s"], s.get("wait_max_s", 0.0)
            )
            row["hold_total_s"] += s.get("hold_total_s", 0.0)
            row["hold_max_s"] = max(
                row["hold_max_s"], s.get("hold_max_s", 0.0)
            )
            for key in ("wait_p99_s", "hold_p99_s"):
                v = s.get(key)
                if v is not None:
                    row[key] = max(row[key] or 0.0, v)
    return {
        "processes": len(by_pid),
        "locks": locks,
        "order_violations": len(violations),
        "order_violation_detail": [
            {"acquiring": r.get("acquiring"), "holding": r.get("holding"),
             "inverts": r.get("inverts")}
            for r in violations
        ],
        "device_boundary_holds": len(device_holds),
    }


def _pcts(values: list) -> dict | None:
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    import math

    vals = sorted(vals)

    def pct(p: float) -> float:
        # nearest-rank on the sorted sample — honest for the small request
        # counts a test/bench stream holds
        return vals[min(len(vals) - 1, math.ceil(p / 100 * len(vals)) - 1)]

    return {
        "count": len(vals),
        "mean": sum(vals) / len(vals),
        "p50": pct(50),
        "p95": pct(95),
        "p99": pct(99),
    }


def summarize_paged(records: list[dict]) -> dict | None:
    """Fold the engine's paged-KV accounting (the final ``serve_summary``
    stats plus the per-tick ``serve/kv_pages_used`` gauge) into the
    page-pool view: layout/sampling mode, peak page occupancy and
    page-exhaustion admission rejections. None when the stream predates
    the paged cache (or the engine ran dense without a summary)."""
    summaries = [r for r in records if r.get("record") == "serve_summary"]
    if not summaries:
        return None
    last = summaries[-1]
    if "kv_layout" not in last:
        return None     # pre-paged stream
    total = last.get("kv_pages_total")
    peak = last.get("kv_pages_peak")
    return {
        "kv_layout": last.get("kv_layout"),
        "sampling": last.get("sampling"),
        "page_size": last.get("kv_page_size"),
        "pages_total": total,
        "pages_peak": peak,
        "peak_occupancy_pct": (
            100.0 * peak / total if total and peak is not None else None
        ),
        "page_exhausted": last.get("page_exhausted"),
    }


def summarize_spec(records: list[dict]) -> dict | None:
    """Fold the engine's speculative-decoding counters (final
    ``serve_summary``) into the speculation view: draft mode, dispatch and
    acceptance counts, and the two ratios that tell whether speculation
    paid for itself — acceptance rate (drafted tokens that matched the
    target stream) and tokens/dispatch (committed tokens per device
    round-trip; 1.0 is the non-speculative floor). None when the stream
    predates speculation or the engine ran with it off."""
    summaries = [r for r in records if r.get("record") == "serve_summary"]
    if not summaries:
        return None
    last = summaries[-1]
    if not last.get("spec_k"):
        return None
    return {
        "spec_k": last.get("spec_k"),
        "spec_draft": last.get("spec_draft"),
        "dispatches": last.get("spec_dispatches"),
        "drafted": last.get("spec_drafted"),
        "accepted": last.get("spec_accepted"),
        "accept_rate": last.get("spec_accept_rate"),
        "tokens_per_dispatch": last.get("tokens_per_dispatch"),
        "prefill_chunk": last.get("prefill_chunk"),
        "prefill_chunks": last.get("prefill_chunks"),
    }


def summarize_precision(records: list[dict]) -> dict | None:
    """Fold the engine's precision stamp (final ``serve_summary``) into
    the quantization view: which variant the replica served (fp32 or
    int8), the weight/KV dtypes behind it, and the paged pool's KV bytes
    per token (int8 pools carry a fp32 scale per head, so the figure is
    head_dim+4 per head, not head_dim). None when the stream predates
    quantized serving."""
    summaries = [r for r in records if r.get("record") == "serve_summary"]
    if not summaries:
        return None
    last = summaries[-1]
    if "weights_dtype" not in last:
        return None     # pre-quantization stream
    return {
        "variant": last.get("variant"),
        "weights_dtype": last.get("weights_dtype"),
        "kv_dtype": last.get("kv_dtype"),
        "kv_bytes_per_token": last.get("kv_bytes_per_token"),
    }


def summarize_prefix(records: list[dict]) -> dict | None:
    """Fold the engine's prefix-cache counters (the nested
    ``prefix_cache`` dict in the final ``serve_summary``) into the
    shared-KV view: lookup/hit traffic, trie churn (inserts, LRU
    evictions, swap invalidations), pages currently indexed and shared,
    COW copies, and tenant-quota admission holds. None when the stream
    predates the prefix cache or the engine ran with it off."""
    summaries = [r for r in records if r.get("record") == "serve_summary"]
    if not summaries:
        return None
    prefix = summaries[-1].get("prefix_cache")
    if not prefix:
        return None
    return {
        "lookups": prefix.get("prefix_lookups"),
        "hits": prefix.get("prefix_hits"),
        "hit_rate": prefix.get("prefix_hit_rate"),
        "inserts": prefix.get("prefix_inserts"),
        "evictions": prefix.get("prefix_evictions"),
        "invalidations": prefix.get("prefix_invalidations"),
        "cached_pages": prefix.get("prefix_cached_pages"),
        "pages_shared": prefix.get("pages_shared"),
        "cow_copies": prefix.get("cow_copies"),
        "tenant_blocked": prefix.get("tenant_blocked"),
        "tenant_page_quota": prefix.get("tenant_page_quota"),
        "prefill_tokens": summaries[-1].get("prefill_tokens"),
    }


def summarize_serve(records: list[dict]) -> dict | None:
    """Fold ``serve_request`` records into per-bucket latency percentiles
    plus aggregate serving stats; None when the stream holds none."""
    reqs = [r for r in records if r.get("record") == "serve_request"]
    if not reqs:
        return None
    done = [r for r in reqs if r.get("status") == "done"]
    by_bucket: dict[int, list[dict]] = {}
    for r in done:
        by_bucket.setdefault(int(r.get("bucket", 0)), []).append(r)
    buckets = []
    for bucket in sorted(by_bucket):
        rs = by_bucket[bucket]
        buckets.append({
            "bucket": bucket,
            "requests": len(rs),
            "new_tokens": sum(r.get("new_tokens", 0) for r in rs),
            "ttft_s": _pcts([r.get("ttft_s") for r in rs]),
            "tpot_s": _pcts([r.get("tpot_s") for r in rs]),
            "total_s": _pcts([r.get("total_s") for r in rs]),
        })
    tokens = sum(r.get("new_tokens", 0) for r in done)
    # aggregate tokens/sec over the stream's request span (ts is stamped at
    # finish; subtract the first request's own latency to recover its start)
    span = None
    if done:
        ts = [r.get("ts") for r in done if r.get("ts") is not None]
        if ts:
            first = min(ts) - (done[0].get("total_s") or 0.0)
            span = max(max(ts) - first, 1e-9)
    return {
        "requests": len(reqs),
        "done": len(done),
        "expired": sum(1 for r in reqs if r.get("status") == "expired"),
        "cancelled": sum(1 for r in reqs if r.get("status") == "cancelled"),
        "tokens": tokens,
        "tokens_per_s": tokens / span if span else None,
        "queue_wait_s": _pcts([r.get("queue_wait_s") for r in reqs]),
        "ttft_s": _pcts([r.get("ttft_s") for r in done]),
        "tpot_s": _pcts([r.get("tpot_s") for r in done]),
        "buckets": buckets,
        "paged": summarize_paged(records),
        "spec": summarize_spec(records),
        "precision": summarize_precision(records),
        "prefix": summarize_prefix(records),
    }


def summarize_fleet(records: list[dict]) -> dict | None:
    """Fold router/fleet records (serve/router.py + serve/fleet.py) into
    the fleet-health view: per-replica routed-request counts, failovers,
    hedges, breaker transitions, crash-vs-graceful exits and drain
    durations. None when the stream holds no fleet records."""
    router_reqs = [r for r in records if r.get("record") == "router_request"]
    failovers = [r for r in records if r.get("record") == "router_failover"]
    hedges = [r for r in records if r.get("record") == "router_hedge"]
    breakers = [r for r in records if r.get("record") == "router_breaker"]
    spawns = [r for r in records if r.get("record") == "replica_spawn"]
    exits = [r for r in records if r.get("record") == "replica_exit"]
    drains = [r for r in records if r.get("record") == "replica_drain"]
    if not (router_reqs or spawns or breakers):
        return None

    replicas: dict[str, dict] = {}

    def rep(name) -> dict:
        return replicas.setdefault(name or "?", {
            "requests": 0, "ok": 0, "midstream_errors": 0,
            "spawns": 0, "crashes": 0, "graceful_exits": 0,
            "breaker_opens": 0,
        })

    for r in router_reqs:
        row = rep(r.get("replica"))
        row["requests"] += 1
        if r.get("status") == "ok":
            row["ok"] += 1
        elif r.get("status") == "error_midstream":
            row["midstream_errors"] += 1
    for r in spawns:
        rep(r.get("replica"))["spawns"] += 1
    for r in exits:
        key = "graceful_exits" if r.get("graceful") else "crashes"
        rep(r.get("replica"))[key] += 1
    for r in breakers:
        if r.get("to") == "open":
            rep(r.get("replica"))["breaker_opens"] += 1
    replicas.pop("?", None)     # rejected requests have no replica

    statuses = [r.get("status") for r in router_reqs]
    return {
        "routed": len(router_reqs),
        "ok": statuses.count("ok"),
        "rejected": statuses.count("rejected"),
        "midstream_errors": statuses.count("error_midstream"),
        "failovers": len(failovers),
        "hedges": len(hedges),
        "breaker_transitions": len(breakers),
        "total_s": _pcts([r.get("total_s") for r in router_reqs]),
        "drain_s": _pcts([r.get("drain_s") for r in drains]),
        "replicas": {k: replicas[k] for k in sorted(replicas)},
    }


def summarize_storm(records: list[dict]) -> dict | None:
    """Fold the load-shaping records (SLO tier lanes + brownout ladder in
    serve/queue.py, autoscaler + dynamic pool in serve/autoscale.py +
    serve/fleet.py) into the storm view: per-tier request latency
    percentiles, shed/brownout counters, and the scale-event timeline
    (scale-ups with spawn->ready latency, drain-based scale-downs with
    measured drain time, bind-race port retries). None when the stream
    holds no tiered/brownout/scale records at all — pre-storm streams
    keep their old summary shape."""
    reqs = [
        r for r in records
        if r.get("record") == "serve_request" and r.get("tier") is not None
    ]
    sheds = [r for r in records if r.get("record") == "serve_shed"]
    brownouts = [
        r for r in records if r.get("record") == "brownout_transition"
    ]
    scales = [r for r in records if r.get("record") == "fleet_scale"]
    auto_events = [
        r for r in records if r.get("record") == "autoscale_event"
    ]
    readies = [r for r in records if r.get("record") == "autoscale_ready"]
    port_retries = [
        r for r in records if r.get("record") == "replica_port_retry"
    ]
    if not (reqs or sheds or brownouts or scales or auto_events):
        return None

    tiers = {}
    for tier in sorted({r.get("tier") for r in reqs}):
        rows = [r for r in reqs if r.get("tier") == tier]
        done = [r for r in rows if r.get("status") == "done"]
        tiers[tier] = {
            "requests": len(rows),
            "done": len(done),
            "expired": sum(1 for r in rows if r.get("status") == "expired"),
            "ttft_s": _pcts([r.get("ttft_s") for r in done]),
            "total_s": _pcts([r.get("total_s") for r in done]),
            "queue_wait_s": _pcts([r.get("queue_wait_s") for r in rows]),
        }

    shed_by_tier: dict[str, int] = {}
    for r in sheds:
        tier = r.get("tier") or "?"
        shed_by_tier[tier] = shed_by_tier.get(tier, 0) + 1
    peak_level = max((r.get("level", 0) for r in brownouts), default=0)
    level_names = ("normal", "shed_batch", "clamp", "fail_fast")

    def _level_of(name) -> int:
        return level_names.index(name) if name in level_names else 0

    # scale-event timeline, oldest first (ts is stamped by the sink)
    timeline = []
    for r in scales:
        timeline.append({
            "ts": r.get("ts"),
            "event": f"scale_{r.get('action')}",
            "replica": r.get("replica"),
            "size": r.get("size"),
            **({"drain_s": r.get("drain_s")}
               if r.get("drain_s") is not None else {}),
        })
    for r in readies:
        timeline.append({
            "ts": r.get("ts"),
            "event": "replica_ready",
            "replica": r.get("replica"),
            "ready_s": r.get("ready_s"),
        })
    for r in port_retries:
        timeline.append({
            "ts": r.get("ts"),
            "event": "port_retry",
            "replica": r.get("replica"),
            "new_port": r.get("new_port"),
        })
    timeline.sort(key=lambda e: e.get("ts") or 0.0)

    return {
        "tiers": tiers,
        "sheds": {
            "total": len(sheds),
            "by_tier": shed_by_tier,
        },
        "brownout": {
            "transitions": len(brownouts),
            "escalations": sum(
                1 for r in brownouts
                if r.get("level", 0) > _level_of(r.get("from"))
            ),
            "peak_level": peak_level,
            "final_level": brownouts[-1].get("level") if brownouts else 0,
        },
        "scale_ups": sum(
            1 for r in scales if r.get("action") == "up"
        ),
        "scale_downs": sum(
            1 for r in scales if r.get("action") == "down"
        ),
        "scale_up_ready_s": _pcts([r.get("ready_s") for r in readies]),
        "scale_down_drain_s": _pcts([
            r.get("drain_s") for r in scales
            if r.get("action") == "down"
        ]),
        "port_retries": len(port_retries),
        "timeline": timeline,
    }


def summarize_swap(records: list[dict]) -> dict | None:
    """Fold hot-swap records (serve/hotswap.py + the engine's swap
    protocol + the fleet's rolling rollout) into the rollout-health view:
    admissions, successful/failed swaps, rollbacks, rollout convergence
    times and how long the pool spent version-skewed. None when the
    stream holds no swap records."""
    admitted = [r for r in records if r.get("record") == "swap_admitted"]
    oks = [r for r in records if r.get("record") == "swap_ok"]
    fails = [r for r in records if r.get("record") == "swap_failed"]
    rollbacks = [r for r in records if r.get("record") == "swap_rollback"]
    rejected = [r for r in records if r.get("record") == "swap_rejected"]
    blocked = [r for r in records if r.get("record") == "swap_blocklisted"]
    rollouts = [r for r in records if r.get("record") == "fleet_swap"]
    skews = [r for r in records if r.get("record") == "router_skew"]
    if not (admitted or oks or fails or rollouts or skews):
        return None
    # version-skew duration: the spans between a router_skew record going
    # >0 and the next one back at 0 (ts is stamped by the sink)
    skew_s = 0.0
    open_t = None
    for r in skews:
        ts = r.get("ts")
        if ts is None:
            continue
        if (r.get("skew") or 0) > 0 and open_t is None:
            open_t = ts
        elif (r.get("skew") or 0) == 0 and open_t is not None:
            skew_s += ts - open_t
            open_t = None
    return {
        "admitted": len(admitted),
        "ok": len(oks),
        "failed": len(fails),
        "rollbacks": len(rollbacks),
        "rejected": len(rejected),
        "blocklisted": sorted({r.get("step") for r in blocked}),
        "load_s": _pcts([r.get("load_s") for r in oks]),
        "rollouts": len(rollouts),
        "rollouts_converged": sum(
            1 for r in rollouts if r.get("converged")
        ),
        "rollout_s": _pcts([r.get("duration_s") for r in rollouts]),
        "skew_events": len(skews),
        "skew_s": skew_s if skews else None,
    }


def _fmt(v, spec=".4g") -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return format(v, spec)
    return str(v)


def render_serve_table(serve: dict) -> str:
    """Per-bucket serving rows + an aggregate footer."""
    def ms(block: dict | None, key: str):
        return block[key] * 1e3 if block and block.get(key) is not None else None

    cols = ["bucket", "reqs", "tokens", "ttft p50 ms", "ttft p95 ms",
            "ttft p99 ms", "tpot p50 ms", "tpot p95 ms", "total p95 ms"]
    rows = []
    for b in serve["buckets"]:
        rows.append([
            _fmt(b["bucket"]), _fmt(b["requests"]), _fmt(b["new_tokens"]),
            _fmt(ms(b["ttft_s"], "p50")), _fmt(ms(b["ttft_s"], "p95")),
            _fmt(ms(b["ttft_s"], "p99")), _fmt(ms(b["tpot_s"], "p50")),
            _fmt(ms(b["tpot_s"], "p95")), _fmt(ms(b["total_s"], "p95")),
        ])
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "serving:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    qw = serve.get("queue_wait_s") or {}
    lines.append(
        f"requests={serve['requests']} done={serve['done']} "
        f"expired={serve['expired']} cancelled={serve['cancelled']} "
        f"tokens/s={_fmt(serve.get('tokens_per_s'))} "
        f"queue-wait p95={_fmt(ms(qw, 'p95') if qw else None)}ms"
    )
    precision = serve.get("precision")
    if precision:
        line = (
            f"precision: variant={precision.get('variant')} "
            f"weights={precision.get('weights_dtype')} "
            f"kv={precision.get('kv_dtype')}"
        )
        if precision.get("kv_bytes_per_token") is not None:
            line += (
                f" kv-bytes/token={_fmt(precision['kv_bytes_per_token'])}"
            )
        lines.append(line)
    paged = serve.get("paged")
    if paged:
        if paged.get("kv_layout") == "paged":
            lines.append(
                f"kv-cache: paged (page={_fmt(paged.get('page_size'))} tok, "
                f"pool={_fmt(paged.get('pages_total'))} pages, "
                f"peak={_fmt(paged.get('pages_peak'))} "
                f"[{_fmt(paged.get('peak_occupancy_pct'), '.1f')}%]) "
                f"sampling={paged.get('sampling')} "
                f"page-exhausted={_fmt(paged.get('page_exhausted'))}"
            )
        else:
            lines.append(
                f"kv-cache: dense  sampling={paged.get('sampling')}"
            )
    prefix = serve.get("prefix")
    if prefix:
        line = (
            f"prefix-cache: hit-rate={_fmt(prefix.get('hit_rate'), '.3f')} "
            f"({_fmt(prefix.get('hits'))}/{_fmt(prefix.get('lookups'))}) "
            f"cached-pages={_fmt(prefix.get('cached_pages'))} "
            f"shared={_fmt(prefix.get('pages_shared'))} "
            f"cow={_fmt(prefix.get('cow_copies'))} "
            f"evictions={_fmt(prefix.get('evictions'))} "
            f"invalidations={_fmt(prefix.get('invalidations'))}"
        )
        if prefix.get("tenant_page_quota"):
            line += (
                f" tenant-quota={_fmt(prefix['tenant_page_quota'], '.2f')}"
                f" tenant-blocked={_fmt(prefix.get('tenant_blocked'))}"
            )
        lines.append(line)
    spec = serve.get("spec")
    if spec:
        line = (
            f"speculation: k={_fmt(spec.get('spec_k'))} "
            f"draft={spec.get('spec_draft')} "
            f"accept-rate={_fmt(spec.get('accept_rate'), '.3f')} "
            f"tokens/dispatch={_fmt(spec.get('tokens_per_dispatch'), '.2f')} "
            f"(dispatches={_fmt(spec.get('dispatches'))} "
            f"drafted={_fmt(spec.get('drafted'))} "
            f"accepted={_fmt(spec.get('accepted'))})"
        )
        if spec.get("prefill_chunk"):
            line += (
                f" prefill-chunk={_fmt(spec.get('prefill_chunk'))}"
                f" chunks={_fmt(spec.get('prefill_chunks'))}"
            )
        lines.append(line)
    return "\n".join(lines)


def render_fleet_table(fleet: dict) -> str:
    """Per-replica fleet rows + a resilience footer."""
    cols = ["replica", "routed", "ok", "midstream", "spawns", "crashes",
            "drains", "brk-opens"]
    rows = []
    for name in sorted(fleet["replicas"]):
        r = fleet["replicas"][name]
        rows.append([
            name, _fmt(r["requests"]), _fmt(r["ok"]),
            _fmt(r["midstream_errors"]), _fmt(r["spawns"]),
            _fmt(r["crashes"]), _fmt(r["graceful_exits"]),
            _fmt(r["breaker_opens"]),
        ])
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "fleet:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    drain = fleet.get("drain_s") or {}
    lines.append(
        f"routed={fleet['routed']} ok={fleet['ok']} "
        f"rejected={fleet['rejected']} "
        f"midstream-errors={fleet['midstream_errors']} "
        f"failovers={fleet['failovers']} hedges={fleet['hedges']} "
        f"breaker-transitions={fleet['breaker_transitions']} "
        f"drain p95={_fmt(drain.get('p95'))}s"
    )
    return "\n".join(lines)


def render_storm_table(storm: dict) -> str:
    """Per-tier latency rows + shed/brownout counters + the scale-event
    timeline (the load-shaping view of a storm stream)."""
    def ms(block: dict | None, key: str):
        return (
            block[key] * 1e3
            if block and block.get(key) is not None else None
        )

    cols = ["tier", "reqs", "done", "expired", "ttft p50 ms",
            "total p50 ms", "total p95 ms", "total p99 ms",
            "queue-wait p95 ms"]
    rows = []
    for tier in sorted(storm["tiers"]):
        t = storm["tiers"][tier]
        rows.append([
            tier, _fmt(t["requests"]), _fmt(t["done"]), _fmt(t["expired"]),
            _fmt(ms(t["ttft_s"], "p50")),
            _fmt(ms(t["total_s"], "p50")), _fmt(ms(t["total_s"], "p95")),
            _fmt(ms(t["total_s"], "p99")),
            _fmt(ms(t["queue_wait_s"], "p95")),
        ])
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "storm:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    sheds = storm["sheds"]
    brown = storm["brownout"]
    shed_detail = ",".join(
        f"{k}={v}" for k, v in sorted(sheds["by_tier"].items())
    ) or "-"
    lines.append(
        f"sheds={sheds['total']} ({shed_detail})  "
        f"brownout: transitions={brown['transitions']} "
        f"peak-level={brown['peak_level']} "
        f"final-level={brown['final_level']}"
        + (" [recovered]" if brown["final_level"] == 0 else " [DEGRADED]")
    )
    ready = storm.get("scale_up_ready_s") or {}
    drain = storm.get("scale_down_drain_s") or {}
    lines.append(
        f"autoscale: ups={storm['scale_ups']} "
        f"(ready p95={_fmt(ready.get('p95'))}s) "
        f"downs={storm['scale_downs']} "
        f"(drain p95={_fmt(drain.get('p95'))}s) "
        f"port-retries={storm['port_retries']}"
    )
    t0 = next(
        (e["ts"] for e in storm["timeline"] if e.get("ts") is not None),
        None,
    )
    for e in storm["timeline"]:
        at = (
            f"+{e['ts'] - t0:.1f}s" if t0 is not None and e.get("ts")
            is not None else "?"
        )
        extra = "".join(
            f" {k}={_fmt(e[k], '.3g')}" for k in ("size", "ready_s",
                                                  "drain_s", "new_port")
            if e.get(k) is not None
        )
        lines.append(f"  {at:>8}  {e['event']:<13} {e['replica']}{extra}")
    return "\n".join(lines)


def render_locks_table(locks: dict, top_n: int = 8) -> str:
    """Top-N locks by contention then hold p99, plus any violations."""
    rows_src = sorted(
        locks["locks"].items(),
        key=lambda kv: (
            -(kv[1]["contentions"]), -(kv[1]["hold_p99_s"] or 0.0),
            kv[0],
        ),
    )[:top_n]
    cols = ["lock", "acquires", "contended", "wait max ms", "wait p99 ms",
            "hold max ms", "hold p99 ms"]

    def ms(v):
        return v * 1e3 if v is not None else None

    rows = [[
        name, _fmt(s["acquires"]), _fmt(s["contentions"]),
        _fmt(ms(s["wait_max_s"])), _fmt(ms(s["wait_p99_s"])),
        _fmt(ms(s["hold_max_s"])), _fmt(ms(s["hold_p99_s"])),
    ] for name, s in rows_src]
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "locks:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    dropped = len(locks["locks"]) - len(rows)
    foot = (
        f"processes={locks['processes']} "
        f"order-violations={locks['order_violations']} "
        f"device-boundary-holds={locks['device_boundary_holds']}"
        + (f" (+{dropped} quieter lock(s) not shown)" if dropped > 0 else "")
        + (" [VIOLATIONS]"
           if locks["order_violations"] or locks["device_boundary_holds"]
           else " [clean]")
    )
    lines.append(foot)
    for v in locks["order_violation_detail"]:
        lines.append(
            f"  INVERSION: acquiring {v['acquiring']} while holding "
            f"{v['holding']} (inverts {v['inverts']})"
        )
    return "\n".join(lines)


def render_comm_table(comm: dict) -> str:
    """Per-program collective-footprint rows + a manifest verdict footer."""
    cols = ["program", "collectives", "kinds", "payload B", "moved B",
            "manifest", "verdict"]
    rows = []
    for name, p in comm["programs"].items():
        kinds = ",".join(
            f"{k}x{n}" for k, n in sorted(p["by_kind"].items())
        ) or "-"
        verdict = (
            "ERROR" if p["error"] else
            "ok" if p["ok"] else
            "?" if p["ok"] is None else "DEVIATES"
        )
        rows.append([
            name, _fmt(p["collectives"]), kinds, _fmt(p["total_bytes"]),
            _fmt(p["total_moved_bytes"]), p["manifest"] or "-", verdict,
        ])
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "comm:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    lines.append(
        f"audits={comm['audits']} deviations={comm['deviations']}"
        + (" [clean]" if comm["clean"] else " [VIOLATIONS]")
    )
    for name, p in comm["programs"].items():
        for d in p["deviations"]:
            lines.append(f"  DEVIATION {name}: {d}")
    return "\n".join(lines)


def render_spans_table(spans: dict) -> str:
    """Per-tier per-phase latency rows + the structural-verdict footer
    (the tracing view of a spanned stream)."""
    def ms(block: dict | None, key: str):
        return (
            block[key] * 1e3
            if block and block.get(key) is not None else None
        )

    cols = ["tier", "phase", "count", "p50 ms", "p95 ms", "p99 ms"]
    rows = []
    for tier in sorted(spans["tiers"]):
        for phase, block in spans["tiers"][tier].items():
            rows.append([
                tier, phase, _fmt(block["count"] if block else 0),
                _fmt(ms(block, "p50")), _fmt(ms(block, "p95")),
                _fmt(ms(block, "p99")),
            ])
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "spans:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    structural_bad = (
        spans["orphan_spans"] or spans["incomplete_traces"]
        or spans["phase_sum_bad"]
    )
    lines.append(
        f"traces={spans['traces']} complete={spans['complete_traces']} "
        f"incomplete={spans['incomplete_traces']} "
        f"orphan-spans={spans['orphan_spans']} "
        f"phase-sum-bad={spans['phase_sum_bad']} "
        f"attempts={spans['attempts']} hedges={spans['hedges']}"
        + (" [INCOMPLETE]" if structural_bad else " [complete]")
    )
    return "\n".join(lines)


def render_slo_table(slo: dict) -> str:
    """Per-tier per-window burn rows from the stream's latest
    ``slo_burn`` record."""
    cols = ["tier", "window", "reqs", "deadline-met", "avail",
            "deadline-burn", "avail-burn"]
    rows = []
    for tier in sorted(slo["tiers"]):
        for label, w in slo["tiers"][tier].items():
            rows.append([
                tier, label, _fmt(w["requests"]),
                _fmt(w["deadline_met"], ".3f"),
                _fmt(w["availability"], ".3f"),
                _fmt(w["deadline_burn"], ".2f"),
                _fmt(w["availability_burn"], ".2f"),
            ])
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(cols)
    ]
    lines = [
        "slo:",
        "  ".join(h.rjust(w) for h, w in zip(cols, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    lines.append(
        f"objectives: deadline={_fmt(slo['deadline_objective'])} "
        f"availability={_fmt(slo['availability_objective'])}  "
        f"max-burn={_fmt(slo['max_burn'], '.2f')} "
        f"peak-burn={_fmt(slo['peak_burn'], '.2f')}"
        + (" [BURNING]" if (slo["max_burn"] or 0) > 1.0 else " [ok]")
    )
    return "\n".join(lines)


def render_table(summary: dict) -> str:
    cols = [
        ("epoch", "epoch"),
        ("steps", "steps"),
        ("train_loss", "loss"),
        ("samples_per_sec_per_chip", "samp/s/chip"),
        ("data_wait_pct", "data-wait %"),
        ("prefetch_occupancy_mean", "pf-occ"),
        ("prefetch_stalls", "pf-stall"),
        ("slowest_host", "slow host"),
        ("wait_skew_s", "skew s"),
        ("accuracy", "acc"),
    ]
    rows = [[_fmt(e.get(k)) for k, _ in cols] for e in summary["epochs"]]
    headers = [h for _, h in cols]
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    lines = [
        "  ".join(h.rjust(w) for h, w in zip(headers, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.rjust(w) for c, w in zip(r, widths)) for r in rows]
    run = summary["run"]
    lines.append(
        f"mesh={run.get('mesh_shape')} chips={run.get('chip_count')} "
        f"ckpt_saves={summary['checkpoint_saves']} "
        f"restarts={summary['restarts']}"
    )
    comp = summary.get("compile")
    if comp:
        hit = comp.get("cache_hit")
        lines.append(
            f"compile: {_fmt(comp.get('total_s'))}s "
            f"(train {_fmt(comp.get('train_compile_s'))}s, "
            f"eval {_fmt(comp.get('eval_compile_s'))}s, "
            f"cache={'hit' if hit else 'miss' if hit is not None else 'off'})"
        )
    serve = summary.get("serve")
    fleet = summary.get("fleet")
    if serve:
        if summary["epochs"]:
            lines.append(render_serve_table(serve))
        else:  # pure serving stream: the serve table IS the output
            lines = [render_serve_table(serve)]
    if fleet:
        if not summary["epochs"] and not serve:
            lines = []  # pure fleet stream: the fleet table IS the output
        lines.append(render_fleet_table(fleet))
    storm = summary.get("storm")
    if storm:
        if not summary["epochs"] and not serve and not fleet:
            lines = []  # pure storm stream: the storm table IS the output
        lines.append(render_storm_table(storm))
    swap = summary.get("swap")
    if swap:
        ro = swap.get("rollout_s") or {}
        lines.append(
            f"hotswap: admitted={swap['admitted']} ok={swap['ok']} "
            f"failed={swap['failed']} rollbacks={swap['rollbacks']} "
            f"rejected={swap['rejected']} "
            f"blocklisted={swap['blocklisted'] or '-'} "
            f"rollouts={swap['rollouts']}"
            f"/{swap['rollouts_converged']} converged "
            f"(p95 {_fmt(ro.get('p95'))}s) "
            f"skew={_fmt(swap.get('skew_s'))}s"
        )
    spans = summary.get("spans")
    if spans:
        lines.append(render_spans_table(spans))
    slo = summary.get("slo")
    if slo:
        lines.append(render_slo_table(slo))
    flight = summary.get("flight")
    if flight:
        reasons = ",".join(
            f"{k}={v}" for k, v in sorted(flight["by_reason"].items())
        )
        lines.append(
            f"flight-dumps: {flight['dumps']} ({reasons}) "
            f"last-ticks={[d['last_tick'] for d in flight['detail']]}"
        )
    locks = summary.get("locks")
    if locks:
        lines.append(render_locks_table(locks))
    comm = summary.get("comm")
    if comm:
        lines.append(render_comm_table(comm))
    guards = summary.get("guards")
    if guards:
        bad = (
            guards["recompiles"] or guards["implicit_transfers"]
            or guards["donation_audits_failed"]
            or guards["sharding_audits_failed"]
        )
        gl = (
            f"guards: recompiles={guards['recompiles']}"
            + (f" ({','.join(guards['recompiled_fns'])})"
               if guards["recompiled_fns"] else "")
            + f" implicit-transfers={guards['implicit_transfers']}"
            + f" donation-fails={guards['donation_audits_failed']}"
            + f" sharding-fails={guards['sharding_audits_failed']}"
            + (" [VIOLATIONS]" if bad else " [clean]")
        )
        lint = guards.get("lint")
        if lint:
            gl += (
                f"  lint: {_fmt(lint.get('findings'))} finding(s), "
                f"{_fmt(lint.get('waived'))} waived"
            )
        lines.append(gl)
    return "\n".join(lines)


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("path", help="metrics.jsonl file or its --metrics-dir")
    p.add_argument("--json", action="store_true",
                   help="print the summary as JSON instead of a table")
    args = p.parse_args(argv)
    summary = summarize(load_records(args.path))
    if args.json:
        print(json.dumps(summary, indent=1))
    else:
        print(render_table(summary))
    return summary


if __name__ == "__main__":
    main()
