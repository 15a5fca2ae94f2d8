"""Speculative decoding + chunked prefill tests (serve/engine.py verify
dispatch, serve/sampling.py ``spec_accept``, serve/paged_cache.py
reservation overshoot, ops/paged_attention.py multi-token query path and
their engine integration): acceptance bit-identity against the
non-speculative stream (greedy here; fixed-seed sampled against the host
sampler in tests/test_paged.py's ``spec_k3`` case),
adversarial all-reject rollback with exact allocator accounting, chunked
prefill token-identity across ragged chunk boundaries, mixed spec/non-spec
slots in one tick, the page-reservation overshoot formula, and the strict
tick-wide scope with the verify program's collective manifest. CPU, tier-1.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models.generate import generate
from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.paged_cache import PageAllocator
from pytorch_distributed_training_tpu.serve.sampling import (
    device_sample,
    spec_accept,
)
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.utils.config import model_preset

pytestmark = pytest.mark.serve

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ListSink:
    """In-memory telemetry sink (same contract as JsonlSink.emit)."""

    def __init__(self):
        self.records = []

    def emit(self, record):
        rec = dict(record)
        rec.setdefault("ts", time.time())
        self.records.append(rec)

    def flush(self, **kw):
        pass

    def of(self, kind):
        return [r for r in self.records if r.get("record") == kind]


@pytest.fixture(scope="module")
def lm():
    cfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))[
        "params"
    ]
    return model, params


def _registry():
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    return reg, sink


def _prompts(model, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, model.config.vocab_size, n).astype(np.int32)
        for n in lengths
    ]


def _want(model, params, prompts, T):
    return [
        np.asarray(generate(model, params, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]


def _run_server(model, params, prompts, T, *, temperature=0.0, top_k=0,
                seed=0, spec_flags=None, draft_model=None, draft_params=None,
                mutate_engine=None, **cfg_kw):
    reg, sink = _registry()
    cfg_kw.setdefault("prompt_buckets", (4, 8, 16))
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, max_new_tokens=T, **cfg_kw,
        ),
        queue_depth=16, registry=reg,
        draft_model=draft_model, draft_params=draft_params,
    )
    if mutate_engine is not None:
        mutate_engine(server.engine)
    server.start()
    try:
        reqs = [
            server.submit(
                p, max_new_tokens=T, temperature=temperature, top_k=top_k,
                seed=seed + i,
                spec=None if spec_flags is None else spec_flags[i],
            )
            for i, p in enumerate(prompts)
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]
    toks = [np.asarray(r.tokens, np.int32) for r in reqs]
    return toks, server.stats(), reg, sink


# --------------------------------------------------- acceptance sampling


def test_spec_accept_leading_match_semantics():
    """``spec_accept`` commits exactly the leading run of draft tokens that
    match the per-position streams, and every target row equals what
    ``device_sample`` produces for that (seed, step) — the primitive the
    engine's bit-identity rests on."""
    rng = np.random.default_rng(0)
    S, Q, V = 3, 4, 32
    logits = jnp.asarray(rng.normal(size=(S, Q, V)), jnp.float32)
    seeds = jnp.asarray([5, 6, 7], jnp.int32)
    steps0 = jnp.asarray([1, 3, 9], jnp.int32)
    temps = jnp.asarray([0.0, 0.7, 0.0], jnp.float32)
    top_ks = jnp.asarray([0, 4, 0], jnp.int32)

    # the per-position reference: each row sampled on its own stream
    want = np.stack([
        np.asarray(device_sample(
            logits[:, j, :], seeds, steps0 + j, temps, top_ks
        ))
        for j in range(Q)
    ], axis=1)

    # drafts agreeing on a known leading prefix per slot: 3, 0, 1 matches.
    # draft[j] guesses emission j (= target row j): the engine feeds it as
    # token j+1, so row j+1's logits condition on it — accept stops at the
    # first row whose guess missed.
    draft = want[:, : Q - 1].copy()
    draft[1, 0] = (draft[1, 0] + 1) % V
    draft[2, 1] = (draft[2, 1] + 1) % V
    target, accept = spec_accept(
        logits, jnp.asarray(draft), seeds, steps0, temps, top_ks
    )
    np.testing.assert_array_equal(np.asarray(target), want)
    np.testing.assert_array_equal(np.asarray(accept), [3, 0, 1])


# ------------------------------------------------------ stream identity


def test_spec_greedy_bit_identical_to_generate(lm):
    """Acceptance pin: the speculative engine's greedy streams (n-gram
    self-drafting) are bit-identical to one-shot generate(), and the
    speculation telemetry fires."""
    model, params = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = _want(model, params, prompts, T)
    toks, stats, reg, _ = _run_server(
        model, params, prompts, T, spec_k=3,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
    assert stats["spec_k"] == 3 and stats["spec_draft"] == "ngram"
    assert stats["spec_dispatches"] > 0
    assert 0 < stats["spec_accepted"] <= stats["spec_drafted"]
    assert 0.0 < stats["spec_accept_rate"] <= 1.0
    # one verify dispatch commits more than one token on average
    assert stats["tokens_per_dispatch"] > 1.0
    gauges = reg.snapshot()["gauges"]
    assert "serve/spec_accept_rate" in gauges
    assert "serve/tokens_per_dispatch" in gauges


def test_mixed_spec_and_nonspec_slots_share_ticks(lm):
    """Per-request spec opt-out: slots with ``spec=False`` ride the same
    verify dispatch with zero drafted tokens, and every stream — both
    kinds, interleaved in the same ticks — stays greedy-exact."""
    model, params = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = _want(model, params, prompts, T)
    toks, stats, _, _ = _run_server(
        model, params, prompts, T, spec_k=3,
        spec_flags=[True, False, True, False, None],
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
    # non-spec slots drafted nothing, spec slots did
    assert 0 < stats["spec_drafted"] < stats["spec_dispatches"] * 2 * 3


# ------------------------------------------------- rollback + allocator


def test_all_reject_rollback_releases_every_page(lm):
    """Adversarial drafter: every proposal is -1 (matches no sampled token
    ever), so EVERY tick rejects the whole draft block. The streams must
    still be greedy-exact (row 0 of each verify is correct by
    construction), the engine must still make one token of progress per
    dispatch, and rollback must be pure cursor rewind: zero accepted
    drafts, zero page_exhausted, and every page back in the pool."""
    model, params = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = _want(model, params, prompts, T)

    def sabotage(engine):
        engine._ngram_draft = lambda hist, k: [-1] * k

    toks, stats, _, _ = _run_server(
        model, params, prompts, T, spec_k=3, mutate_engine=sabotage,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
        assert len(got) == T
    assert stats["spec_dispatches"] > 0 and stats["spec_drafted"] > 0
    assert stats["spec_accepted"] == 0
    assert stats["spec_accept_rate"] == 0.0
    # all-reject degrades to the non-speculative rate: at most ONE token
    # per SLOT per dispatch (the cross-slot batch still shares a dispatch)
    assert 1.0 <= stats["tokens_per_dispatch"] <= 2.0
    assert stats["page_exhausted"] == 0
    # the dead draft lanes leaked nothing: pool exactly restored
    assert stats["kv_pages_used"] == 0
    assert stats["kv_pages_free"] == stats["kv_pages_total"]


# ------------------------------------------------------- chunked prefill


def test_chunked_prefill_identical_across_ragged_boundaries(lm):
    """Chunked prefill == monolithic prefill, token for token, across
    prompt lengths that land on, under, and over the chunk boundary (len %
    chunk in {0,1,chunk-1}) — the ragged last chunk pads but commits only
    real positions."""
    model, params = lm
    T = 5
    lengths = [3, 4, 5, 8, 9, 14, 16]
    prompts = _prompts(model, lengths, seed=5)
    want = _want(model, params, prompts, T)
    toks, stats, reg, _ = _run_server(
        model, params, prompts, T, prefill_chunk=4,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(
            got, ref, err_msg=f"request {i} (len {lengths[i]})"
        )
    assert stats["prefill_chunk"] == 4
    assert stats["prefill_chunks"] == sum(-(-n // 4) for n in lengths)
    assert "serve/prefill_chunks" in reg.snapshot()["gauges"]


def test_spec_plus_chunked_prefill_identical(lm):
    """Both features on at once: chunked prompts stream in while other
    slots verify speculative blocks, and every stream is still exact."""
    model, params = lm
    T = 5
    prompts = _prompts(model, [3, 9, 14, 16, 5], seed=2)
    want = _want(model, params, prompts, T)
    toks, stats, _, _ = _run_server(
        model, params, prompts, T, spec_k=3, prefill_chunk=4,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
    assert stats["spec_dispatches"] > 0 and stats["prefill_chunks"] > 0


# ----------------------------------------------------- draft-model lane


def test_draft_model_lane_identity_and_acceptance(lm):
    """The draft-model lane with the BASE model drafting for itself:
    greedy proposals then match the greedy target stream almost always
    (the first verify after a partial acceptance may resync), acceptance
    approaches 1.0, and the streams stay exact."""
    model, params = lm
    T = 6
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = _want(model, params, prompts, T)
    toks, stats, _, _ = _run_server(
        model, params, prompts, T, spec_k=3, spec_draft="model",
        draft_model=model, draft_params=params,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
    assert stats["spec_draft"] == "model"
    assert stats["spec_accept_rate"] > 0.9, stats["spec_accept_rate"]


# ------------------------------------------------ reservation overshoot


def test_pages_reserved_formula():
    """The documented overshoot formula: a spec slot's reservation covers
    the highest position a verify dispatch can ever scatter —
    ``(prompt + max_new - 2) + k`` — for any shape, so mid-flight
    page exhaustion is impossible by construction."""
    alloc = PageAllocator(
        num_pages=64, page_size=4, pages_per_slot=16, num_slots=1
    )
    assert alloc.pages_reserved(10, 0) == alloc.pages_needed(10)
    for total, k, page in [(5, 1, 2), (8, 3, 4), (17, 7, 4), (40, 5, 8),
                           (3, 2, 16), (64, 3, 8)]:
        a = PageAllocator(
            num_pages=128, page_size=page, pages_per_slot=64, num_slots=1
        )
        reserved = a.pages_reserved(total, k)
        assert reserved == a.pages_needed(total + k)
        worst_scatter_index = (total - 2) + k
        assert worst_scatter_index < reserved * page, (total, k, page)


def test_reservation_overshoot_never_trips_page_exhausted(lm):
    """A pool sized EXACTLY to the formula (num_slots x
    pages_reserved(bucket + max_new, k) + the null page) serves a burst of
    full-length speculative requests with ZERO page_exhausted events —
    the overshoot reservation makes draft scatter beyond the emission cap
    safe by construction, not by slack."""
    model, params = lm
    T, k, page_size = 8, 3, 4
    prompts = _prompts(model, [8, 5, 8, 6, 7, 8], seed=1)
    want = _want(model, params, prompts, T)
    per_slot = -(-(8 + T + k) // page_size)     # pages_reserved(16, 3)
    num_pages = 2 * per_slot + 1                # 2 slots, + null page
    toks, stats, _, _ = _run_server(
        model, params, prompts, T, spec_k=k,
        prompt_buckets=(8,), page_size=page_size, num_pages=num_pages,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
        assert len(got) == T                    # ran to the emission cap
    assert stats["page_exhausted"] == 0
    assert stats["kv_pages_used"] == 0
    assert stats["kv_pages_free"] == num_pages - 1


# ------------------------------------------------- strict scope + audits


def test_spec_strict_scope_verify_manifest_and_donation(lm):
    """With warmup, a speculative session runs its whole tick under
    transfer_guard("disallow"): zero implicit transfers (the only D2H is
    the verify result — token ids + accept counts), zero recompiles, the
    hot verify program passes its zero-collective manifest, and its cache
    donation survived to the executable."""
    from pytorch_distributed_training_tpu.analysis.guards import GuardSet

    model, params = lm
    reg, sink = _registry()
    gs = GuardSet(mode="strict", registry=reg)
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(4, 8), max_new_tokens=4,
            warmup=True, spec_k=3,
        ),
        queue_depth=16, registry=reg, guards=gs,
    ).start()
    try:
        rng = np.random.default_rng(3)
        reqs = []
        for i, n in enumerate([3, 6, 2, 7, 4, 5]):
            reqs.append(server.submit(
                rng.integers(1, model.config.vocab_size, n).astype(np.int32),
                max_new_tokens=4,
                temperature=0.8 if i % 2 else 0.0, top_k=3, seed=i,
            ))
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        )
    finally:
        server.close()

    assert all(r.status == "done" for r in reqs)
    stats = server.stats()
    assert stats["guard_mode"] == "strict"
    assert stats["guard_recompiles"] == 0
    assert stats["guard_implicit_transfers"] == 0
    assert not sink.of("recompile") and not sink.of("implicit_transfer")
    assert gs.wrapped["serve_verify"].calls >= 2
    # the hot program under speculation is the VERIFY dispatch: it carries
    # the zero-collective manifest (single-device engine moves zero bytes)
    (comm,) = sink.of("comm_audit")
    assert comm["name"] == "serve_verify" and comm["ok"] is True
    assert comm["count"] == 0
    # cache donation on the verify program survived lowering
    donations = [
        r for r in sink.of("donation_audit") if r["name"] == "serve_verify"
    ]
    assert donations and all(r.get("aliased") for r in donations)


# --------------------------------------------------------- summarization


def test_summarize_metrics_speculation_line():
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        from summarize_metrics import (
            render_serve_table,
            summarize_serve,
            summarize_spec,
        )
    finally:
        sys.path.pop(0)

    records = [
        {"record": "serve_request", "status": "done", "bucket": 8,
         "new_tokens": 4, "ttft_s": 0.01, "tpot_s": 0.002, "total_s": 0.02,
         "queue_wait_s": 0.001, "ts": 100.0},
        {"record": "serve_summary", "kv_layout": "paged", "sampling":
         "device", "kv_page_size": 8, "kv_pages_total": 32, "kv_pages_peak":
         6, "page_exhausted": 0, "spec_k": 3, "spec_draft": "ngram",
         "spec_dispatches": 10, "spec_drafted": 30, "spec_accepted": 21,
         "spec_accept_rate": 0.7, "tokens_per_dispatch": 3.1,
         "prefill_chunk": 4, "prefill_chunks": 9},
    ]
    spec = summarize_spec(records)
    assert spec["spec_k"] == 3 and spec["accept_rate"] == 0.7
    assert spec["prefill_chunks"] == 9
    table = render_serve_table(summarize_serve(records))
    assert "speculation:" in table
    assert "accept-rate=0.700" in table
    assert "tokens/dispatch=3.10" in table
    assert "prefill-chunk=4" in table
    # engines without speculation keep the old table
    assert summarize_spec([records[0]]) is None
