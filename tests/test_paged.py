"""Paged KV cache + on-device sampling tests (serve/paged_cache.py,
serve/sampling.py, ops/paged_attention.py and their engine integration):
allocator lifecycle, page-budget admission backpressure, block-table
attention pins (reference vs dense formula, pallas-interpret vs reference),
the device-sampler's bit-exactness pin against the host sampler, engine
token-identity (greedy against one-shot generate; sampled streams of every
engine variant against the host sampler over the plain model's no-cache
logits), mixed-context serving below dense-equivalent memory, and the
strict tick-wide transfer scope. CPU, tier-1.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models.generate import generate
from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
from pytorch_distributed_training_tpu.ops.paged_attention import (
    paged_attention,
)
from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.paged_cache import (
    PageAllocator,
    strip_tables,
    with_tables,
)
from pytorch_distributed_training_tpu.serve.sampling import (
    device_sample,
    host_sample,
)
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.utils.config import model_preset

from kv_pools import fold_heads  # sibling module (pytest sys.path)

pytestmark = pytest.mark.serve


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


# --------------------------------------------------------------- allocator


def test_allocator_alloc_free_reuse():
    alloc = PageAllocator(
        num_pages=9, page_size=4, pages_per_slot=3, num_slots=2
    )
    assert alloc.pages_free == 8 and alloc.pages_used == 0

    alloc.admit(0, 3)
    assert alloc.pages_used == 3 and alloc.pages_free == 5
    first = alloc.slot_pages(0)
    assert len(first) == 3 and 0 not in first
    np.testing.assert_array_equal(alloc.block_table[0], np.asarray(first))

    alloc.admit(1, 2)
    assert alloc.pages_used == 5
    # disjoint ownership, never the null page
    assert not set(first) & set(alloc.slot_pages(1))

    alloc.release(0)
    assert alloc.pages_used == 2 and alloc.pages_free == 6
    assert alloc.slot_pages(0) == ()
    np.testing.assert_array_equal(alloc.block_table[0], 0)

    # LIFO free list: the just-freed pages are re-handed first (hot set
    # stays small), in the same order the slot originally held them
    alloc.admit(0, 3)
    assert alloc.slot_pages(0) == first
    assert alloc.peak_used == 5


def test_allocator_exhaustion_backpressure_and_misuse():
    alloc = PageAllocator(
        num_pages=5, page_size=4, pages_per_slot=4, num_slots=2
    )
    assert alloc.can_alloc(4) and not alloc.can_alloc(5)
    alloc.admit(0, 3)
    assert not alloc.can_alloc(2)       # 1 free page left
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.admit(1, 2)
    with pytest.raises(RuntimeError, match="already holds"):
        alloc.admit(0, 1)
    with pytest.raises(ValueError, match="block-table rows"):
        alloc.admit(1, 5)
    # a failed admit must not leak or corrupt anything
    assert alloc.pages_used == 3 and alloc.can_alloc(1)

    # release is idempotent and returns everything
    alloc.release(0)
    alloc.release(0)
    assert alloc.pages_free == 4 and alloc.pages_used == 0

    # ceil-division page budget
    assert alloc.pages_needed(1) == 1
    assert alloc.pages_needed(4) == 1
    assert alloc.pages_needed(5) == 2
    assert alloc.pages_needed(0) == 1   # a slot always needs one page


def test_allocator_rejects_degenerate_shapes():
    with pytest.raises(ValueError, match="page_size"):
        PageAllocator(num_pages=4, page_size=0, pages_per_slot=1, num_slots=1)
    with pytest.raises(ValueError, match="num_pages"):
        PageAllocator(num_pages=1, page_size=4, pages_per_slot=1, num_slots=1)
    with pytest.raises(ValueError, match="pages_per_slot"):
        PageAllocator(num_pages=4, page_size=4, pages_per_slot=0, num_slots=1)


def test_with_tables_strip_tables_roundtrip():
    pools = {
        "layers_0": {"attn": {"k_pages": "K0", "v_pages": "V0"}},
        "layers_1": {"attn": {"k_pages": "K1", "v_pages": "V1"}},
    }
    full = with_tables(pools, "BT", "CL")
    for layer in ("layers_0", "layers_1"):
        node = full[layer]["attn"]
        assert node["block_table"] == "BT" and node["context_len"] == "CL"
    assert strip_tables(full) == pools
    # the original pools tree is untouched (with_tables builds a new dict)
    assert "block_table" not in pools["layers_0"]["attn"]


# ----------------------------------------------------- page-budget admission


def test_pop_ready_accept_predicate_is_strict_fifo():
    from pytorch_distributed_training_tpu.serve.queue import (
        GenRequest,
        RequestQueue,
    )

    q = RequestQueue(max_depth=8, prompt_buckets=(4, 8), max_new_tokens=4)
    big = q.submit(GenRequest(
        id="big", prompt_ids=np.ones(7, np.int32), max_new_tokens=4,
    ))
    q.submit(GenRequest(
        id="small", prompt_ids=np.ones(3, np.int32), max_new_tokens=4,
    ))

    # the earliest-submitted head (big) fails the predicate: pop_ready
    # must return None — the small request may NOT slip past it
    assert q.pop_ready(accept=lambda r: r.bucket <= 4) is None
    assert q.depth() == 2

    # once the head is accepted, submission order resumes
    assert q.pop_ready(accept=lambda r: True) is big
    assert q.pop_ready().id == "small"
    assert q.pop_ready() is None


# ------------------------------------------------------- paged attention op


def _paged_fixture(seed=0, batch=3, heads=2, head_dim=4, page_size=4,
                   windows=3, num_pages=16):
    """Random contiguous K/V scattered into a noise-filled page pool via a
    shuffled block table, plus the dense [B, T, H, D] mirror. The pools
    come back in the engine's lane-dense [N, P, H*D] shape."""
    rng = np.random.default_rng(seed)
    T = page_size * windows
    q = rng.standard_normal((batch, heads, head_dim)).astype(np.float32)
    k = rng.standard_normal((batch, T, heads, head_dim)).astype(np.float32)
    v = rng.standard_normal((batch, T, heads, head_dim)).astype(np.float32)
    # pools start as GARBAGE, not zeros: masked lanes must be excluded by
    # the length mask alone, never by relying on zeroed storage
    k_pages = rng.standard_normal(
        (num_pages, page_size, heads, head_dim)
    ).astype(np.float32)
    v_pages = rng.standard_normal(
        (num_pages, page_size, heads, head_dim)
    ).astype(np.float32)
    ids = rng.permutation(np.arange(1, num_pages))[: batch * windows]
    block_table = ids.reshape(batch, windows).astype(np.int32)
    for b in range(batch):
        for w in range(windows):
            k_pages[block_table[b, w]] = k[b, w * page_size:(w + 1) * page_size]
            v_pages[block_table[b, w]] = v[b, w * page_size:(w + 1) * page_size]
    lengths = np.asarray([1, T - 3, T], np.int32)[:batch]
    return (q, k, v, fold_heads(k_pages), fold_heads(v_pages), block_table,
            lengths)


def _dense_formula(q, k, v, lengths, scale):
    """The exact fp32-softmax formula models/bert.py uses on the dense
    cache path, applied to contiguous K/V."""
    scores = jnp.einsum(
        "bnd,btnd->bnt", q, k, preferred_element_type=jnp.float32
    ) * scale
    pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    scores = jnp.where(
        pos < lengths[:, None, None], scores, jnp.finfo(jnp.float32).min
    )
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bnt,btnd->bnd", probs, v)


@pytest.mark.parametrize(
    "heads,head_dim", [(2, 4), (4, 16), (3, 64), (16, 64)],
    ids=["lanes8", "lanes64", "lanes192", "lanes1024"],
)
def test_paged_reference_bitwise_matches_dense_formula(heads, head_dim):
    """Token identity with the dense formula at every lane width: under a
    tile (8, 64), no multiple of 128 (192) and gpt2-medium's 1024."""
    q, k, v, k_pages, v_pages, bt, lengths = _paged_fixture(
        heads=heads, head_dim=head_dim
    )
    assert k_pages.shape == (16, 4, heads * head_dim)
    scale = q.shape[-1] ** -0.5
    want = _dense_formula(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lengths), scale,
    )
    got = paged_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(bt), jnp.asarray(lengths),
        scale=scale, impl="reference",
    )
    # bitwise: the gather through the block table reassembles the same
    # contiguous K/V, the masked (garbage) lanes contribute exact zeros
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_pallas_interpret_matches_reference():
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        tpu_interpret_mode,
    )

    q, k, v, k_pages, v_pages, bt, lengths = _paged_fixture(seed=5)
    scale = q.shape[-1] ** -0.5
    ref = paged_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(bt), jnp.asarray(lengths),
        scale=scale, impl="reference",
    )
    with tpu_interpret_mode():
        got = paged_attention(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(bt), jnp.asarray(lengths),
            scale=scale, impl="pallas",
        )
    # online softmax reorders the reduction: tight allclose, not bitwise
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-6
    )


def test_paged_attention_validates_shapes():
    q, k, v, k_pages, v_pages, bt, lengths = _paged_fixture()
    # q may be [B, H, D] (single query) or [B, Q, H, D] (multi-token
    # query, the spec-verify / chunked-prefill path) — 5-D is invalid.
    with pytest.raises(ValueError):
        paged_attention(
            jnp.asarray(q)[:, None, None], jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(bt), jnp.asarray(lengths),
            scale=1.0,
        )
    with pytest.raises(ValueError):
        paged_attention(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(bt), jnp.asarray(lengths)[:-1], scale=1.0,
        )
    # pools are lane-dense [N, P, H*D]: the unfolded [N, P, H, D] form is
    # refused by rank, and a q whose heads x head_dim does not fold to the
    # pools' lane axis by name
    unfolded = jnp.asarray(k_pages).reshape(16, 4, 2, 4)
    with pytest.raises(ValueError, match=r"rank 4, want 3"):
        paged_attention(
            jnp.asarray(q), unfolded, unfolded, jnp.asarray(bt),
            jnp.asarray(lengths), scale=1.0,
        )
    with pytest.raises(
        ValueError, match=r"axis 'heads\*head_dim': q has 2 x 2 = 4"
    ):
        paged_attention(
            jnp.asarray(q)[..., :2], jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(bt), jnp.asarray(lengths),
            scale=1.0,
        )


# ----------------------------------------------------------------- sampling


def test_device_sample_bitwise_matches_host_sampler():
    """serve/sampling.device_sample is the in-jit mirror of
    ``host_sample``: same token id for every (temperature, top_k, seed,
    step) cell, including greedy ties, k=0 (no truncation), k=1 and
    k >= vocab."""
    vocab = 32
    rng = np.random.default_rng(0)
    cases = [
        (0.0, 0), (0.0, 5),             # greedy ignores top_k
        (0.7, 0), (0.7, 5), (1.3, 1),
        (0.9, vocab + 100),             # oversized k = no truncation
    ]
    for seed in (0, 11):
        for step in (0, 1, 5):
            logits = rng.standard_normal((len(cases), vocab)).astype(
                np.float32
            )
            logits[0, 3] = logits[0, 7] = logits[0].max() + 1.0  # greedy tie
            temps = np.asarray([t for t, _ in cases], np.float32)
            top_ks = np.asarray([k for _, k in cases], np.int32)
            got = np.asarray(device_sample(
                jnp.asarray(logits),
                jnp.full((len(cases),), seed, jnp.int32),
                jnp.full((len(cases),), step, jnp.int32),
                jnp.asarray(temps), jnp.asarray(top_ks),
            ))
            for i, (temp, top_k) in enumerate(cases):
                want = host_sample(
                    logits[i], temperature=temp, top_k=top_k, seed=seed,
                    step=step,
                )
                assert int(got[i]) == want, (temp, top_k, seed, step)


# --------------------------------------------------------- engine identity


def _run_server(model, params, prompts, T, *, temperature=0.0, top_k=0,
                seed=0, first_alone=False, **cfg_kw):
    """Serve ``prompts`` (request ``i`` under ``seed + i``) through a
    2-slot engine; ``first_alone`` finishes the first before the rest are
    submitted (it seeds a prefix cache the others can hit)."""
    reg, sink = _registry()
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(4, 8, 16), max_new_tokens=T,
            **cfg_kw,
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(server.submit(
                p, max_new_tokens=T, temperature=temperature, top_k=top_k,
                seed=seed + i,
            ))
            if first_alone and i == 0:
                assert wait_until(reqs[0].done.is_set, timeout=120)
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        )
    finally:
        server.close()
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]
    return [np.asarray(r.tokens, np.int32) for r in reqs], server.stats()


def test_paged_greedy_token_identical_to_generate(lm):
    """Acceptance pin: the engine's greedy continuations are bit-identical
    to one-shot generate() (dense flax cache, lockstep: another program)
    at the exact prompt length."""
    model, params = lm
    T = 5
    prompts = _prompts(model, [3, 6, 9, 14, 5], seed=7)
    want = [
        np.asarray(generate(model, params, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]
    paged, pstats = _run_server(model, params, prompts, T)
    for i, (p_toks, ref) in enumerate(zip(paged, want)):
        np.testing.assert_array_equal(p_toks, ref, err_msg=f"paged req {i}")
    assert pstats["kv_layout"] == "paged" and pstats["kv_pages_peak"] > 0
    assert pstats["sampling"] == "device"


def _no_cache_logits(model, params, seqs):
    """fp32 ``[n, L, vocab]`` logits of the plain model (no cache, no
    engine) over ``seqs``: ONE jitted forward at one padded length
    (causal: padding after a position cannot reach it)."""
    ids = np.zeros((len(seqs), max(len(q) for q in seqs)), np.int32)
    for i, q in enumerate(seqs):
        ids[i, : len(q)] = q
    forward = jax.jit(lambda p, x: model.apply({"params": p}, x))
    return np.asarray(forward(params, ids), np.float32)


def assert_streams_are_host_samples(model, params, prompts, streams, *,
                                    temperature, top_k, seed):
    """Every served token ``t`` of request ``i`` is ``host_sample`` of the
    plain model's own logits at that position under ``(seed + i, step=t)``
    (``step``: tokens already emitted): the reference is independent of
    any engine, and holds the per-slot seed/step/temperature/top-k
    operands and the ``fold_in(key(seed), step)`` stream to it."""
    logits = _no_cache_logits(
        model, params, [np.concatenate([p, q]) for p, q in zip(prompts, streams)]
    )
    for i, (p, stream) in enumerate(zip(prompts, streams)):
        for t, token in enumerate(stream):
            want = host_sample(
                logits[i, len(p) - 1 + t], temperature=temperature,
                top_k=top_k, seed=seed + i, step=t,
            )
            assert int(token) == want, f"request {i} token {t}"


@pytest.mark.parametrize("engine", [
    dict(),
    dict(prefill_chunk=4),
    dict(prefix_cache=True, page_size=4),
    dict(spec_k=3),
], ids=["bucket", "prefill_chunk", "prefix_cache", "spec_k3"])
def test_sampled_stream_is_the_host_samplers_under_fixed_seed(lm, engine):
    """Fixed-key sampled decode is exact across the sampling location in
    every engine variant: per-bucket prefill, chunked prefill, a
    prefix-cache hit (tail through the chunk program) and the k+1-position
    verify block each emit the host sampler's token at every position."""
    model, params = lm
    T = 6
    rng = np.random.default_rng(3)
    shared = rng.integers(1, model.config.vocab_size, 9).astype(np.int32)
    prompts = [
        np.concatenate([
            shared[:n], rng.integers(1, model.config.vocab_size, m),
        ]).astype(np.int32)
        for n, m in ((9, 3), (9, 5), (0, 7))    # 12, 14 (shared 9), 7
    ]
    kw = dict(temperature=0.8, top_k=5, seed=11)
    streams, stats = _run_server(
        model, params, prompts, T, first_alone=True, **kw, **engine
    )
    assert all(len(q) == T for q in streams)
    if "prefix_cache" in engine:
        # the second prompt mapped the first's pages: two full ones and a
        # copy of the third, which they share up to mid-page
        assert stats["prefix_cache"]["prefix_hits"] == 1
        assert stats["prefix_cached_tokens"] == 9
        assert stats["prefix_cache"]["cow_copies"] == 1
    if "spec_k" in engine:
        assert stats["spec_dispatches"] > 0
    if "prefill_chunk" in engine:
        assert stats["prefill_chunks"] > len(prompts)
    assert_streams_are_host_samples(
        model, params, prompts, streams, **kw
    )


def test_page_exhaustion_backpressure_never_hangs(lm):
    """A pool holding ONE worst-case request at a time still drains a
    6-request burst: admission blocks on pages (page_exhausted ticks up),
    never wedges, and every answer is still greedy-exact."""
    model, params = lm
    T = 8
    prompts = _prompts(model, [8, 5, 8, 6, 7, 8], seed=1)
    want = [
        np.asarray(generate(model, params, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]
    reg, sink = _registry()
    # pages_per_slot = ceil((8+8)/4) = 4; num_pages=5 leaves 4 usable —
    # exactly one worst-case request's budget, despite 4 slots
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=4, prompt_buckets=(8,), max_new_tokens=T,
            page_size=4, num_pages=5,
        ),
        queue_depth=8, registry=reg,
    ).start()
    try:
        reqs = [server.submit(p, max_new_tokens=T) for p in prompts]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    for i, (req, ref) in enumerate(zip(reqs, want)):
        assert req.status == "done"
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), ref, err_msg=f"request {i}"
        )
    stats = server.stats()
    assert stats["page_exhausted"] > 0
    assert stats["kv_pages_used"] == 0 and stats["kv_pages_peak"] <= 4
    # eviction returned every page to the pool
    assert stats["kv_pages_free"] == 4


def test_mixed_context_pool_below_dense_equivalent(lm):
    """One engine admits a 1x-8x mixed-context workload through a pool
    SMALLER than num_slots x longest-context (what a buffer a slot would
    need: it charges every slot the longest context) and stays
    greedy-exact including the longest request."""
    model, params = lm
    T = 4
    lengths = [3, 4, 26, 32, 4, 20]
    prompts = _prompts(model, lengths, seed=9)
    want = [
        np.asarray(generate(model, params, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]
    reg, sink = _registry()
    page_size = 4
    pages_per_slot = -(-(32 + T) // page_size)          # 9
    dense_equiv = 4 * pages_per_slot                    # 36 usable pages
    num_pages = 20                                      # 19 usable < 36
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=4, prompt_buckets=(4, 32), max_new_tokens=T,
            page_size=page_size, num_pages=num_pages,
        ),
        queue_depth=8, registry=reg,
    ).start()
    try:
        reqs = [server.submit(p, max_new_tokens=T) for p in prompts]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    for i, (req, ref) in enumerate(zip(reqs, want)):
        assert req.status == "done"
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), ref,
            err_msg=f"request {i} (len {lengths[i]})",
        )
    stats = server.stats()
    assert stats["kv_pages_total"] == num_pages - 1 < dense_equiv
    peak = stats["kv_pages_peak"]
    assert peak > 0 and peak <= num_pages - 1
    # the per-tick pool gauges landed in the registry
    gauges = reg.snapshot()["gauges"]
    assert "serve/kv_pages_used" in gauges
    assert "serve/kv_pages_free" in gauges
    assert gauges["serve/kv_pages_used"] == 0.0  # everything evicted


# ------------------------------------------------- strict tick-wide scope


def test_strict_tick_scope_two_buckets_zero_implicit_transfers(lm):
    """Acceptance: with warmup=True every compiled program is warm before
    the first real tick, so the WHOLE tick body runs under
    transfer_guard("disallow") from request one — a 2-bucket mixed
    greedy/sampled session records ZERO implicit transfers and zero
    recompiles in strict mode."""
    from pytorch_distributed_training_tpu.analysis.guards import GuardSet

    model, params = lm
    reg, sink = _registry()
    gs = GuardSet(mode="strict", registry=reg)
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(4, 8), max_new_tokens=4,
            warmup=True,
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
    assert stats["compiled_prefill_buckets"] == [4, 8]
    assert stats["guard_mode"] == "strict"
    assert stats["guard_recompiles"] == 0
    assert stats["guard_implicit_transfers"] == 0
    assert not sink.of("recompile") and not sink.of("implicit_transfer")
    for name in ("serve_prefill_b4", "serve_prefill_b8", "serve_decode"):
        assert gs.wrapped[name].calls >= 2, name
    # the warmed decode program passed its strict collective manifest:
    # a single-device engine moves zero bytes between chips
    (comm,) = sink.of("comm_audit")
    assert comm["name"] == "serve_decode" and comm["ok"] is True
    assert comm["count"] == 0


# ------------------------------------------------- periodic lock summaries


@pytest.mark.concurrency
def test_periodic_lock_summary_emits_on_cadence_and_stops():
    from pytorch_distributed_training_tpu.analysis.concurrency import (
        start_periodic_summary,
    )
    from pytorch_distributed_training_tpu.analysis.concurrency.locks import (
        LockRegistry,
        lock,
    )

    reg, sink = _registry()
    lr = LockRegistry(mode="record")
    with lock("test.periodic", registry=lr):
        pass
    ps = start_periodic_summary(0.02, registry=reg, lock_registry=lr)
    try:
        deadline = time.monotonic() + 10
        while ps.emitted < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        ps.stop()
    stopped_at = ps.emitted
    assert stopped_at >= 3
    recs = sink.of("lock_summary")
    assert len(recs) >= 3
    assert all("test.periodic" in r["locks"] for r in recs)
    # stop() is bounded, idempotent, and halts emission
    ps.stop()
    time.sleep(0.08)
    assert ps.emitted == stopped_at

    with pytest.raises(ValueError, match="interval_s"):
        start_periodic_summary(0.0, registry=reg, lock_registry=lr)


# ------------------------------------------------ one layout, one sampler


@pytest.mark.parametrize("field,old,flag", [
    ("kv_layout", "dense", "--kv-layout dense"),
    ("sampling", "host", "--sampling host"),
])
def test_engine_config_refuses_the_removed_path_by_its_flag(field, old, flag):
    with pytest.raises(ValueError, match=f"{flag}: .*removed in PR 31"):
        EngineConfig(**{field: old})
    # the value that is left passes under its old name (the benchmark's
    # configurations still give it)
    only = getattr(EngineConfig(), field)
    assert getattr(EngineConfig(**{field: only}), field) == only


@pytest.mark.parametrize("flag,old,only", [
    ("--kv-layout", "dense", "paged"), ("--sampling", "host", "device"),
])
def test_clis_refuse_the_removed_value_before_anything_loads(
        flag, old, only, capsys):
    from pytorch_distributed_training_tpu.cli import fleet_lm, serve_lm

    with pytest.raises(SystemExit) as e:
        serve_lm.main(["--model", "gpt2-tiny", flag, old])
    assert e.value.code == 2
    assert f"argument {flag}: invalid choice: '{old}'" in capsys.readouterr().err
    dest = flag[2:].replace("-", "_")
    assert getattr(serve_lm.build_parser().parse_args([flag, only]), dest) == only
    # the fleet front end forwards neither flag: it has none to forward
    with pytest.raises(SystemExit) as e:
        fleet_lm.build_parser().parse_args([flag, only])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
