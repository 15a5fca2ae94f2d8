"""Shared-KV prefix cache tests (serve/prefix_cache.py, the refcount half
of serve/paged_cache.py and their engine integration): page refcount
lifecycle + misuse guards (double release, writing/freeing shared pages),
trie match/insert/evict edges, copy-on-write at a mid-page divergence,
the acceptance pin that cached-prefix streams are BIT-IDENTICAL to cold
prefill (greedy and fixed-seed sampled; plain, chunked and speculative
engines; tp=2 and weight-int8 variants), tenant-quota fairness, eviction
under page pressure never corrupting an in-flight stream, hot-swap
invalidation (post-swap streams never reuse pre-swap pages), the
multi-tenant trace mix determinism pin, and the telemetry surface
(gauges, admission-span attrs, /healthz page split). CPU, tier-1.
"""

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
from pytorch_distributed_training_tpu.serve.prefix_cache import PrefixCache
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.utils.config import model_preset

pytestmark = [pytest.mark.serve, pytest.mark.prefix]


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


def _shared_prompts(model, prefix_len, tail_lens, seed=0):
    """Prompts sharing one ``prefix_len``-token system prefix with random
    private tails — the workload the cache exists for."""
    rng = np.random.default_rng(seed)
    vocab = model.config.vocab_size
    prefix = rng.integers(1, vocab, prefix_len).astype(np.int32)
    return [
        np.concatenate([prefix, rng.integers(1, vocab, n).astype(np.int32)])
        for n in tail_lens
    ]


def _want(model, params, prompts, T):
    return [
        np.asarray(generate(model, params, p[None], max_new_tokens=T))[
            0, len(p):
        ]
        for p in prompts
    ]


def _serve_serial(server, prompts, T, **submit_kw):
    """Submit one at a time so the first request INSERTS before the rest
    match — deterministic hit pattern regardless of tick interleaving."""
    toks = []
    for i, p in enumerate(prompts):
        r = server.submit(p, max_new_tokens=T, **submit_kw)
        assert wait_until(r.done.is_set, timeout=120), r.status
        assert r.status == "done", r.status
        toks.append(np.asarray(r.tokens, np.int32))
    return toks


# ------------------------------------------------------ allocator refcounts


def test_refcount_acquire_share_release():
    alloc = PageAllocator(
        num_pages=9, page_size=4, pages_per_slot=4, num_slots=3
    )
    alloc.admit(0, 2)
    a, b = alloc.slot_pages(0)
    assert alloc.refcount(a) == 1 and alloc.pages_shared == 0

    # share page a into slot 1's row alongside a private page
    alloc.admit_shared(1, [a], 1)
    assert alloc.refcount(a) == 2 and alloc.pages_shared == 1
    assert alloc.block_table[1][0] == a
    # shared pages are not double-counted as used
    assert alloc.pages_used == 3

    # releasing the original holder must NOT free the shared page
    alloc.release(0)
    assert alloc.refcount(a) == 1 and alloc.refcount(b) == 0
    assert a not in alloc._free
    # the last holder's release finally frees it
    alloc.release(1)
    assert alloc.refcount(a) == 0 and alloc.pages_used == 0
    assert alloc.pages_free == 8


def test_refcount_misuse_guards():
    alloc = PageAllocator(
        num_pages=6, page_size=4, pages_per_slot=8, num_slots=2
    )
    alloc.admit(0, 2)
    a, b = alloc.slot_pages(0)

    # acquire only shares LIVE pages; out-of-range and the null page raise
    free_page = next(
        p for p in range(1, 6) if alloc.refcount(p) == 0
    )
    with pytest.raises(RuntimeError, match="free"):
        alloc.acquire(free_page)
    with pytest.raises(ValueError, match="out of range"):
        alloc.acquire(0)
    with pytest.raises(ValueError, match="out of range"):
        alloc.acquire(6)

    # double release of an already-free page raises (a freed id may be in
    # another slot's row — silence would corrupt it)
    alloc.release(0)
    with pytest.raises(RuntimeError, match="double release"):
        alloc.decref(a)

    # admit_shared misuse mirrors admit's guards
    alloc.admit(0, 1)
    (p,) = alloc.slot_pages(0)
    with pytest.raises(RuntimeError, match="already holds"):
        alloc.admit_shared(0, [p], 1)
    with pytest.raises(ValueError, match="block-table rows"):
        alloc.admit_shared(1, [p], 8)
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.admit_shared(1, [p], alloc.pages_free + 1)
    # failed admits leak nothing
    assert alloc.refcount(p) == 1 and alloc.slot_pages(1) == ()


def test_cow_repoints_private_copy_and_guards():
    alloc = PageAllocator(
        num_pages=6, page_size=4, pages_per_slot=8, num_slots=2
    )
    alloc.admit(0, 1)
    (shared,) = alloc.slot_pages(0)

    # writing an exclusively-held page needs no cow — calling it is a bug
    with pytest.raises(RuntimeError, match="exclusively-held"):
        alloc.cow(0, 0)

    alloc.admit_shared(1, [shared], 1)
    old, new = alloc.cow(1, 0)
    assert old == shared and new != shared
    assert alloc.block_table[1][0] == new
    assert alloc.slot_pages(1)[0] == new
    # the old page kept its other holder; the copy is private
    assert alloc.refcount(shared) == 1 and alloc.refcount(new) == 1
    assert alloc.pages_shared == 0

    # cow with a drained free list raises rather than corrupting
    alloc.release(1)
    alloc.admit_shared(1, [shared], alloc.pages_free)
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.cow(1, 0)


def test_release_order_lifo_free_list_preserved_under_sharing():
    """The refcount layer must not perturb the LIFO reuse order pinned by
    test_paged.py: a release returns a slot's pages such that a same-size
    re-admit gets the same pages back in the same order."""
    alloc = PageAllocator(
        num_pages=9, page_size=4, pages_per_slot=3, num_slots=2
    )
    alloc.admit(0, 3)
    first = alloc.slot_pages(0)
    alloc.release(0)
    alloc.admit(0, 3)
    assert alloc.slot_pages(0) == first


# ------------------------------------------------------------------- trie


def _trie(num_pages=16, page_size=4, num_slots=4):
    alloc = PageAllocator(
        num_pages=num_pages, page_size=page_size,
        pages_per_slot=num_pages, num_slots=num_slots,
    )
    return PrefixCache(alloc), alloc


def test_trie_insert_match_exact_and_partial():
    cache, alloc = _trie()
    toks = list(range(100, 110))            # 2 full pages + 2 leftover
    alloc.admit(0, 3)
    pages = alloc.slot_pages(0)

    # only FULL pages are indexed; the partial third page is not
    assert cache.insert(toks, pages) == 2
    assert cache.cached_pages == 2
    assert alloc.refcount(pages[0]) == 2 and alloc.refcount(pages[2]) == 1

    # exact full-page match, no cow source
    m = cache.match(toks[:8])
    assert m.hit and m.pages == pages[:2]
    assert m.cached_len == 8 and m.cow_src is None

    # mid-page divergence: 1 full page + 2 tokens into the second ->
    # the second page is the copy-on-write source
    m = cache.match(toks[:6] + [999, 998])
    assert m.pages == pages[:1] and m.cached_len == 6
    assert m.cow_src == pages[1]

    # divergence inside the FIRST page: no full pages, cow only
    m = cache.match(toks[:3] + [999])
    assert m.pages == () and m.cached_len == 3 and m.cow_src == pages[0]
    assert m.hit

    # total miss
    m = cache.match([1, 2, 3, 4, 5])
    assert not m.hit and m.pages == () and m.cow_src is None

    # note() is the only counter path — match alone never counts
    assert cache.hits == 0 and cache.misses == 0
    cache.note(True)
    cache.note(False)
    assert cache.stats()["prefix_hit_rate"] == 0.5


def test_trie_first_writer_wins_and_insert_guards():
    cache, alloc = _trie()
    toks = list(range(200, 208))
    alloc.admit(0, 2)
    alloc.admit(1, 2)
    p0, p1 = alloc.slot_pages(0), alloc.slot_pages(1)

    assert cache.insert(toks, p0) == 2
    # a duplicate insert from another slot creates nothing and bumps no
    # refcount — the first writer's pages stay canonical
    assert cache.insert(toks, p1) == 0
    assert cache.cached_pages == 2
    assert alloc.refcount(p1[0]) == 1

    # divergent second half: shares the first node, adds one
    toks2 = toks[:4] + [777, 778, 779, 780]
    alloc.admit(2, 2)
    p2 = alloc.slot_pages(2)
    assert cache.insert(toks2, p2) == 1
    assert cache.cached_pages == 3
    # the shared first page was NOT re-acquired (node already existed)
    assert alloc.refcount(p0[0]) == 2

    with pytest.raises(ValueError, match="full pages"):
        cache.insert(list(range(12)), p0[:2])


def test_trie_evict_lru_protect_and_idle():
    cache, alloc = _trie()
    runs = []
    for slot, base in enumerate((100, 200, 300)):
        toks = [base + i for i in range(8)]
        alloc.admit(slot, 2)
        cache.insert(toks, alloc.slot_pages(slot))
        runs.append((toks, alloc.slot_pages(slot)))
        alloc.release(slot)               # cache-only now (refcount 1)

    # freshen run 0 so run 1 is the LRU victim
    cache.match(runs[0][0])
    freed_before = alloc.pages_free
    assert cache.evict_until(1) == 1
    assert alloc.pages_free == freed_before + 1
    # leaf-first: the run's SECOND page went first
    assert alloc.refcount(runs[1][1][1]) == 0
    assert alloc.refcount(runs[1][1][0]) == 1

    # protect pins pages an in-progress match is about to map
    protected = set(runs[0][1])
    assert cache.evict_until(100, protect=protected) >= 1
    for page in protected:
        assert alloc.refcount(page) == 1    # survived a drain-everything

    # pages still referenced by a slot are never evictable
    alloc.admit_shared(3, list(runs[0][1]), 0)
    assert cache.evict_until(100) == 0
    assert cache.cached_pages == 2

    # evict_idle drops every cache-only run; slot-shared entries survive
    alloc.release(3)
    assert cache.evict_idle() == 2
    assert cache.cached_pages == 0
    assert alloc.pages_used == 0


def test_trie_invalidate_all_keeps_inflight_pages_alive():
    cache, alloc = _trie()
    toks = list(range(50, 58))
    alloc.admit(0, 2)
    cache.insert(toks, alloc.slot_pages(0))
    shared = alloc.slot_pages(0)

    # slot 1 shares the cached run (an in-flight hit) when the flush lands
    alloc.admit_shared(1, list(shared), 0)
    dropped = cache.invalidate_all()
    assert dropped == 2 and cache.cached_pages == 0
    assert cache.stats()["prefix_invalidations"] == 1

    # the in-flight slots keep their pages; nothing was freed under them
    assert alloc.refcount(shared[0]) == 2
    assert not cache.match(toks[:8]).hit
    alloc.release(0)
    alloc.release(1)
    assert alloc.pages_used == 0


# -------------------------------------------------- engine: cached == cold


def _run_prefix_server(model, params, prompts, T, *, registry=None,
                       submit_kw=None, **cfg_kw):
    reg, sink = (registry, None) if registry is not None else _registry()
    cfg_kw.setdefault("prompt_buckets", (24,))
    cfg_kw.setdefault("page_size", 4)
    cfg_kw.setdefault("num_pages", 64)
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, max_new_tokens=T, kv_layout="paged",
            sampling="device", prefix_cache=True, **cfg_kw,
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        toks = _serve_serial(server, prompts, T, **(submit_kw or {}))
    finally:
        server.close()
    return toks, server.stats(), sink, server


@pytest.mark.parametrize("variant", ["plain", "chunked", "spec"])
def test_cached_greedy_bit_identical_to_cold_with_cow(lm, variant):
    """THE acceptance pin: streams served from cached prefixes are
    token-identical to one-shot generate() — with the shared prefix
    deliberately NOT page-aligned (14 tokens, page_size 4) so every hit
    exercises the copy-on-write path — across the plain, chunked-prefill
    and speculative engines."""
    model, params = lm
    T = 5
    cfg_kw = {
        "plain": {},
        "chunked": dict(prefill_chunk=4),
        "spec": dict(spec_k=2, spec_draft="ngram"),
    }[variant]
    prompts = _shared_prompts(model, 14, [4, 6, 3], seed=3)
    want = _want(model, params, prompts, T)
    toks, stats, _, _ = _run_prefix_server(
        model, params, prompts, T, **cfg_kw
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"{variant} req {i}")
    pc = stats["prefix_cache"]
    assert pc["prefix_hits"] == 2 and pc["prefix_lookups"] == 3
    assert pc["cow_copies"] == 2          # 14 % 4 != 0: every hit COWs
    assert stats["page_exhausted"] == 0
    # each hit skipped at least the 12 fully-paged shared tokens
    cold = sum(len(p) for p in prompts)
    assert stats["prefill_tokens"] <= cold - 2 * 12


def test_cached_sampled_fixed_seed_identical_to_cache_off(lm):
    """Fixed-seed sampled decode is exact across the cache: the same
    submissions through a prefix_cache engine and a cache-off engine yield
    identical tokens (device sampling keys on (seed, position) only)."""
    model, params = lm
    T = 6
    prompts = _shared_prompts(model, 12, [5, 7, 4], seed=11)
    kw = dict(temperature=0.8, top_k=5, seed=9)
    cached, stats, _, _ = _run_prefix_server(
        model, params, prompts, T, submit_kw=kw
    )
    assert stats["prefix_cache"]["prefix_hits"] == 2

    reg, _ = _registry()
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(24,), max_new_tokens=T,
            kv_layout="paged", sampling="device", page_size=4, num_pages=64,
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        cold = _serve_serial(server, prompts, T, **kw)
    finally:
        server.close()
    for i, (a, b) in enumerate(zip(cached, cold)):
        assert len(a) == T
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


@pytest.mark.tp
def test_cached_tp2_bit_identical_to_generate(lm):
    """tp=2: the head-sharded engine's cache-hit streams (COW copies over
    page-leading sharded pools included) stay greedy-exact."""
    model, params = lm
    T = 5
    prompts = _shared_prompts(model, 14, [4, 6], seed=5)
    want = _want(model, params, prompts, T)
    toks, stats, _, _ = _run_prefix_server(
        model, params, prompts, T, tp=2
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"tp2 req {i}")
    pc = stats["prefix_cache"]
    assert pc["prefix_hits"] == 1 and pc["cow_copies"] == 1


def test_cached_int8_weights_bit_identical_on_snapped_grid(lm):
    """Weight-only int8 + prefix cache: on int8-grid weights the cached
    streams match the fp32 reference bit-for-bit (the COW copy must also
    cover the int8 engine's pool tree)."""
    from pytorch_distributed_training_tpu.ops.quant import (
        dequantize_serve_params,
        quantize_serve_params,
    )

    model, params = lm
    snapped = dequantize_serve_params(quantize_serve_params(params))
    T = 5
    prompts = _shared_prompts(model, 14, [4, 5], seed=13)
    want = _want(model, snapped, prompts, T)
    toks, stats, _, _ = _run_prefix_server(
        model, snapped, prompts, T, weights_dtype="int8"
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"int8 req {i}")
    assert stats["variant"] == "int8"
    assert stats["prefix_cache"]["prefix_hits"] == 1


# ------------------------------------------------------------ tenant lanes


def test_queue_tenant_lanes_blocked_tenant_does_not_freeze_others():
    from pytorch_distributed_training_tpu.serve.queue import (
        GenRequest,
        RequestQueue,
    )

    q = RequestQueue(max_depth=8, prompt_buckets=(8,), max_new_tokens=4)

    def sub(rid, tenant):
        return q.submit(GenRequest(
            id=rid, prompt_ids=np.ones(3, np.int32), max_new_tokens=4,
            tenant=tenant,
        ))

    a1, a2 = sub("a1", "ta"), sub("a2", "ta")
    b1 = sub("b1", "tb")

    # tenant ta's head is rejected: its OWN later request may not bypass
    # it, but tenant tb's head (submitted after both) still pops
    popped = q.pop_ready(accept=lambda r: r.tenant != "ta")
    assert popped is b1
    assert q.depth() == 2

    # once ta unblocks, its requests drain in submit order
    assert q.pop_ready() is a1
    assert q.pop_ready() is a2
    assert q.pop_ready() is None

    # tenantless traffic keeps the historical strict-FIFO no-bypass rule
    c1, c2 = sub("c1", None), sub("c2", None)
    assert q.pop_ready(accept=lambda r: r is not c1) is None
    assert q.pop_ready() is c1 and q.pop_ready() is c2

    with pytest.raises(ValueError, match="tenant"):
        q.submit(GenRequest(
            id="bad", prompt_ids=np.ones(3, np.int32), max_new_tokens=4,
            tenant="",
        ))


def test_tenant_quota_holds_flood_without_page_exhaustion(lm):
    """A tenant over its private-page quota is HELD (tenant_blocked ticks
    up, page_exhausted does not) while other tenants keep being served;
    the flood drains once its own slots release pages."""
    model, params = lm
    T = 4
    prompts = _shared_prompts(model, 8, [4, 5, 3, 6, 4], seed=17)
    reg, sink = _registry()
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(16,), max_new_tokens=T,
            kv_layout="paged", sampling="device", page_size=4,
            num_pages=33,                   # 32 usable
            prefix_cache=True, tenant_page_quota=0.1875,  # 6 pages/tenant
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        # flood tenant: the cold head reserves ceil((16+4)/4)=5 private
        # pages and a hit still needs 3 fresh tail pages, so two ta
        # requests in flight (>= 8) breach the 6-page quota — the quota
        # serializes them while tb rides alongside
        reqs = [
            server.submit(p, max_new_tokens=T,
                          tenant="ta" if i != 2 else "tb")
            for i, p in enumerate(prompts)
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    assert all(r.status == "done" for r in reqs)
    want = _want(model, params, prompts, T)
    for i, (req, ref) in enumerate(zip(reqs, want)):
        np.testing.assert_array_equal(
            np.asarray(req.tokens, np.int32), ref, err_msg=f"request {i}"
        )
    stats = server.stats()
    assert stats["prefix_cache"]["tenant_blocked"] > 0
    assert stats["page_exhausted"] == 0     # quota holds are not exhaustion
    assert stats["prefix_cache"]["tenant_page_quota"] == 0.1875


# ------------------------------------------- eviction + swap invalidation


def test_eviction_under_pressure_never_corrupts_streams(lm):
    """A pool too small to hold the cache AND fresh admissions LRU-evicts
    idle cached runs instead of blocking: a cold foreign-prefix request
    forces eviction of the resident runs, later same-prefix hits force
    eviction WHILE their own matched pages must be protected — and every
    stream stays greedy-exact with zero page_exhausted."""
    model, params = lm
    T = 4
    # 8 usable pages; every request reserves ceil((16+4)/4) = 5, a
    # finished prompt leaves 2-3 cached pages behind -> from the third
    # admission on, free pages only exist by evicting cached runs
    shared_a = _shared_prompts(model, 8, [4, 5, 6, 3], seed=23)
    foreign = _shared_prompts(model, 8, [4], seed=24)
    prompts = shared_a[:2] + foreign + shared_a[2:]
    want = _want(model, params, prompts, T)
    toks, stats, _, _ = _run_prefix_server(
        model, params, prompts, T,
        prompt_buckets=(16,), num_pages=9,
    )
    for i, (got, ref) in enumerate(zip(toks, want)):
        np.testing.assert_array_equal(got, ref, err_msg=f"request {i}")
    pc = stats["prefix_cache"]
    assert pc["prefix_evictions"] > 0
    assert pc["prefix_hits"] >= 2           # eviction didn't kill sharing
    assert stats["page_exhausted"] == 0
    assert stats["kv_pages_used"] == pc["prefix_cached_pages"]


def test_hotswap_invalidates_prefix_index(lm):
    """Cached KV is a function of the weights that wrote it: a hot-swap
    flushes the whole index, so a post-swap repeat of a pre-swap prompt is
    a MISS served entirely by the new weights (and never maps a pre-swap
    page)."""
    model, params = lm
    pB = jax.tree.map(lambda x: x + 0.01 * jnp.sign(x), params)
    T = 5
    prompts = _shared_prompts(model, 12, [4, 6], seed=29)
    reg, sink = _registry()
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(24,), max_new_tokens=T,
            kv_layout="paged", sampling="device", page_size=4, num_pages=64,
            prefix_cache=True,
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        pre = _serve_serial(server, prompts, T)
        np.testing.assert_array_equal(pre[0], _want(model, params, prompts, T)[0])
        assert server.stats()["prefix_cache"]["prefix_hits"] == 1

        ticket = server.engine.request_swap(pB, 2)
        assert ticket.done.wait(60) and ticket.ok

        # the same prompts again: pure misses on a flushed index, streams
        # token-identical to the NEW weights' cold answers
        post = _serve_serial(server, prompts, T)
        stats = server.stats()
    finally:
        server.close()
    want_b = _want(model, pB, prompts, T)
    for i, (got, ref) in enumerate(zip(post, want_b)):
        np.testing.assert_array_equal(got, ref, err_msg=f"post-swap req {i}")
    pc = stats["prefix_cache"]
    assert pc["prefix_invalidations"] == 1
    # post-swap: one fresh miss then one fresh hit (rebuilt from new-weight
    # pages) — the pre-swap entries contributed nothing
    assert pc["prefix_lookups"] == 4 and pc["prefix_hits"] == 2
    # the weights actually moved (guards against a vacuous identity)
    assert not np.array_equal(pre[0], post[0])


# ------------------------------------------------------ telemetry surface


def test_prefix_gauges_span_attrs_and_health_page_split(lm):
    model, params = lm
    T = 4
    prompts = _shared_prompts(model, 12, [4, 5], seed=31)
    reg, sink = _registry()
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(24,), max_new_tokens=T,
            kv_layout="paged", sampling="device", page_size=4, num_pages=64,
            prefix_cache=True,
        ),
        queue_depth=16, registry=reg,
    ).start()
    try:
        r0 = server.submit(prompts[0], max_new_tokens=T)
        assert wait_until(r0.done.is_set, timeout=120)
        health_mid = server.health()
        r1 = server.submit(prompts[1], max_new_tokens=T)
        assert wait_until(r1.done.is_set, timeout=120)
    finally:
        server.close()

    # gauges landed
    gauges = reg.snapshot()["gauges"]
    for name in ("serve/prefix_hit_rate", "serve/pages_shared",
                 "serve/cow_copies"):
        assert name in gauges, name
    assert gauges["serve/prefix_hit_rate"] == 0.5

    # the admission span carries the hit attribution
    from pytorch_distributed_training_tpu.telemetry.spans import (
        spans_by_trace,
    )

    traces = spans_by_trace(sink.records)
    adm0 = {s["name"]: s for s in traces[r0.id]}["admission"]
    adm1 = {s["name"]: s for s in traces[r1.id]}["admission"]
    assert adm0["attrs"]["prefix_hit"] is False
    assert adm0["attrs"]["cached_tokens"] == 0
    assert adm1["attrs"]["prefix_hit"] is True
    assert adm1["attrs"]["cached_tokens"] == 12

    # /healthz exposes the shared/free page split beside the load fields
    assert health_mid["kv_pages_shared"] == 0     # cached, not yet shared
    assert health_mid["kv_pages_free"] > 0
    st = server.stats()
    assert st["kv_pages_shared"] == 0             # both requests finished
    assert st["prefix_cache"]["pages_shared"] == 0


# ------------------------------------------------------- trace tenant mix


def test_trace_tenant_mix_deterministic_and_single_tenant_unchanged():
    from pytorch_distributed_training_tpu.serve.trace import (
        TraceConfig,
        generate_trace,
        trace_stats,
    )

    # the legacy pin, extended: tenants=0 must consume the IDENTICAL rng
    # stream as before the field existed — same config, same events, no
    # tenant fields set
    base = TraceConfig(seed=4, duration_s=6.0)
    a, b = generate_trace(base), generate_trace(base)
    assert a == b
    assert all(ev.tenant is None and ev.prefix_len == 0 for ev in a)

    mix = TraceConfig(
        seed=4, duration_s=6.0, tenants=3, shared_prefix_len=16,
    )
    m1, m2 = generate_trace(mix), generate_trace(mix)
    assert m1 == m2 and len(m1) > 0
    names = {ev.tenant for ev in m1}
    assert names <= {"tenant0", "tenant1", "tenant2"} and len(names) >= 2
    for ev in m1:
        assert ev.prefix_len == 16
        # shared prefix + at least one private token, still bounded
        assert ev.prompt_len >= 17
        assert ev.prompt_len <= max(mix.prompt_len_max, 17)
    st = trace_stats(m1)
    assert sum(st["by_tenant"].values()) == len(m1)

    with pytest.raises(ValueError, match="tenants"):
        TraceConfig(tenants=-1)
    with pytest.raises(ValueError, match="shared_prefix_len"):
        TraceConfig(tenants=2, shared_prefix_len=0)


# ------------------------------------------------------------ config guards


def test_prefix_cache_config_validation():
    with pytest.raises(ValueError, match="--kv-layout dense: .*PR 31"):
        EngineConfig(kv_layout="dense", prefix_cache=True)
    with pytest.raises(ValueError, match="--sampling host: .*PR 31"):
        EngineConfig(
            kv_layout="paged", sampling="host", prefix_cache=True,
        )
    with pytest.raises(ValueError, match="tenant_page_quota"):
        EngineConfig(kv_layout="paged", tenant_page_quota=1.5)
    with pytest.raises(ValueError, match="tenant_page_quota"):
        EngineConfig(
            kv_layout="paged", sampling="device", tenant_page_quota=0.5,
        )
