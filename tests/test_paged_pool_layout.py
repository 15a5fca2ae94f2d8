"""The paged K/V pools keep ONE device layout from parameter to donated
result — pinned without a chip.

XLA:TPU chooses a parameter's device layout from its shape. With trailing
``[16 heads, 64 head_dim]`` axes a ``bf16[3073, 16, 16, 64]`` pool got the
PAGE axis on the lanes (``{0,3,2,1}``): no scatter or gather can work page
by page there, so every program relaid all 48 pools out to row-major before
a one-token write and back after it — 96 pool-sized copies a decode tick and
a prefill (the chip's trace, ``benchmarks/traces/gpt2_medium_decode.json``).
The pools are lane-dense now, ``[3073, 16, 1024]``, whose default layout IS
row-major. These tests compile the engine's real decode program, one prefill
bucket and the copy-on-write page copy at the benchmark cell's shapes (48
slots, 3,073 pages of 16, gpt2-medium, bf16) for a described ``v5e:2x2`` and
hold the compiled text to that: row-major pool parameters, no ``copy``,
``transpose`` or ``convert`` of a pool's size, every donated pool aliased.
One case builds the old 4-D pool by hand and checks that the detector DOES
count its two copies, so the pin cannot pass by looking at nothing.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from pytorch_distributed_training_tpu.analysis.guards import (
    GuardSet,
    count_aliased_buffers,
)
from pytorch_distributed_training_tpu.analysis.spmd.hlo import count_relayouts
from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
from pytorch_distributed_training_tpu.serve.engine import (
    DecodeEngine,
    EngineConfig,
)
from pytorch_distributed_training_tpu.serve.queue import RequestQueue
from pytorch_distributed_training_tpu.utils.config import model_preset

from test_guards import ListSink  # sibling module (pytest sys.path)

pytestmark = [pytest.mark.serve]

SLOTS, PAGE, BUCKETS, MAX_NEW = 48, 16, (64, 128, 256, 512), 512
LAYERS, HEADS, HEAD_DIM = 24, 16, 64
PAGES = SLOTS * (BUCKETS[-1] + MAX_NEW) // PAGE + 1  # 3,073 with the null page

# `%pools__... = bf16[3073,16,1024]{2,1,0:T(8,128)(2,1)} parameter(5)`
_PARAMETER_RE = re.compile(
    r"^\s*%?[\w.-]+\s*=\s*\w+\[(?P<dims>[0-9,]+)\]\{(?P<order>[0-9,]*)"
    r"[^}]*\}\s+parameter\("
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
    except Exception as e:  # noqa: BLE001 - whatever libtpu says, it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _gpt2_engine(preset, **config):
    """An engine on zero weights (only shapes matter to a compile)."""
    model = GPT2LMModel(model_preset(preset))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))
    )["params"]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    econf = EngineConfig(kv_layout="paged", sampling="device", **config)
    queue = RequestQueue(
        max_depth=16, prompt_buckets=econf.prompt_buckets,
        max_new_tokens=econf.max_new_tokens,
    )
    return model, params, econf, queue


@pytest.fixture(scope="module")
def cell_engine():
    """The benchmark cell's engine (gpt2_medium_paged), not warmed: its
    programs are compiled below for the described chip, not for this host."""
    model, params, econf, queue = _gpt2_engine(
        "gpt2-medium", num_slots=SLOTS, prompt_buckets=BUCKETS,
        max_new_tokens=MAX_NEW, page_size=PAGE, warmup=False,
    )
    engine = DecodeEngine(model, params, econf, queue)
    leaves = jax.tree.leaves(engine._cache)
    assert len(leaves) == 2 * LAYERS
    assert {(x.shape, x.dtype) for x in leaves} == {
        ((PAGES, PAGE, HEADS * HEAD_DIM), jnp.dtype(jnp.bfloat16))
    }
    return engine


def _program(engine, name):
    """(jitted program, its warm-up operands) as ``_warmup`` calls it."""
    S, W = engine.config.num_slots, engine.config.pages_per_slot
    i32, f32 = np.int32, np.float32
    if name == "decode":
        # (previous ids, fresh mask, tokens, block table, context, seeds,
        # steps, temperatures, top-ks)
        ops = (np.zeros((S,), i32), np.ones((S,), np.bool_),
               np.zeros((S,), i32), np.zeros((S, W), i32), np.zeros((S,), i32),
               np.zeros((S,), i32), np.zeros((S,), i32), np.zeros((S,), f32),
               np.zeros((S,), i32))
        return engine._decode_step_fn(), (engine._params, engine._cache, *ops)
    if name == "page_copy":
        return engine._copy_fn(), (engine._cache, i32(0), i32(0))
    bucket = int(name.split("_")[1])
    ops = (np.zeros((1, bucket), i32), i32(1), np.zeros((1, W), i32), i32(0),
           f32(0.0), i32(0))
    return engine._prefill_fn(bucket), (engine._params, engine._cache, *ops)


def _compile_for(sharding, fn, args) -> str:
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args,
    )
    return fn.lower(*specs).compile().as_text()


def _pool_parameter_orders(text, elements):
    """minor-to-major order of every ENTRY parameter of `elements` elements."""
    entry = text[text.index("\nENTRY"):]
    orders = []
    for line in entry.splitlines():
        m = _PARAMETER_RE.match(line)
        if m and math.prod(int(d) for d in m["dims"].split(",")) == elements:
            orders.append(m["order"])
    return orders


@pytest.mark.parametrize("program", ["decode", "prefill_128", "page_copy"])
def test_cell_programs_never_relayout_a_pool(one_chip, cell_engine, program):
    fn, args = _program(cell_engine, program)
    text = _compile_for(one_chip, fn, args)
    pool = PAGES * PAGE * HEADS * HEAD_DIM
    orders = _pool_parameter_orders(text, pool)
    # all 48 pools are parameters, each in the row-major layout (page axis
    # major, the 1024 folded heads x head_dim minor-most, on the lanes)
    assert orders == ["2,1,0"] * (2 * LAYERS), orders
    assert count_relayouts(text, {pool}) == 0
    # and every donated pool still comes home in its parameter's buffer
    assert count_aliased_buffers(text) == 2 * LAYERS


@pytest.fixture(scope="module")
def decode_text_on_one_chip(one_chip, cell_engine):
    """The cell's decode program compiled with the kernel dispatch gate
    answering as one TPU device does. The engine keeps one jitted decode
    program and jax one trace of it, so the traces made under the other
    answer are dropped before and after."""
    from pytorch_distributed_training_tpu.ops import dispatch

    fn, args = _program(cell_engine, "decode")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(dispatch, "mode", lambda: "direct")
        jax.clear_caches()
        dispatch.DISPATCH_PATHS.clear()
        try:
            text = _compile_for(one_chip, fn, args)
            paths = dict(dispatch.DISPATCH_PATHS)
        finally:
            dispatch.DISPATCH_PATHS.clear()
            jax.clear_caches()
    assert paths.get("paged_attn:direct") == LAYERS, paths
    assert "paged_attn:xla" not in paths, paths
    return text


def test_decode_program_on_one_chip_reads_through_the_page_walk(
    decode_text_on_one_chip,
):
    """With the gate at "direct" the cell's decode program holds one
    ``paged_attn`` Mosaic call a layer, and nothing of the XLA formula's
    ``[48, 1024, 16, 64]`` window (gathered, unfolded, widened) in any
    dtype or axis order."""
    text = decode_text_on_one_chip
    calls = [
        line for line in text.splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line
        and "paged_attn" in line
    ]
    assert len(calls) == LAYERS, len(calls)
    window = SLOTS * (BUCKETS[-1] + MAX_NEW) * HEADS * HEAD_DIM
    sized = [
        m.group(0)
        for m in re.finditer(r"\b(?:bf16|f32|f16|s8|s32)\[([0-9,]+)\]", text)
        if math.prod(int(d) for d in m.group(1).split(",")) == window
    ]
    assert not sized, sorted(set(sized))


def test_decode_program_on_one_chip_keeps_the_pools_in_place(
    decode_text_on_one_chip,
):
    """The kernel takes the pools as they lie: still 48 row-major
    parameters, no pool-sized copy, transpose or convert around the 24
    calls, every donated pool aliased."""
    text = decode_text_on_one_chip
    pool = PAGES * PAGE * HEADS * HEAD_DIM
    assert _pool_parameter_orders(text, pool) == ["2,1,0"] * (2 * LAYERS)
    assert count_relayouts(text, {pool}) == 0
    assert count_aliased_buffers(text) == 2 * LAYERS


def test_detector_counts_the_copies_around_a_4d_pool(one_chip):
    """The parent's shape, built by hand: a ``[3073, 16, 16, 64]`` pool's
    default layout puts the pages on the lanes, and a one-token-per-slot
    write costs a relayout out and one back — which the detector counts."""
    shape = (PAGES, PAGE, HEADS, HEAD_DIM)

    def write(k_pool, v_pool, page_ids, offs, k, v):
        return (k_pool.at[page_ids, offs].set(k),
                v_pool.at[page_ids, offs].set(v))

    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32)
    token = jax.ShapeDtypeStruct((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    text = _compile_for(
        one_chip, jax.jit(write, donate_argnums=(0, 1)),
        (pool, pool, ids, ids, token, token),
    )
    orders = _pool_parameter_orders(text, math.prod(shape))
    assert orders == ["0,3,2,1"] * 2, orders
    assert count_relayouts(text, {math.prod(shape)}) == 2 * 2


def test_count_relayouts_reads_plain_async_and_fused_forms():
    """The instruction forms XLA:TPU writes (lines of the chip's dump of the
    parent's decode program, and their async and fused kin): counted by
    the result's element count, a `-done` half never twice."""
    text = """
%fused_computation.7 (p: bf16[3073,16,16,64]) -> f32[3073,16,16,64] {
  ROOT %convert.9 = f32[3073,16,16,64]{3,2,1,0} convert(%p)
}
ENTRY %main {
  %k = bf16[3073,16,16,64]{0,3,2,1:T(8,128)(2,1)} parameter(0)
  %copy.217 = bf16[3073,16,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%k), sharding={replicated}
  %copy-start.1 = (bf16[3073,16,16,64]{0,3,2,1:T(8,128)(2,1)}, bf16[3073,16,16,64]{3,2,1,0}, u32[]{:S(2)}) copy-start(%copy.217)
  %copy-done.1 = bf16[3073,16,16,64]{0,3,2,1:T(8,128)(2,1)} copy-done(%copy-start.1)
  %transpose.3 = bf16[16,3073,16,64]{3,2,1,0} transpose(%k), dimensions={1,0,2,3}
  %copy.5 = bf16[48,16,64]{2,1,0} copy(%token)
  %fusion.52 = bf16[3073,16,16,64]{3,2,1,0} fusion(%copy.217, %ids), kind=kLoop
}
"""  # noqa: E501 - lines as the compiler writes them
    assert count_relayouts(text, {3073 * 16 * 16 * 64}) == 4
    assert count_relayouts(text, {48 * 16 * 64}) == 1
    assert count_relayouts(text, ()) == 0


def test_relayout_counter_reaches_the_audit_record_and_the_stats():
    """CPU: a warmed engine's one comm audit of its hot program carries
    ``kv_pool_relayout_ops``, counted from the compiled text the audit
    already fetched, and ``engine.stats()`` reports the same number."""
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    registry, sink = MetricsRegistry(), ListSink()
    registry.attach_sink(sink)
    model, params, econf, queue = _gpt2_engine(
        "gpt2-tiny", num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
        page_size=4, warmup=True,
    )
    engine = DecodeEngine(
        model, params, econf, queue, registry=registry,
        guards=GuardSet(mode="strict", registry=registry),
    )
    (audit,) = sink.of("comm_audit")
    assert audit["name"] == "serve_decode" and audit["ok"]
    pools = {math.prod(x.shape) for x in jax.tree.leaves(engine._cache)}
    assert set(audit["kv_pool_elements"]) == pools
    # XLA:CPU widens a bf16 pool to fp32 around its scatter, so this
    # backend's count is its own; the chip's is pinned to 0 above
    count = audit["kv_pool_relayout_ops"]
    assert isinstance(count, int) and count >= 0
    assert engine.stats()["kv_pool_relayout_ops"] == count
    # before any audit there is nothing to report, and that reads None
    model, params, econf, queue = _gpt2_engine(
        "gpt2-tiny", num_slots=2, prompt_buckets=(8,), max_new_tokens=4,
        page_size=4, warmup=False,
    )
    cold = DecodeEngine(model, params, econf, queue, registry=registry)
    assert cold.stats()["kv_pool_relayout_ops"] is None
