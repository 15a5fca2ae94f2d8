"""What the comm audit reads out of a compiled program's text besides its
collectives (`analysis/spmd/hlo.py`): the instructions under the model's
named scopes, which tie a device trace's events (named by instruction, no
metadata) back to `jax.named_scope`, and whole-buffer copies that only
change the memory space, counted apart from relayouts."""

import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
    count_kernel_calls,
    count_relayouts,
    count_row_gathers,
    count_space_moves,
    scope_instructions,
)
from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
    CommManifest,
    comm_audit,
)

TEXT = """
%fused_computation.7 (p: bf16[8,128]) -> bf16[8,128] {
  %inner.1 = bf16[8,128]{1,0} add(%p, %p), metadata={op_name="jit(f)/M/moe.experts/add"}
}
ENTRY %main.107 (pool.1: bf16[11777,16,128]) -> bf16[8,128] {
  %pool = bf16[11777,16,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  %sort.1 = (f32[48,64]{1,0}, s32[48,64]{1,0}) sort(%a, %b), metadata={op_name="jit(f)/M/layer_0/sparse_attn.topk/sort" source_file="x.py"}
  %fusion.7 = bf16[8,128]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(f)/M/layer_1/experts/moe/moe.experts/dot_general"}
  %fusion.8 = bf16[8,128]{1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(f)/M/layer_1/experts/moe/moe.route/top_k"}
  %fusion.9 = bf16[8,128]{1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(f)/M/layer_1/almost_moe/add"}
  %copy-start.10 = (bf16[11777,16,128]{2,1,0:T(8,128)(2,1)}, bf16[11777,16,128]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%fusion.55)
  %copy-done.10 = bf16[11777,16,128]{2,1,0:T(8,128)(2,1)} copy-done(%copy-start.10)
  %copy-start.11 = (bf16[11777,16,128]{0,2,1:T(8,128)(2,1)}, bf16[11777,16,128]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%pool)
  %copy.12 = bf16[11777,16,128]{2,1,0} copy(%pool)
}
"""  # noqa: E501 - lines as the compiler writes them
POOL = 11777 * 16 * 128
# a decode program's row fetches: two Mosaic calls of the kernel, one of
# another kernel whose name starts the same, and an XLA gather of rows left
KERNEL_TEXT = """
ENTRY %main.9 (pool.1: bf16[11777,16,2560]) -> bf16[8,128] {
  %row_fetch = bf16[48,2048,2560]{2,1,0:T(8,128)(2,1)} custom-call(%c, %w, %t, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/M/layer_0/sparse_attn.gather/jit(_row_fetch)/row_fetch/pallas_call"}, backend_config={"x":1}
  %row_fetch.3 = bf16[64,513,3456]{2,1,0:T(8,128)(2,1)} custom-call(%c, %w, %t, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/M/layer_2/window_attn/jit(_row_fetch)/row_fetch/pallas_call"}
  %row_fetcher.1 = bf16[8,128]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/M/layer_0/sparse_attn.gather/row_fetcher"}
  %gather.4 = bf16[48,1,2048,2560]{3,2,1,0} gather(%pool, %rows), offset_dims={3}, slice_sizes={1,2560}, metadata={op_name="jit(f)/M/layer_4/sparse_attn.gather/gather"}
}
"""  # noqa: E501 - lines as the compiler writes them


def test_scope_instructions_lists_entry_instructions_by_scope():
    scopes = scope_instructions(TEXT, ("sparse_attn.topk", "sparse_attn.gather", "moe"))
    assert scopes == {"sparse_attn.topk": ["sort.1"], "sparse_attn.gather": [],
                      "moe": ["fusion.7", "fusion.8"]}
    # the first matching scope takes an instruction; fusion bodies are out
    assert scope_instructions(TEXT, ("moe.route", "moe")) == {
        "moe.route": ["fusion.8"], "moe": ["fusion.7"]}
    assert scope_instructions(TEXT, ()) == {}


def test_copies_between_memory_spaces_in_one_layout_are_a_part_of_the_count():
    # copy-start.10 (one layout, another memory space), copy-start.11, copy.12
    assert count_relayouts(TEXT, {POOL}) == 3
    assert count_space_moves(TEXT, {POOL}) == 1        # copy-start.10
    assert count_space_moves(TEXT, {5}) == 0 and count_space_moves(TEXT, ()) == 0


def test_kernel_calls_are_counted_by_name_and_scope_beside_the_gathers():
    """`latent_row_fetches` / `window_row_fetches`: the Mosaic calls named
    `row_fetch` under a scope (not a kernel whose name merely starts so),
    beside `count_row_gathers`' XLA gathers of rows left."""
    assert count_kernel_calls(KERNEL_TEXT, "sparse_attn.gather", "row_fetch") == 1
    assert count_kernel_calls(KERNEL_TEXT, "window_attn", "row_fetch") == 1
    assert count_kernel_calls(KERNEL_TEXT, "sparse_attn.topk", "row_fetch") == 0
    assert count_kernel_calls(TEXT, "sparse_attn.gather", "row_fetch") == 0
    assert count_row_gathers(KERNEL_TEXT, "sparse_attn.gather", 640) == 1
    assert count_row_gathers(KERNEL_TEXT, "window_attn", 1152) == 0


def test_the_audit_writes_the_scopes_of_a_real_program():
    records = []

    class Registry:
        sink = None

        def emit(self, record):
            records.append(record)

        def inc(self, *a, **k):
            pass

    def f(x):
        with jax.named_scope("outer.part"):
            y = jnp.sin(x) @ x
        with jax.named_scope("other"):
            return y + 1.0

    compiled = jax.jit(f).lower(jnp.ones((8, 8))).compile()
    manifest = CommManifest("f", allowed=(), trace_scopes=("outer", "other"))
    comm_audit("f", compiled, manifest, registry=Registry(), mode="record",
               world_size=1)
    scopes, = [r for r in records if r["record"] == "program_scopes"]
    assert scopes["name"] == "f" and set(scopes["scopes"]) == {"outer", "other"}
    assert scopes["scopes"]["outer"] or scopes["scopes"]["other"]
    # a manifest that names no scope writes no such record
    records.clear()
    comm_audit("f", compiled, CommManifest("f", allowed=()),
               registry=Registry(), mode="record", world_size=1)
    assert [r["record"] for r in records] == ["comm_audit"]
