"""SPMD analysis unit tests: the four static lint rules
(analysis/rules/spmd.py) on synthetic fixtures, the compiled-HLO
collective extractor + ICI/DCN cost model (analysis/spmd/hlo.py) on a
hand-written dump, expected-collective manifests and the ``comm_audit``
guard (analysis/spmd/manifest.py), and the ``--rules`` driver filter.
Everything here is jax-compile-free and tier-1 cheap; the end-to-end
footprint pins over real compiled programs live in test_parallel_mp.py."""

import textwrap

import pytest

from pytorch_distributed_training_tpu.analysis.guards import GuardViolation
from pytorch_distributed_training_tpu.analysis.lint import (
    lint_paths,
    lint_source,
    select_rules,
)
from pytorch_distributed_training_tpu.analysis.rules import spmd
from pytorch_distributed_training_tpu.analysis.spmd.hlo import (
    COLLECTIVE_KINDS,
    CostModel,
    extract_collectives,
    summarize_collectives,
)
from pytorch_distributed_training_tpu.analysis.spmd.manifest import (
    CommManifest,
    comm_audit,
    serve_manifest,
    train_manifest,
)
from pytorch_distributed_training_tpu.telemetry.registry import (
    MetricsRegistry,
)
from pytorch_distributed_training_tpu.utils.config import MeshConfig
from test_guards import ListSink  # sibling module (pytest sys.path)


def _findings(src, rule_id):
    out = lint_source(textwrap.dedent(src), rules=(spmd,))
    return [f for f in out if f.rule == rule_id]


# ------------------------------------------------------- pspec-mismatch


def test_pspec_unknown_axis_flagged():
    (f,) = _findings(
        """
        from jax.sharding import PartitionSpec as P
        SPEC = P("data", "modle")
        """,
        spmd.PSPEC_RULE_ID,
    )
    assert "'modle'" in f.message


def test_pspec_duplicate_axis_flagged():
    (f,) = _findings(
        """
        from jax.sharding import PartitionSpec
        SPEC = PartitionSpec("data", "data")
        """,
        spmd.PSPEC_RULE_ID,
    )
    assert "two different dims" in f.message


def test_pspec_canonical_spec_clean():
    assert not _findings(
        """
        from jax.sharding import PartitionSpec as P
        SPEC = P(("data", "fsdp"), None, "model")
        """,
        spmd.PSPEC_RULE_ID,
    )


def test_canonical_axes_pinned_to_mesh_config():
    # spmd.py keeps the universe as literals (the linter must not import
    # jax); this pin makes MeshConfig drift fail loudly.
    assert spmd.CANONICAL_AXES == set(MeshConfig.AXIS_NAMES) | {"seq"}


# ------------------------------------------------- shardmap-axis-misuse


def test_collective_unknown_axis_flagged():
    (f,) = _findings(
        """
        import jax
        def inner(x):
            return jax.lax.psum(x, "batch")
        """,
        spmd.AXIS_RULE_ID,
    )
    assert "psum" in f.message and "'batch'" in f.message


def test_collective_traced_without_binding_flagged():
    (f,) = _findings(
        """
        import jax
        @jax.jit
        def step(x):
            return jax.lax.psum(x, "data")
        """,
        spmd.AXIS_RULE_ID,
    )
    assert "no" in f.message and "shard_map" in f.message


def test_collective_under_shard_map_clean():
    assert not _findings(
        """
        import jax
        from jax.experimental.shard_map import shard_map
        def inner(x):
            return jax.lax.psum(x, "data")
        f = shard_map(inner, mesh=None, in_specs=None, out_specs=None)
        """,
        spmd.AXIS_RULE_ID,
    )


def test_dispatch_shard_map_binds_axis_too():
    # the normalized ops/dispatch wrapper counts as a binder
    assert not _findings(
        """
        import jax
        from pytorch_distributed_training_tpu.ops import dispatch
        def inner(x):
            return jax.lax.psum(x, "data")
        f = dispatch.shard_map(inner, mesh=None, in_specs=None,
                               out_specs=None)
        """,
        spmd.AXIS_RULE_ID,
    )


# ---------------------------------------------------- collective-in-loop


def test_collective_in_scan_body_flagged():
    (f,) = _findings(
        """
        import jax
        from jax.experimental.shard_map import shard_map
        def body(carry, x):
            return carry + jax.lax.psum(x, "data"), None
        def outer(xs):
            return jax.lax.scan(body, 0.0, xs)
        f = shard_map(body, mesh=None, in_specs=None, out_specs=None)
        """,
        spmd.LOOP_RULE_ID,
    )
    assert "PER ITERATION" in f.message


def test_collective_in_host_loop_flagged():
    (f,) = _findings(
        """
        import jax
        from jax.experimental.shard_map import shard_map
        def inner(x):
            out = 0.0
            for _ in range(4):
                out = out + jax.lax.psum(x, "data")
            return out
        f = shard_map(inner, mesh=None, in_specs=None, out_specs=None)
        """,
        spmd.LOOP_RULE_ID,
    )
    assert "host loop" in f.message


def test_axis_index_in_scan_body_not_a_loop_finding():
    assert not _findings(
        """
        import jax
        from jax.experimental.shard_map import shard_map
        def body(carry, x):
            return carry + jax.lax.axis_index("data"), None
        def outer(xs):
            return jax.lax.scan(body, 0, xs)
        f = shard_map(body, mesh=None, in_specs=None, out_specs=None)
        """,
        spmd.LOOP_RULE_ID,
    )


def test_collective_after_loop_clean():
    assert not _findings(
        """
        import jax
        from jax.experimental.shard_map import shard_map
        def inner(xs):
            out = 0.0
            for x in xs:
                out = out + x
            return jax.lax.psum(out, "data")
        f = shard_map(inner, mesh=None, in_specs=None, out_specs=None)
        """,
        spmd.LOOP_RULE_ID,
    )


# -------------------------------------------------- implicit-replication


def test_large_literal_init_in_jit_flagged():
    (f,) = _findings(
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def step(x):
            buf = jnp.zeros((256, 256), jnp.float32)
            return x + buf
        """,
        spmd.REPL_RULE_ID,
    )
    assert "65536" in f.message and "REPLICATED" in f.message


def test_small_or_untraced_inits_clean():
    assert not _findings(
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def step(x):
            return x + jnp.zeros((8, 8), jnp.float32)   # small: noise
        def host_side():
            return jnp.zeros((512, 512))                # not traced
        buf = jnp.zeros((1024, 1024))                   # module level
        """,
        spmd.REPL_RULE_ID,
    )


# ------------------------------------------------------- driver plumbing


def test_select_rules_accepts_all_spmd_ids():
    mods = select_rules(spmd.RULE_IDS)
    assert spmd in mods


def test_select_rules_rejects_unknown_id():
    with pytest.raises(ValueError, match="unknown rule id"):
        select_rules(("pspec-mismatch", "no-such-rule"))


def test_lint_paths_rule_filter(tmp_path):
    # one pspec finding + one mutable-default finding in the same file;
    # the --rules filter must report only the requested id
    path = tmp_path / "fixture.py"
    path.write_text(textwrap.dedent(
        """
        from jax.sharding import PartitionSpec as P
        SPEC = P("modle")
        def f(x, acc=[]):
            acc.append(x)
            return acc
        """
    ))
    full = lint_paths([str(path)])
    assert {f.rule for f in full.findings} >= {
        "pspec-mismatch", "mutable-default"
    }
    subset = lint_paths([str(path)], rule_ids=("pspec-mismatch",))
    assert {f.rule for f in subset.findings} == {"pspec-mismatch"}


# --------------------------------------------------- HLO extractor + cost

_HLO = """\
HloModule step

ENTRY %main {
  %all-gather.1 = f32[16,256]{1,0} all-gather(f32[2,256]{1,0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %all-reduce-start.2 = (f32[128]{0}, f32[128]{0}) all-reduce-start(f32[128]{0} %p1), replica_groups=[2,4]<=[8], to_apply=%add
  %all-reduce-done.2 = f32[128]{0} all-reduce-done(%all-reduce-start.2)
  %reduce-scatter.3 = f32[32]{0} reduce-scatter(f32[256]{0} %p2), replica_groups={}, dimensions={0}, to_apply=%add
  %add.4 = f32[128]{0} add(f32[128]{0} %a, f32[128]{0} %b)
  ROOT %collective-permute.5 = bf16[64]{0} collective-permute(bf16[64]{0} %p3), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
}
"""


def test_extract_collectives_synthetic_dump():
    cs = extract_collectives(_HLO, world_size=8)
    assert [c.kind for c in cs] == [
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    ]
    ag, ar, rs, cp = cs
    # explicit replica groups: size of the first group
    assert (ag.bytes, ag.group_size, ag.asynchronous) == (16 * 256 * 4, 4,
                                                          False)
    # async start: tuple shape counts the result buffer once, iota groups
    assert (ar.bytes, ar.group_size, ar.asynchronous) == (128 * 4, 4, True)
    # replica_groups={} means "all devices" -> world_size
    assert (rs.bytes, rs.group_size) == (32 * 4, 8)
    # permute: distinct devices in the pair list; bf16 = 2 bytes
    assert (cp.bytes, cp.group_size, cp.dtype) == (64 * 2, 4, "bf16")
    # -done halves and plain ops never match
    assert all("done" not in c.name and c.kind != "add" for c in cs)


# what XLA:TPU writes (bert-large fsdp=4 step compiled for a v5e 2x2, PR 21):
# tiled layouts with parentheses of their own, combined all-reduces with
# tuple shapes, async permutes, and reduce-scatter as a fused computation
_TPU_HLO = """\
HloModule jit_train_step

%all-reduce-scatter.6.clone.clone (input.245: bf16[4096,1024]) -> bf16[1032,1024] {
  %input.245 = bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %pad.1095 = bf16[4128,1024]{1,0:T(8,128)(2,1)} pad(%input.245, %constant.48184), padding=0_32x0_0
  %all-reduce.1695 = bf16[4128,1024]{1,0:T(8,128)(2,1)} all-reduce(%pad.1095), channel_id=848, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.4.clone
  ROOT %dynamic-slice.1442 = bf16[1032,1024]{1,0:T(8,128)(2,1)} dynamic-slice(%all-reduce.1695, %multiply.5524, %constant.48186), dynamic_slice_sizes={1032,1024}
}

ENTRY %main.1039_spmd (param.1: f32[1024]) -> f32[1024] {
  %fusion.9135 = bf16[1032,1024]{1,0:T(8,128)(2,1)} fusion(%custom-call.1241), kind=kCustom, calls=%all-reduce-scatter.6.clone.clone
  %all-reduce.1499 = (f32[1024]{0:T(1024)S(1)}, f32[2,1024]{1,0:T(2,128)S(1)}, /*index=2*/f32[16,64]{1,0:T(8,128)S(1)}) all-reduce(%a, %b, %c), channel_id=7, replica_groups={{0,1,2,3}}, to_apply=%add
  %collective-permute-start.13 = (bf16[24,1024]{1,0:T(8,128)(2,1)}, bf16[24,1024]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%slice.1268), channel_id=933, source_target_pairs={{0,1},{1,2},{2,3}}
  %collective-permute-done.13 = bf16[24,1024]{1,0:T(8,128)(2,1)} collective-permute-done(%collective-permute-start.13)
  ROOT %all-gather.3 = bf16[1024,4096]{1,0:T(8,128)(2,1)} all-gather(%p), channel_id=9, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
}
"""


def test_extract_collectives_reads_tpu_layouts_and_fused_reduce_scatter():
    rs, ar, cp, ag = extract_collectives(_TPU_HLO, world_size=4)
    # the all-reduce INSIDE an all-reduce-scatter fusion is the TPU's
    # reduce-scatter: reported as one, payload = the scattered shard
    assert (rs.kind, rs.bytes, rs.group_size) == (
        "reduce-scatter", 4128 * 1024 * 2 // 4, 4)
    # a combined all-reduce: tuple shape whose layouts carry parentheses
    assert (ar.kind, ar.bytes) == (
        "all-reduce", (1024 + 2 * 1024 + 16 * 64) * 4)
    # async permute: the result buffer once, not the alias and the flags
    assert (cp.kind, cp.bytes, cp.asynchronous) == (
        "collective-permute", 24 * 1024 * 2, True)
    assert (ag.kind, ag.bytes, ag.group_size) == (
        "all-gather", 1024 * 4096 * 2, 4)
    # and this footprint is what the fsdp manifest states
    fsdp = train_manifest(_Shape(data=1, fsdp=4), fsdp_sharded=True)
    summary = summarize_collectives([rs, ar, cp, ag])
    assert fsdp.check(summary) == []
    assert train_manifest(_Shape(data=4)).check(summary) != []


def test_cost_model_ring_bytes_and_links():
    cm = CostModel(ici_gbps=90.0, dcn_gbps=12.5, devices_per_host=8)
    ag, ar, rs, cp = extract_collectives(_HLO, world_size=8)
    assert cm.moved_bytes(ag) == int(ag.bytes * 3 / 4)       # (g-1)/g
    assert cm.moved_bytes(ar) == int(2 * ar.bytes * 3 / 4)   # RS + AG
    assert cm.moved_bytes(rs) == rs.bytes * 7                # result * (g-1)
    assert cm.moved_bytes(cp) == cp.bytes                    # point-to-point
    assert cm.link(8) == "ici" and cm.link(9) == "dcn"
    # group-of-1 (or unknown) moves nothing
    solo = ag.__class__(name="x", kind="all-gather", dtype="f32", bytes=64,
                        group_size=1, line=1, asynchronous=False)
    assert cm.moved_bytes(solo) == 0


def test_summarize_collectives_totals():
    s = summarize_collectives(extract_collectives(_HLO, world_size=8))
    assert s["count"] == 4
    assert set(s["by_kind"]) == {
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    }
    assert s["total_bytes"] == 16384 + 512 + 128 + 128
    assert s["total_moved_bytes"] == sum(
        v["moved_bytes"] for v in s["by_kind"].values()
    )
    # every group here fits in one 8-device host -> all traffic is ICI
    assert s["dcn_moved_bytes"] == 0
    assert s["ici_moved_bytes"] == s["total_moved_bytes"]
    assert s["est_time_s"] > 0


# ----------------------------------------------------- manifests + audit


class _Shape:
    """mesh stand-in: train_manifest only reads ``mesh.shape``."""

    def __init__(self, **shape):
        self.shape = shape


def test_manifest_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown collective kind"):
        CommManifest("bad", allowed=("all-gatherr",))


def test_manifest_check_deviations():
    m = CommManifest("m", allowed=("all-reduce",),
                     required=("all-reduce",), max_bytes=100)
    summary = {
        "by_kind": {"all-gather": {"count": 2, "bytes": 64}},
        "total_bytes": 640,
    }
    devs = m.check(summary)
    assert any("unexpected all-gather x2" in d for d in devs)
    assert "required all-reduce absent" in devs
    assert any("exceeds manifest ceiling" in d for d in devs)
    clean = {"by_kind": {"all-reduce": {"count": 1, "bytes": 8}},
             "total_bytes": 8}
    assert m.check(clean) == []


def test_train_manifest_shapes_by_mesh_axes():
    assert train_manifest(_Shape(data=1)).allowed == ()
    assert train_manifest(_Shape(data=8)).allowed == ("all-reduce",)
    fsdp = train_manifest(_Shape(data=2, fsdp=4), fsdp_sharded=True)
    # collective-permute: the halo exchange after XLA:TPU's padded
    # reduce-scatter (see train_manifest)
    assert set(fsdp.allowed) == {"all-reduce", "all-gather",
                                "reduce-scatter", "collective-permute"}
    assert fsdp.required == ("all-gather",)
    # fsdp axis present but nothing actually sharded: no gather required
    assert train_manifest(_Shape(data=2, fsdp=4)).required == ()
    assert "collective-permute" in train_manifest(
        _Shape(data=4, stage=2)).allowed
    assert "all-to-all" in train_manifest(_Shape(model=4)).allowed


def test_serve_manifest_pins_single_device_to_silence():
    assert serve_manifest(1).allowed == ()
    assert serve_manifest(8).allowed == COLLECTIVE_KINDS


class _Stage:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        if isinstance(self._text, Exception):
            raise self._text
        return self._text


def _registry():
    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    return reg, sink


def test_comm_audit_conforming_records_ok():
    reg, sink = _registry()
    manifest = CommManifest("step", allowed=(
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    ))
    rec = comm_audit("step", _Stage(_HLO), manifest, registry=reg,
                     mode="strict", world_size=8)
    assert rec["ok"] is True and rec["deviations"] == []
    (emitted,) = sink.of("comm_audit")
    assert emitted["count"] == 4 and emitted["manifest"] == "step"
    assert "guards/comm_deviations" not in reg.snapshot()["counters"]


def test_comm_audit_record_mode_logs_without_raising():
    reg, sink = _registry()
    rec = comm_audit("step", _Stage(_HLO), CommManifest("silent"),
                     registry=reg, mode="record", world_size=8)
    assert rec["ok"] is False and len(rec["deviations"]) == 4
    assert reg.snapshot()["counters"]["guards/comm_deviations"] == 4
    (emitted,) = sink.of("comm_audit")
    assert emitted["ok"] is False


def test_comm_audit_strict_raises_on_deviation():
    reg, sink = _registry()
    manifest = CommManifest("gathered", allowed=COLLECTIVE_KINDS,
                            required=("all-to-all",))
    with pytest.raises(GuardViolation,
                       match="required all-to-all absent"):
        comm_audit("step", _Stage(_HLO), manifest, registry=reg,
                   mode="strict", world_size=8)
    (emitted,) = sink.of("comm_audit")    # record lands before the raise
    assert emitted["ok"] is False


def test_comm_audit_survives_backends_without_text():
    reg, sink = _registry()
    rec = comm_audit("step", _Stage(RuntimeError("no dump")),
                     CommManifest("m"), registry=reg, mode="strict")
    assert rec["ok"] is None and "no dump" in rec["error"]
    (emitted,) = sink.of("comm_audit")
    assert emitted["ok"] is None
