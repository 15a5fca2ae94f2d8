"""A model family is files found by name, and weights come one leaf at a
time: a rehearsal checkout ADDS a third family (its leaf map, its counts,
a weight kind of its own, its reference) and `run.py` runs its cell from
those files alone; a family without the count a cell needs is refused by
name; a leaf made alone holds the values it has in the whole; the install
fills the program's tree in groups under its cap and ends with the tree
the one call gave."""

import copy

import jax
import numpy as np
import pytest

import rehearsal
from harness import adapters, family, weights

# ---- a third family, as files only ------------------------------------

OTHER_FAMILY = '''"""A family a test added: the tiny GPT-2 program under another name,
with counts a test can recognise and a weight kind of its own."""

import jax

from harness import adapters

TABLE = [
    (r"wte/embedding", "wte"), (r"wpe/embedding", "wpe"),
    *adapters.block_rows("block_", {"ln_1": "ln1", "ln_2": "ln2"}),
    (r"ln_f/scale", "lnf_g"), (r"ln_f/bias", "lnf_b"),
]


def prefill_flops(config, prompt, observed=None):
    assert observed["request"].prompt_len == prompt
    return 12345.0 * config["model"]["n_layer"]


def decode_flops(config, context, observed=None):
    assert context >= observed["request"].prompt_len
    return 0.5


def init(kind, key, shape, std):
    if kind != "gain_uniform":
        raise ValueError(kind)
    return jax.random.uniform(key, shape, minval=0.8, maxval=1.2)
'''
OTHER_REFERENCE = '''"""The plain GPT-2 reference, its last gain drawn by the family's own
kind."""

from reference.gpt2_medium_paged import served_token_gaps  # noqa: F401
from reference import gpt2_medium_paged as _plain


def weight_spec(model):
    spec = _plain.weight_spec(model)
    spec["lnf_g"] = (spec["lnf_g"][0], "gain_uniform")
    return spec
'''
NO_COUNT_FAMILY = '''"""A family that maps its leaves and counts nothing."""

from families.gpt2 import TABLE  # noqa: F401
'''
FLOPS_READERS = [
    ("test.prefill_flops", "def read(obs):\n    return obs.get('prefill_flops')\n"),
    ("test.decode_flops", "def read(obs):\n    return obs.get('decode_flops')\n"),
]


def _metric(name):
    return {"name": name, "unit": "flop", "better": "higher",
            "source": "program_counter", "layer": "model step",
            "moves": "serve.tpot_p95_ms", "workloads": ["other_chat1"]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    other = dict(copy.deepcopy(rehearsal.TINY_GPT2), name="other_paged",
                 adapter="tiny_other", reference="tiny_other")
    bare = dict(copy.deepcopy(rehearsal.TINY_GPT2), name="bare_paged",
                adapter="tiny_bare")
    cells = [
        {"name": "other_chat1", "config": "other_paged", "traffic": "tiny_chat",
         "chips": 1, "why": "tests only"},
        {"name": "bare_chat1", "config": "bare_paged", "traffic": "tiny_chat",
         "chips": 1, "why": "tests only"},
    ]
    return rehearsal.make_checkout(
        str(tmp_path_factory.mktemp("bench")), configs=[other, bare],
        traffic=[("tiny_chat", rehearsal.TINY_CHAT)], cells=cells,
        end_to_end_cells=[("serve.tpot_p95_ms", "other_chat1"),
                          ("serve.tpot_p95_ms", "bare_chat1")],
        per_layer=[_metric(n) for n, _ in FLOPS_READERS], readers=FLOPS_READERS,
        files=[("families/tiny_other.py", OTHER_FAMILY),
               ("families/tiny_bare.py", NO_COUNT_FAMILY),
               ("reference/tiny_other.py", OTHER_REFERENCE)])


def test_a_third_family_runs_from_added_files_alone(checkout):
    rc, result, out, err = rehearsal.run_cell(
        checkout, "other_chat1", seconds=3, trace=1)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 5
    assert result["notes"]["checked_tokens"] > 20
    # the operations the run summed are the added file's: 2 layers x 12345
    # a prompt, half an operation a decoded token
    prefills = result["metrics"]["test.prefill_flops"]["value"] / (2 * 12345.0)
    decoded = result["metrics"]["test.decode_flops"]["value"] / 0.5
    assert prefills == int(prefills) > 5 and decoded == int(decoded) > prefills


def test_a_family_without_the_cells_count_is_refused_by_name(checkout):
    rc, result, out, err = rehearsal.run_cell(checkout, "bare_chat1")
    assert rc != 0 and result is None
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]
    assert "benchmarks/families/tiny_bare.py has no `prefill_flops`" in err
    assert "cli.serve_lm.main" not in out  # refused before the server is built


# ---- weights one leaf at a time ---------------------------------------

def real_spec(tiny: dict) -> dict:
    import importlib

    reference = importlib.import_module("reference." + tiny["reference"])
    return reference.weight_spec(tiny["model"])


TINIES = [rehearsal.TINY_BERT, rehearsal.TINY_GPT2]


@pytest.mark.parametrize("tiny", TINIES, ids=lambda t: t["adapter"])
def test_a_leaf_made_alone_equals_the_wholes_bit_for_bit(tiny):
    spec = real_spec(tiny)
    seed = 2**31 + 17
    whole = weights.make(spec, seed, 0.2)
    assert set(whole) == set(spec)
    source = weights.Source(spec, seed, 0.2)
    for name, (shape, _) in spec.items():
        alone = weights.leaf(spec, seed, name, 0.2)
        assert alone.shape == tuple(shape) and alone.dtype == np.float32
        assert np.array_equal(np.asarray(alone), np.asarray(whole[name])), name
        assert weights.nbytes(spec, name) == alone.nbytes
    assert np.array_equal(np.asarray(source.leaf("layers.q_w")),
                          np.asarray(source.whole()["layers.q_w"]))
    with pytest.raises(KeyError):
        weights.leaf(spec, seed, "no_such_leaf")


def test_an_unknown_kind_is_the_familys_or_an_error():
    spec = {"a": ((4,), "normal"), "rate": ((3, 2), "decay")}
    with pytest.raises(ValueError, match="decay"):
        weights.make(spec, 1)

    def init(kind, key, shape, std):
        assert kind == "decay"
        return jax.random.uniform(key, shape, minval=1.0, maxval=16.0)

    got = weights.make(spec, 1, init=init)
    assert np.all(np.asarray(got["rate"]) >= 1.0) and got["rate"].shape == (3, 2)
    plain = weights.make({"a": ((4,), "normal"), "b": ((3, 2), "normal")}, 1)
    assert np.array_equal(np.asarray(got["a"]), np.asarray(plain["a"]))


# ---- the install, in groups under its cap ------------------------------

def program_tree(tiny: dict) -> dict:
    """Zeros laid out as the program keeps the model: the paths the
    family's table names, kernels per head."""
    m = tiny["model"]
    gpt = tiny["adapter"] == "gpt2"
    h = m["n_embd"] if gpt else m["hidden_size"]
    f = m["n_inner"] if gpt else m["intermediate_size"]
    heads = m["n_head"] if gpt else m["num_attention_heads"]
    n = m["n_layer"] if gpt else m["num_hidden_layers"]
    z = lambda *shape: jax.numpy.zeros(shape, jax.numpy.float32)  # noqa: E731
    norm = lambda: {"scale": z(h), "bias": z(h)}  # noqa: E731

    def layer(n1, n2):
        proj = lambda: {"kernel": z(h, heads, h // heads),  # noqa: E731
                        "bias": z(heads, h // heads)}
        return {
            "attention": {"query": proj(), "key": proj(), "value": proj(),
                          "out": {"kernel": z(heads, h // heads, h), "bias": z(h)}},
            "mlp_up": {"kernel": z(h, f), "bias": z(f)},
            "mlp_down": {"kernel": z(f, h), "bias": z(h)}, n1: norm(), n2: norm()}

    if gpt:
        tree = {"wte": {"embedding": z(m["vocab_size"], h)},
                "wpe": {"embedding": z(m["n_positions"], h)}, "ln_f": norm()}
        tree.update({f"block_{i}": layer("ln_1", "ln_2") for i in range(n)})
        return tree
    return {
        "bert": {
            "embeddings": {
                "word_embeddings": {"embedding": z(m["vocab_size"], h)},
                "position_embeddings": {
                    "embedding": z(m["max_position_embeddings"], h)},
                "token_type_embeddings": {"embedding": z(m["type_vocab_size"], h)},
                "norm": norm()},
            **{f"layer_{i}": layer("attention_norm", "mlp_norm") for i in range(n)},
            "pooler": {"kernel": z(h, h), "bias": z(h)}},
        "classifier": {"kernel": z(h, m["num_labels"]), "bias": z(m["num_labels"])},
    }


@pytest.mark.parametrize("tiny", TINIES, ids=lambda t: t["adapter"])
def test_grouped_install_stays_under_its_cap_and_equals_the_one_call(tiny):
    spec = real_spec(tiny)
    fam = family.of(tiny)
    source = weights.Source(spec, 2**31 + 5, 0.2)
    template = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in adapters.flat(program_tree(tiny)).items()}
    flat_template = {tuple(k.split("/")): v for k, v in template.items()}
    cap = max(weights.nbytes(spec, n) for n in spec)  # the largest leaf alone
    plan = adapters.groups(flat_template, spec, fam, cap)
    assert len(plan) >= 3
    assert [n for names, _ in plan for n in names] == list(spec)  # spec order
    for names, keys in plan:
        assert sum(weights.nbytes(spec, n) for n in names) <= cap
        assert keys
    assert sorted(k for _, keys in plan for k in keys) == sorted(flat_template)
    assert len(adapters.groups(flat_template, spec, fam, 2**40)) == 1
    with pytest.raises(ValueError, match="per layer"):
        adapters.groups(flat_template, spec, fam, cap - 1)

    grouped = adapters.flat(adapters.install(program_tree(tiny), source, fam, cap))
    one_call = adapters.flat(adapters.install(program_tree(tiny), source, fam, 2**40))
    walked = adapters.flat(adapters.to_program(
        source.whole(), jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), program_tree(tiny)),
        fam))
    assert set(grouped) == set(one_call) == set(walked) == set(template)
    for path in template:
        assert grouped[path].shape == template[path].shape
        assert np.array_equal(np.asarray(grouped[path]), np.asarray(one_call[path]))
        assert np.array_equal(np.asarray(grouped[path]), np.asarray(walked[path]))
    # the same leaf under the reference's name, a slice and a reshape away
    whole = source.whole()
    q3 = "block_1/attention/query/kernel" if tiny["adapter"] == "gpt2" \
        else "bert/layer_1/attention/query/kernel"
    assert adapters.leaf_name(q3, fam) == "layers.1.q_w"
    assert np.array_equal(np.asarray(grouped[q3]).reshape(whole["layers.q_w"][1].shape),
                          np.asarray(whole["layers.q_w"][1]))


def test_a_spec_that_names_its_leaves_per_layer_is_walked_the_same():
    """What a family sized to the chip does: `layers.<N>.<name>` in the
    spec, no stacked leaf, so no group ever holds more than a layer."""
    fam = family.of(rehearsal.TINY_GPT2)
    stacked = real_spec(rehearsal.TINY_GPT2)
    per_layer = {}
    for name, (shape, kind) in stacked.items():
        if name.startswith("layers."):
            for i in range(shape[0]):
                per_layer[f"layers.{i}.{name[7:]}"] = (shape[1:], kind)
        else:
            per_layer[name] = (shape, kind)
    assert adapters.source_of("layers.1.q_w", per_layer) == ("layers.1.q_w", None)
    assert adapters.source_of("layers.1.q_w", stacked) == ("layers.q_w", 1)
    with pytest.raises(KeyError):
        adapters.source_of("layers.1.nothing", stacked)
    source = weights.Source(per_layer, 9, 0.2)
    cap = max(weights.nbytes(per_layer, n) for n in per_layer)
    got = adapters.flat(adapters.install(
        program_tree(rehearsal.TINY_GPT2), source, fam, cap))
    whole = source.whole()
    assert np.array_equal(
        np.asarray(got["block_1/mlp_up/kernel"]), np.asarray(whole["layers.1.up_w"]))
    assert np.array_equal(
        np.asarray(got["block_0/attention/out/bias"]), np.asarray(whole["layers.0.o_b"]))
