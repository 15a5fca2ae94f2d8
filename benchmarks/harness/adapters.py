"""Between the reference's leaves and the program's parameter trees: the
generic walk. What a family knows (which program leaf is which reference
leaf) is in its own file, `benchmarks/families/<adapter>.py`
(`harness/family.py`); nothing here is keyed by a family's name.

The reference keeps plain matrices under short names, stacked over layers
(`layers.q_w` [L, h, h]) or named per layer (`layers.3.q_w`); the program
keeps a flax tree with per-head kernels (`bert/layer_3/attention/query/
kernel` [h, heads, d]). Every program leaf is fed by ONE reference leaf:
the family's `TABLE` (or its own `leaf_name(path)`) gives that leaf's
per-layer name, which the walk finds in the spec as it stands or as row
`N` of the stacked `layers.<name>`. Laying it out is a slice, a reshape
and a cast, which leave every norm as it is; a family that needs more (a
transpose) brings `to_program(ref_leaves, flat_template)`, handed the
reference leaves of one group and `{path tuple: ShapeDtypeStruct}` of the
program leaves they feed, returning `{path tuple: array}`.
"""

from __future__ import annotations

import re

#: cap on the float32 bytes one jitted call of `install` generates, so
#: that the install's peak is the installed tree plus one group
INSTALL_GROUP_BYTES = 512 * 2**20

_BLOCK = [
    (r"attention/query/kernel", "q_w"), (r"attention/query/bias", "q_b"),
    (r"attention/key/kernel", "k_w"), (r"attention/key/bias", "k_b"),
    (r"attention/value/kernel", "v_w"), (r"attention/value/bias", "v_b"),
    (r"attention/out/kernel", "o_w"), (r"attention/out/bias", "o_b"),
    (r"mlp_up/kernel", "up_w"), (r"mlp_up/bias", "up_b"),
    (r"mlp_down/kernel", "down_w"), (r"mlp_down/bias", "down_b"),
]


def block_rows(prefix: str, norms: dict) -> list:
    """Table rows of the plain transformer block (`reference/block.py`)
    for a program that names its layers `<prefix><N>/...`; `norms` maps
    the program's two LayerNorm modules to the reference's short names."""
    rows = [(rf"{prefix}(\d+)/{p}", rf"layers.\1.{n}") for p, n in _BLOCK]
    for module, short in norms.items():
        rows.append((rf"{prefix}(\d+)/{module}/scale", rf"layers.\1.{short}_g"))
        rows.append((rf"{prefix}(\d+)/{module}/bias", rf"layers.\1.{short}_b"))
    return rows


def leaf_name(path: str, fam) -> str:
    """The reference's per-layer name of the program leaf at `path`."""
    own = getattr(fam, "leaf_name", None)
    if own is not None:
        return own(path)
    for pattern, name in fam.TABLE:
        m = re.fullmatch(pattern, path)
        if m:
            return m.expand(name)
    raise KeyError(f"program leaf {path!r} has no name in {fam.__name__}")


def source_of(name: str, have) -> tuple:
    """(reference leaf, row or None) that holds the per-layer `name`:
    itself where the spec names leaves per layer, row N of the stacked
    `layers.<name>` otherwise."""
    if name in have:
        return name, None
    m = re.fullmatch(r"layers\.(\d+)\.(.+)", name)
    if m and "layers." + m.group(2) in have:
        return "layers." + m.group(2), int(m.group(1))
    raise KeyError(f"no reference leaf holds {name!r}")


def flat(tree) -> dict:
    """{'a/b/c': leaf} of a nested dict of arrays."""
    from flax import traverse_util

    return {"/".join(k): v for k, v in traverse_util.flatten_dict(tree).items()}


def fill(ref_leaves: dict, flat_template: dict, fam) -> dict:
    """`{path tuple: array}` for the program leaves of `flat_template`
    (`{path tuple: shape and dtype}`), from the reference leaves that
    feed them."""
    own = getattr(fam, "to_program", None)
    if own is not None:
        return own(ref_leaves, flat_template)
    out = {}
    for key, leaf in flat_template.items():
        src, row = source_of(leaf_name("/".join(key), fam), ref_leaves)
        x = ref_leaves[src] if row is None else ref_leaves[src][row]
        out[key] = x.reshape(leaf.shape).astype(leaf.dtype)
    return out


def to_program(ref_weights: dict, template, fam) -> dict:
    """The reference's leaves laid out as the program's parameter tree
    (`template`: the tree of shapes and dtypes to fill)."""
    from flax import traverse_util

    return traverse_util.unflatten_dict(
        fill(ref_weights, traverse_util.flatten_dict(template), fam))


def groups(flat_template: dict, spec: dict, fam, cap_bytes: int) -> list:
    """The install's plan: `[(reference leaves, program paths they
    feed)]` in the spec's order, the float32 bytes of each group's
    reference leaves under `cap_bytes`. One leaf over the cap is refused:
    a family that large names its leaves per layer."""
    from harness import weights

    fed = {}
    for key in flat_template:
        src, _ = source_of(leaf_name("/".join(key), fam), spec)
        fed.setdefault(src, []).append(key)
    packs, size = [], 0
    for name in spec:
        if name not in fed:
            continue
        n = weights.nbytes(spec, name)
        if n > cap_bytes:
            raise ValueError(
                f"reference leaf {name!r} is {n} bytes in float32, over the "
                f"install's cap of {cap_bytes}: name the leaves per layer "
                f"in the weight spec (harness/weights.py)")
        if not packs or size + n > cap_bytes:
            packs.append([])
            size = 0
        packs[-1].append(name)
        size += n
    return [(names, [k for s in names for k in fed[s]]) for names in packs]


def install(params, source, fam, cap_bytes: int = INSTALL_GROUP_BYTES):
    """Replace a program's parameter tree by the benchmark's seeded
    weights (`source`: `weights.Source`), on the old tree's shardings, one
    jitted call per group of `groups`: the peak is the installed tree plus
    one group in float32. The old leaves are deleted first so that both
    trees never live together."""
    import jax
    from flax import traverse_util

    old = traverse_util.flatten_dict(params)
    shardings = {k: v.sharding for k, v in old.items()}
    template = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in old.items()}
    plan = groups(template, source.spec, fam, cap_bytes)
    for leaf in old.values():
        leaf.delete()
    del old
    out, key = {}, source.key()
    for names, keys in plan:
        sub = {k: template[k] for k in keys}
        make = jax.jit(
            lambda k, names=frozenset(names), sub=sub: fill(
                source.generate(k, names), sub, fam),
            out_shardings={k: shardings[k] for k in keys})
        out.update(make(key))
    return traverse_util.unflatten_dict(out)

