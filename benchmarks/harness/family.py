"""What belongs to one model family, found by name as a configuration's
other files are: `benchmarks/families/<adapter>.py`, the module a
configuration file names under `adapter`. No table of families lives in
harness code; a later PR adds a file. A family file holds

- the leaf map between the program's parameter tree and the reference's
  leaves: `TABLE`, rows of `(regular expression over the program's path,
  reference leaf name)`, or its own `leaf_name(path)`; and, where a slice
  and a reshape do not lay a reference leaf out as the program keeps it,
  its own `to_program(ref_leaves, flat_template)` (`harness/adapters.py`
  says what each is handed);
- the counts, each handed the WHOLE configuration (so that a family can
  see its `reduced` shares: experts held, vocabulary rows): for a
  training cell `train_flops_per_sample(config, seq)`; for a serving cell
  `prefill_flops(config, prompt, observed)` and `decode_flops(config,
  context, observed)`, where `observed` is what the run saw of the
  request (`harness/serve_driver.py::run`), and
  `cache_bytes_per_token(config, bytes_per_value)` /
  `cache_read_bytes(config, contexts, bytes_per_value)` for the bytes of
  cached state. A count a cell needs and the family does not bring is an
  error that names it: nothing falls back to the dense block's count;
- weight kinds of its own: `init(kind, key, shape, std)` for a `kind` in
  the reference's `weight_spec` that `harness/weights.py` does not know.
"""

from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rel(config: dict) -> str:
    return f"benchmarks/families/{config.get('adapter')}.py"


def of(config: dict):
    """The family module of a configuration."""
    name = config.get("adapter")
    if not name or not os.path.isfile(
            os.path.join(HERE, "families", f"{name}.py")):
        raise SystemExit(
            f"benchmarks/run.py: configuration {config.get('name')!r} names "
            f"the family (`adapter`) {name!r}: no file {_rel(config)}")
    return importlib.import_module("families." + name)


def count(config: dict, name: str):
    """The family's count `name`, or an exit that names what is missing."""
    fn = getattr(of(config), name, None)
    if fn is None:
        raise SystemExit(
            f"benchmarks/run.py: configuration {config.get('name')!r}: its "
            f"family file {_rel(config)} has no `{name}`; a count is never "
            f"taken from another family or from the dense block by default")
    return fn


def source(config: dict, spec: dict, seed: int):
    """The seeded weights of a run: `harness/weights.py::Source` with the
    configuration's spread and the family's own kinds."""
    from harness import weights

    return weights.Source(spec, seed, weights.std_of(config),
                          getattr(of(config), "init", None))
