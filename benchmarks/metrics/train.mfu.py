"""The whole step's share of the chip's bf16 peak: samples per second of
the window times the operations a sample needs forward and backward
(`train_flops_per_sample` of the configuration's family file,
recomputation not counted) over chips times peak. Nothing to read without
a table of peaks (a CPU rehearsal)."""

from harness import family


def read(obs):
    if not obs.get("peaks") or not obs.get("samples"):
        return None
    config = obs["config"]
    need = family.count(config, "train_flops_per_sample")(config, obs["seq"])
    rate = obs["samples"] / obs["window_s"]
    return 100.0 * rate * need / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
