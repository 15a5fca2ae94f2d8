"""The whole step's share of the chip's bf16 peak: samples per second of
the window times the operations a sample needs forward and backward
(`flops.train_flops_per_sample`, recomputation not counted) over chips
times peak. Nothing to read without a table of peaks (a CPU rehearsal)."""

from harness import flops


def read(obs):
    if not obs.get("peaks") or not obs.get("samples"):
        return None
    need = flops.train_flops_per_sample(obs["config"]["model"], obs["seq"])
    rate = obs["samples"] / obs["window_s"]
    return 100.0 * rate * need / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
