"""Device time per run of the compiled train step in one micro-batch's
forward and backward, all micro-batches of the step together: the
instructions the step lists under its `train.grad` scope (the fused
add-LayerNorm kernels among them), averaged over the runs in the traced
window (`harness/train_phases.py`). Nothing to read where the program keeps
no scopes record or the trace holds no run of the step."""

from harness import train_phases


def read(obs):
    return train_phases.read(obs, "train.grad")
