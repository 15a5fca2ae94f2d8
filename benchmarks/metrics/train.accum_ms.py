"""Device time per run of the compiled train step in the gradient carry's
adds: the instructions the step lists under its `train.accumulate` scope
(with each fusion the add closes: the zeroed first carry, the cast of a
bf16 gradient), averaged over the runs in the traced window
(`harness/train_phases.py`). Nothing to read where the program keeps no
scopes record or the trace holds no run of the step."""

from harness import train_phases


def read(obs):
    return train_phases.read(obs, "train.accumulate")
