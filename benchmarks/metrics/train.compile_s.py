"""Seconds inside the trainer's AOT warm start (trace, lower, compile or
load from the cache, for the train and the eval step), taken by the
harness's wrapper around `Trainer._warm_start`."""


def read(obs):
    return obs.get("compile_s")
