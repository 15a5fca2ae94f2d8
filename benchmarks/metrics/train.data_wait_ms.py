"""Mean time per step the loop waited inside the loader's `next()`, taken
by the harness's wrapper around `train_loader.epoch`."""


def read(obs):
    if not obs.get("steps"):
        return None
    return 1e3 * obs["data_wait_s"] / obs["steps"]
