"""The whole model step's share of the chip's bf16 peak over the window:
operations of the prompts whose first token arrived in it (causal prefill,
head on the last token) and of every token decoded in it over its own
context, both counted by the configuration's family file
(`prefill_flops`, `decode_flops`; the driver sums them as the tokens
arrive), over window seconds times chips times peak."""


def read(obs):
    if not obs.get("peaks") or not obs.get("window_s"):
        return None
    work = obs["prefill_flops"] + obs["decode_flops"]
    if not work:
        return None
    return 100.0 * work / (
        obs["window_s"] * obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
