"""Layer: step programs. Source: host_clock. The whole serving path's share
of the chip's peak: flops the prompt and output tokens processed in the
window need (the configuration's flops.py, at the mean context of the mix)
per second, over the peak."""


def read(facts):
    if "tokens_in_window" not in facts or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    cfg, traffic = facts["config"], facts["traffic"]
    mean_ctx = (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]) / 4.0
    out = facts["tokens_in_window"] * flops.flops_per_token(cfg, mean_ctx)
    prompt = facts["prompt_tokens_window"] * flops.flops_per_token(
        cfg, traffic["prompt_tokens"]["max"] / 4.0, head=False)
    return 100.0 * (out + prompt) / facts["seconds"] / facts["peaks"]["bf16_flops"]
