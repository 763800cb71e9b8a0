"""Layer: step programs. Source: host_clock. The whole step's share of the
chip's peak: flops the forward and backward need per item (the
configuration's flops.py) x items/s over chips x peak. Recomputation does not
count."""


def read(facts):
    if "items" not in facts or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    per_item = flops.train_flops_per_item(facts["config"], facts["traffic"])
    rate = facts["items"] / facts["window_s"] / facts["chips"]
    return 100.0 * per_item * rate / facts["peaks"]["bf16_flops"]
