"""Layer: kernels. Source: device_trace. The expert layers' grouped products'
share of their roofline: the larger of flops / peak flops and bytes / peak
bytes of one layer's products, forward and backward (the configuration's
flops.py `moe_gmm`, at the pairs a layer the program counted:
moe_pairs_per_token.py x the step's tokens), times the expert layers held,
over the device time a step of every grouped product. XLA lowers
`jax.lax.ragged_dot_general` to Mosaic calls named `ragged-dot-none.N`,
found by `^%?ragged-dot-none` on the operation's HLO text (their small
`ragged-dot-metadata` calls are left out). Returns nothing where the trace
holds no such call, the configuration counts no `moe_gmm` or the program
counted no pairs."""

PRODUCTS = r"^%?ragged-dot-none"


def read(facts):
    tr = facts.get("trace")
    step = tr.module_time() if tr is not None else None
    if step is None or "cell" not in facts:
        return None
    flops = facts["cell"].config_module("flops")
    counted = facts["cell"].reader("moe_pairs_per_token").counted()
    if not hasattr(flops, "moe_gmm") or counted is None:
        return None
    seconds, calls = tr.op_time(PRODUCTS)
    if not calls:
        return None
    cfg = facts["config"]
    pairs = counted[0] / counted[1] * facts["items_per_step"]
    need_f, need_b = flops.moe_gmm(cfg, facts["traffic"],
                                   facts["batch_per_chip"], pairs)
    layers = list(cfg["mlp_layer_types"])[:cfg["num_hidden_layers"]
                                           ].count("sparse")
    least = layers * max(need_f / facts["peaks"]["bf16_flops"],
                         need_b / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / step[2])
