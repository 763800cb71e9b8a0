"""Layer: step programs. Source: device_trace. Device time of the expert
layers' dispatch loops over the device time of the step program that holds
them, whole runs inside the traced window. Every trip of an expert layer
(the gather of its pairs' rows, the grouped products, the weighting, the
scatter back, and the same again backward) runs inside a `while` operation
whose trip count follows the pairs routed here, and the decoder has no other
loop at the level of XLA (the attention kernels loop inside their Mosaic
calls), so the loops are found by `^%?while` on the operation's HLO text.
The router's matmul, the top-k and the sort of the pairs run outside the
loops under fusion names of XLA's own and are not in this share. Returns
nothing where the trace holds no such operation."""

LOOPS = r"^%?while"


def read(facts):
    tr = facts.get("trace")
    step = tr.module_time() if tr is not None else None
    if step is None:
        return None
    seconds, calls = tr.op_time(LOOPS)
    if not calls:
        return None
    return 100.0 * seconds / (step[1] * step[2])
