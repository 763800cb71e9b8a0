"""Layer: input. Source: program_span (`fit.input`) over device_trace. Share
of the traced window in which the first chip is idle and the host is inside
`fit.input`: the wait for data that starves the device, as against the wait
that overlaps device work."""
from benchmark import spans


def read(facts):
    sp = spans.load(facts)
    return None if sp is None else sp.share(sp.idle_in_ns("fit.input"))
