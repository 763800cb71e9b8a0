"""Layer: entry, training. Source: program_span (`fit.step`: fit's
`forward_backward` + `update`, the dispatch, feeding `fit_dispatch_ms`) over
device_trace. Share of the traced window in which the first chip is idle and
the host is issuing a step: dispatch the device did not hide."""
from benchmark import spans


def read(facts):
    sp = spans.load(facts)
    return None if sp is None else sp.share(sp.idle_in_ns("fit.step"))
