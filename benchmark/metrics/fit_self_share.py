"""Layer: entry, training. Source: program_span (`fit.epoch` less its
children `fit.input`, `fit.step`, `fit.pace`, `fit.metric_sync`,
`fit.callbacks`, `fit.eval`). Share of the traced window that is fit's own
Python between its phases: the self time of the entry layer."""
from benchmark import spans


def read(facts):
    sp = spans.load(facts)
    return None if sp is None else sp.share(sp.self_ns("fit.epoch"))
