"""Layer: input. Source: program_span (`fit.input`: each `next(data_iter)`
of `Module.fit` with `prepare()`, feeding `fit_input_wait_ms`). Share of the
traced window that fit spent waiting for its next batch, whether the chip
ran meanwhile or not (`input_starved_share` is the part in which it did
not)."""
from benchmark import spans


def read(facts):
    sp = spans.load(facts)
    return None if sp is None else sp.share(sp.covered_ns("fit.input"))
