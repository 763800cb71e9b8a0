"""Layer: input. Source: program_span (the benchmark's own span around the
iterator's next()). Share of the window spent inside the DataIter."""


def read(facts):
    if "input_wait_s" not in facts:
        return None
    return 100.0 * facts["input_wait_s"] / facts["window_s"]
