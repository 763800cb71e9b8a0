"""Layer: KV arena. Source: program_counter (DecodeSession.metrics
`decode_prefill_stalls`, what the window added): prefill dispatches longer
than the declared chunk while a generating sequence waited."""


def read(facts):
    counters = facts.get("counters")
    return None if not counters else counters["decode_prefill_stalls"]
