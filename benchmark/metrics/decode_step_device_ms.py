"""Layer: step programs. Source: device_trace. Device time of one run of the
decode step program: the program that ran most often in the traced slice
among those the configuration's `trace_programs.decode_step` pattern names
(every program, where the pattern is empty)."""


def read(facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    pattern = facts["config"].get("trace_programs", {}).get("decode_step")
    got = tr.module_time(pattern or None, by="runs")
    return None if got is None else got[1] * 1e3
