"""Layer: entry, training. Source: program_counter (mxtpu.telemetry
`fit_dispatch_ms`, bucket mid-point). Host time to issue one step."""


def read(facts):
    tel = facts.get("telemetry")
    if not tel:
        return None
    return tel["fit_dispatch_ms"]["p50"]
