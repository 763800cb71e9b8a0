"""Layer: step programs. Source: program_counter. The fullest held expert's
pairs over the mean held expert's, the worst expert layer of the last step
whose loads fit fetched: the program's gauge `moe_load_max_over_mean`
(docs/observability.md; read in process from mxtpu.telemetry). 1 is an even
load. Returns nothing where the program has no such gauge."""


def read(facts):
    if "sparse" not in (facts.get("config") or {}).get("mlp_layer_types", ()):
        return None     # the gauge is the process's, whatever ran in it
    try:
        from mxtpu import telemetry
    except ImportError:
        return None
    got = [m.value for m in telemetry.registry().series()
           if m.name == "moe_load_max_over_mean"]
    return got[0] if got and got[0] else None
