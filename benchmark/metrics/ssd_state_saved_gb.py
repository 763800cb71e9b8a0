"""Layer: step programs. Source: program_counter. Bytes of chunk-boundary
state the forward of the state-space scan keeps for its backward, a step:
the program's gauge `ssd_state_saved_bytes` (one differentiated call's,
docs/observability.md; read in process from mxtpu.telemetry, as
delta_rule_state_saved_gb.py reads its own) times the `M` layers the
configuration holds. Returns nothing where the program has no such gauge or
the configuration no such layer."""


def read(facts):
    cfg = facts.get("config")
    if not cfg or "hybrid_override_pattern" not in cfg:
        return None
    try:
        from mxtpu import telemetry
    except ImportError:
        return None
    per_call = [m.value for m in telemetry.registry().series()
                if m.name == "ssd_state_saved_bytes"]
    if not per_call or not per_call[0]:
        return None
    held = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return per_call[0] * held.count("M") / 1e9
