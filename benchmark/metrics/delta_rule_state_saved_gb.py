"""Layer: step programs. Source: program_counter. Bytes of chunk-boundary
state the forward of the gated delta rule keeps for its backward, a step:
the program's gauge `delta_rule_state_saved_bytes` (one differentiated
call's, docs/observability.md; read in process from mxtpu.telemetry, as
benchmark/spans.py reads mxtpu.obs.trace) times the linear-attention layers
the configuration holds. Returns nothing where the program has no such
gauge."""


def read(facts):
    cfg = facts.get("config")
    if not cfg or "layer_types" not in cfg:
        return None
    try:
        from mxtpu import telemetry
    except ImportError:
        return None
    per_call = [m.value for m in telemetry.registry().series()
                if m.name == "delta_rule_state_saved_bytes"]
    if not per_call or not per_call[0]:
        return None
    layers = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return per_call[0] * layers.count("linear_attention") / 1e9
