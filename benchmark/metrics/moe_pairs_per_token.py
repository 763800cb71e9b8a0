"""Layer: step programs. Source: program_counter. (token, expert) pairs the
expert layers held here were handed, a token a layer: the program's
counters `moe_pairs_routed` over `moe_tokens_seen` (docs/observability.md;
read in process from mxtpu.telemetry, counted from the loads that fit
fetches at its metric syncs). At top_k 10 of 256 experts with 8 held the
uniform expectation is 0.3125. Returns nothing where the program has no
such counters or the configuration no
expert layer."""


def counted():
    """(pairs routed, tokens seen) or None."""
    try:
        from mxtpu import telemetry
    except ImportError:
        return None
    got = {m.name: m.value for m in telemetry.registry().series()
           if m.name in ("moe_pairs_routed", "moe_tokens_seen")}
    if not got.get("moe_tokens_seen"):
        return None
    return got.get("moe_pairs_routed", 0), got["moe_tokens_seen"]


def read(facts):
    if "sparse" not in (facts.get("config") or {}).get("mlp_layer_types", ()):
        return None     # the counters are the process's, whatever ran in it
    got = counted()
    return None if got is None else got[0] / got[1]
