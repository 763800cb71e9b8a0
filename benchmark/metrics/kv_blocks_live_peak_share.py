"""Layer: KV arena. Source: program_counter (PagedArena.blocks_live, sampled
every 20 ms while the window is open). Most blocks live at once over the
pool's blocks."""


def read(facts):
    blocks = facts.get("live_blocks")
    if not blocks:
        return None
    return 100.0 * max(blocks) / facts["blocks_total"]
