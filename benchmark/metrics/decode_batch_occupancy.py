"""Layer: entry and scheduler, serving. Source: program_counter (the arena's
free-slot gauge, sampled every 20 ms while the window is open). Live slots
over capacity, mean over the samples."""


def read(facts):
    live = facts.get("live_slots")
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / facts["slot_capacity"]
