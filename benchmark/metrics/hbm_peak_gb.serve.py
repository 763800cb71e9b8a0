"""Layer: device. Source: program_counter (benchmark/chip.py
`memory_peak_bytes` of the chip, read when the server has shut and before
the reference runs)."""


def read(facts):
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
