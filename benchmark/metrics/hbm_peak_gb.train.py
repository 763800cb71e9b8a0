"""Layer: device. Source: program_counter
(benchmark/chip.py `memory_peak_bytes` of the fullest chip, read when the
window closes and before the reference runs)."""


def read(facts):
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
