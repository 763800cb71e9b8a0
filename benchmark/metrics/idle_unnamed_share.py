"""Layer: device. Source: program_span over device_trace. Share of the first
chip's idle time in the traced window during which the host is in none of
fit's phase spans (only in `fit`'s or `fit.epoch`'s own time, or outside the
program): what the tracing does not name; 0 where the chip was never idle."""
from benchmark import spans


def read(facts):
    sp = spans.load(facts)
    if sp is None or sp.idle is None:
        return None
    return 100.0 * sp.idle_unnamed_ns() / sp.idle_ns() if sp.idle_ns() else 0.0
