"""Layer: load generator. Source: host_clock. How late the client sent a
request against the instant it was due, 95th percentile: a starved
generator must not be read as a fast server."""
from benchmark.generators.open_loop_http import percentile


def read(facts):
    late = facts.get("late_ms")
    return percentile(late, 95) if late else None
