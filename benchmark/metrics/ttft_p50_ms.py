"""Layer: entry and scheduler, serving. Source: host_clock (the client's
side of the HTTP stream, from the instant a request was due to its first
token; median over the window's requests)."""
import statistics


def read(facts):
    ttft = facts.get("ttft_ms")
    return statistics.median(ttft) if ttft else None
