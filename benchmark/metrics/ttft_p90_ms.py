"""Layer: entry and scheduler, serving. Source: host_clock (the client's
side of the HTTP stream; 90th percentile over the window's requests, a failed
request counting as the time-out). The percentile the ten-samples rule gives
at 160 requests; it spread 5.7% and 10.3% over two sets of six runs where the
95th spread 2.2% and 2.4% (PERF.md), so the 95th is the end-to-end metric and
this one stands beside it."""
from benchmark.generators.open_loop_http import percentile


def read(facts):
    ttft = facts.get("ttft_ms")
    return percentile(ttft, 90) if ttft else None
