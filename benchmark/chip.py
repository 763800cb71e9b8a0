"""The device the benchmark runs on: the table of peaks, the look for a chip,
the compile meter and the memory reading. Copied from bench.py
(`require_chip`, `PEAK_TFLOPS`) and chip_smoke.py (`CompileMeter`), which a
later `simplicity` PR may delete; this copy is the yardstick's own.
"""
import collections

# Per-chip peaks by jax `device_kind`. Source: Google Cloud documentation,
# "TPU v5e" system architecture: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
# HBM2e at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect. A kind that is
# not listed is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def require_chip(chips):
    """The contract's `device` object and the chip's peaks. On a CPU
    platform, a device_kind without peaks or too few chips it exits with
    code 1 and the reason on standard error."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform == "cpu":
        raise SystemExit("benchmark: no accelerator: jax platform is %r"
                         % d0.platform)
    if d0.device_kind not in PEAKS:
        raise SystemExit("benchmark: no peaks on record for device_kind %r; "
                         "add it to benchmark/chip.py PEAKS with its source"
                         % d0.device_kind)
    if len(devs) < chips:
        raise SystemExit("benchmark: cell asks for %d chips, jax sees %d"
                         % (chips, len(devs)))
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    return device, PEAKS[d0.device_kind]


def cpu_rehearsal_device():
    """Stand-in for `require_chip` in the tests and `--rehearse` runs: the
    device as JAX reports it and the v5e peaks. No result line is printed."""
    import jax
    d0 = jax.devices()[0]
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(jax.devices())}, PEAKS["TPU v5 lite"])


def open_device(chips, rehearse=False):
    """What every entry of the benchmark does before it touches mxtpu: the
    cache setting below, then the look for a chip (or, rehearsing, the CPU).
    Returns (the contract's `device` object, the chip's peaks)."""
    cache_every_program()
    return cpu_rehearsal_device() if rehearse else require_chip(chips)


def cache_every_program():
    """Keep the many programs that compile in under a second in JAX's
    persistent cache too: a warm set-up of the LM cell falls from 35 to 30 s
    with 56 of 56 programs found, not 11 (PERF.md)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_bytes(devices):
    """Bytes held on the fullest of `devices` now: live arrays plus what the
    runtime has reserved for the loaded programs' temporaries. On the TPU
    `peak_bytes_in_use` leaves the temporaries out (a jitted call with 1.07
    GB of them moved `bytes_reserved`, not `bytes_in_use`: my chip run, PR
    24), so the peak the benchmark reports is the larger of
    `peak_bytes_in_use` and this sum as read at the window's two ends."""
    held = 0
    for d in devices:
        stats = d.memory_stats() or {}
        held = max(held, int(stats.get("bytes_in_use", 0))
                   + int(stats.get("bytes_reserved", 0)))
    return held


def memory_peak_bytes(devices, samples=()):
    """Peak on the fullest of `devices`: `peak_bytes_in_use`, or the
    largest of `samples` (readings of `memory_bytes`) where that is more.
    0 where the backend reports none, as the CPU does."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return max([peak] + list(samples))


class CompileMeter:
    """Every trace/lower/compile JAX does in this process and what the
    persistent cache did with it (jax.monitoring events)."""

    def __init__(self):
        import jax
        self.rows = []      # (stage, program name, seconds)
        self.cache = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event.startswith("/jax/core/compile/"):
            self.rows.append((event.rsplit("/", 1)[1],
                              str(kw.get("fun_name", "?")), float(secs)))

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.cache[event.rsplit("/", 1)[1]] += 1

    def mark(self):
        return len(self.rows), collections.Counter(self.cache)

    def since(self, mark=None):
        """What was compiled since `mark` (the whole process without one)."""
        n0, cache0 = mark or (0, collections.Counter())
        rows = self.rows[n0:]
        backend = [(n, s) for st, n, s in rows
                   if st == "backend_compile_duration"]
        cache = self.cache - cache0
        return {"compile_s": sum(s for _, _, s in rows),
                "programs": [n for n, _ in backend],
                "cache_hits": cache["cache_hits"],
                "cache_misses": cache["cache_misses"]}
