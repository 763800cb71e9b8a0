"""Layer: compile pipeline and cache. Source: host_clock (jax.monitoring
trace + lower + backend-compile durations of the whole process)."""


def read(facts):
    return facts.get("compile_s")
