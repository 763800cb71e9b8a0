"""Layer: compile pipeline and cache. Source: program_counter
(jax.monitoring backend compiles between the window's first and last
instant). Must read 0; any other value also fails `correct`."""


def read(facts):
    return facts.get("window_compiles")
