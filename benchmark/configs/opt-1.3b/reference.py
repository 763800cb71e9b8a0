"""Plain reference of `opt-1.3b`: benchmark/references/opt.py (the same file
as `opt-1.3b-train`; the parameter names are shared)."""
from benchmark.references.opt import forward, param_specs  # noqa: F401
