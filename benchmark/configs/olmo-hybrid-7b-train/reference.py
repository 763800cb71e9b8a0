"""Plain reference of `olmo-hybrid-7b-train`: benchmark/references/olmo_hybrid.py."""
from benchmark.references.olmo_hybrid import (block_loss, forward,  # noqa: F401
                                              param_specs, split_rows)
