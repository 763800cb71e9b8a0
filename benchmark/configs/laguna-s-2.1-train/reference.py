"""Plain reference of `laguna-s-2.1-train`: benchmark/references/laguna.py."""
from benchmark.references.laguna import (block_loss, forward,  # noqa: F401
                                         param_specs, route_choices,
                                         split_rows)
