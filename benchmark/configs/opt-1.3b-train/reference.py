"""Plain reference of `opt-1.3b-train`: benchmark/references/opt.py."""
from benchmark.references.opt import (block_loss, forward, param_specs,  # noqa: F401
                                      split_rows)
