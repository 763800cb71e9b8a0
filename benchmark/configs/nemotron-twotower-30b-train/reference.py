"""Plain reference of `nemotron-twotower-30b-train`:
benchmark/references/nemotron_h.py."""
from benchmark.references.nemotron_h import (block_loss,  # noqa: F401
                                             forward, param_specs,
                                             route_choices, split_rows)
