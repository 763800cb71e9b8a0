"""Plain reference of `resnet50`: benchmark/references/resnet.py."""
from benchmark.references.resnet import (aux_specs, block_loss, forward,  # noqa: F401
                                         param_specs, split_rows)
