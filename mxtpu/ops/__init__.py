"""Operator library: importing this package registers every op.

See registry.py for the design; families mirror SURVEY.md §2.3 / Appendix A.
"""
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import linalg  # noqa: F401
from . import rnn  # noqa: F401
from . import contrib  # noqa: F401
from . import spatial  # noqa: F401
from . import custom  # noqa: F401
from . import attention  # noqa: F401
from . import delta_rule  # noqa: F401
from . import ssd  # noqa: F401
from . import rotary  # noqa: F401
from . import heads  # noqa: F401
from . import moe  # noqa: F401
from .registry import OpDef, get_op, list_ops, op_exists, register  # noqa: F401
