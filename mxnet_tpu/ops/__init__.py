"""Functional operator library (single source of truth for nd/sym/jit).

Importing this package registers the full op surface. The Pallas kernels
(BN-apply + ReLU + 1x1 convolution) live in ``mxnet_tpu.ops.pallas_fused``.
"""
from .registry import (OpDef, register_op, get_op, has_op, list_ops, alias,
                       parse_attr)

from . import elemwise  # noqa: F401
from . import reduce  # noqa: F401
from . import shape_ops  # noqa: F401
from . import creation  # noqa: F401
from . import nn  # noqa: F401
from . import seq  # noqa: F401
from . import random_ops  # noqa: F401
from . import linalg  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import contrib  # noqa: F401
from . import surface  # noqa: F401
from . import pallas_fused  # noqa: F401

__all__ = ["OpDef", "register_op", "get_op", "has_op", "list_ops", "alias",
           "parse_attr"]
