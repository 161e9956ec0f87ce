"""Gluon neural-network layers (reference: python/mxnet/gluon/nn/)."""
from .activations import *  # noqa: F401,F403
from .basic_layers import *  # noqa: F401,F403
from .conv_layers import *  # noqa: F401,F403
from .seq_layers import *  # noqa: F401,F403

from . import activations, basic_layers, conv_layers, seq_layers

__all__ = (activations.__all__ + basic_layers.__all__ +  # noqa: F405
           conv_layers.__all__ + seq_layers.__all__)  # noqa: F405
