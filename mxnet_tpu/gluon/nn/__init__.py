"""Gluon neural-network layers (reference: python/mxnet/gluon/nn/)."""

from .basic_layers import *  # noqa: F401,F403
from .basic_layers import Activation  # noqa: F401
from .conv_layers import *  # noqa: F401,F403
from .activations import *  # noqa: F401,F403
from .transformer import *  # noqa: F401,F403
from .mla_moe import *  # noqa: F401,F403
from .hybrid_lm import *  # noqa: F401,F403
