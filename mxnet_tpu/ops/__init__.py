"""Operator library: importing this package registers all operators.

Layout mirrors the reference src/operator/ split (SURVEY.md §2.1):
elemwise/reduce/matrix ≈ src/operator/tensor/, nn ≈ src/operator/nn/,
init_ops+random ≈ init_op.cc + src/operator/random/, optimizer_ops ≈
optimizer_op.cc, rnn_ops ≈ rnn.cc (via lax.scan), control_flow ≈
control_flow.cc, contrib ≈ src/operator/contrib/.
"""

from . import registry  # noqa: F401
from . import elemwise  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import init_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn  # noqa: F401
from . import contrib  # noqa: F401
from . import attention  # noqa: F401
from . import llm  # noqa: F401
from . import custom  # noqa: F401
from . import quantization  # noqa: F401
from . import linalg  # noqa: F401
from . import extended  # noqa: F401

from .registry import apply_op, get, list_ops, register  # noqa: F401
