"""Operator registry and implementations (jax/lax-backed).

Reference parity: the nnvm op registry + src/operator/* kernel tree
(NNVM_REGISTER_OP; FCompute dispatch — include/mxnet/op_attr_types.h ~L60).
On TPU each op is a pure jax function; XLA performs the kernel fusion that
mshadow expression templates / FusedOp RTC do in the reference.
"""
from .registry import Operator, register, get_op, invoke, list_ops
from . import params  # noqa: F401  (typed param descriptors)

from . import elemwise  # noqa: F401
from . import creation  # noqa: F401
from . import reduce_ops  # noqa: F401
from . import matrix  # noqa: F401
from . import indexing  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import cv_ops  # noqa: F401
from . import quantization  # noqa: F401
from . import warp_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import hc_ops  # noqa: F401
