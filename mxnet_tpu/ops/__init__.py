"""Operator library: registry + op families.

Importing this package registers every operator (the analogue of the static
NNVM_REGISTER_OP initializers in src/operator/*.cc).
"""
from . import registry
from .registry import OpDef, apply_op, get_op, invoke, list_ops, register

from . import math as _math            # noqa: F401  tensor/elemwise/linalg
from . import nn as _nn                # noqa: F401  neural-net kernels
from . import rnn as _rnn              # noqa: F401  fused RNN
from . import linear_attention as _la  # noqa: F401  gated delta rule
from . import moe as _moe              # noqa: F401  router, grouped experts
from . import state_space as _ssm      # noqa: F401  Mamba-2 chunked scan
from . import optimizer_ops as _opt    # noqa: F401  optimizer updates
from . import random_ops as _rand      # noqa: F401  samplers
from . import detection as _det        # noqa: F401  SSD/R-CNN contrib ops
from . import control_flow as _cf      # noqa: F401  foreach/while/cond
from . import quantization as _quant   # noqa: F401  int8 quantize family
from . import image_ops as _img        # noqa: F401  on-device augmentation
from . import vision_extra as _vx      # noqa: F401  legacy vision/contrib tail
from . import parity_aliases as _pa    # noqa: F401  internal-name tail (last)

__all__ = ["OpDef", "register", "get_op", "list_ops", "invoke", "apply_op"]
