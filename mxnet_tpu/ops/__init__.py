"""Operator library: one registry, pure JAX implementations.

Importing this package registers all ops (analog of the reference's static
registration at library load; src/operator/*.cc NNVM_REGISTER_OP blocks).
"""
from . import registry
from .registry import register, get, list_ops, alias, Operator

from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import matrix        # noqa: F401
from . import nn            # noqa: F401
from . import random_ops    # noqa: F401
from . import init_ops      # noqa: F401
from . import optimizer_ops # noqa: F401
from . import image_ops     # noqa: F401
from . import quantization  # noqa: F401
from . import quant_serve   # noqa: F401
from . import contrib_ops   # noqa: F401
from . import custom_op     # noqa: F401
from . import vision_ops    # noqa: F401
from . import pallas_flash  # noqa: F401
from . import lm_ops        # noqa: F401
from ..kernels import bn_act as _kernel_bn_act    # noqa: F401  (tier ops)
from ..kernels import mlp as _kernel_mlp          # noqa: F401
from . import linalg        # noqa: F401
from . import legacy_aliases  # noqa: F401  (must come after the bases)
