"""Op registry package — importing this module registers the core op set.

Counterpart of the reference's operator registration at library-load time
(ref: src/operator/** static NNVM_REGISTER_OP initialisers, listed through
MXListAllOpNames and surfaced to Python by generated wrappers).
"""
from . import registry
from .registry import (OP_REGISTRY, Operator, apply_pure, get_op, invoke,
                       list_ops, register_op)

# registration side effects
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import rnn  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import contrib  # noqa: F401
from . import pallas_attention  # noqa: F401
from . import eva_attention  # noqa: F401
from . import latent_attention  # noqa: F401
from . import differential_attention  # noqa: F401
from . import pallas_convbn  # noqa: F401
from . import linalg  # noqa: F401
from . import image_ops  # noqa: F401
from . import quantization  # noqa: F401
from . import ssm  # noqa: F401
from . import selective_scan  # noqa: F401
from . import moe  # noqa: F401
from . import rotary  # noqa: F401
from . import short_conv  # noqa: F401

__all__ = ["registry", "OP_REGISTRY", "Operator", "apply_pure", "get_op",
           "invoke", "list_ops", "register_op"]
