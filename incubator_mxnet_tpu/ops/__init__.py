"""Operator library.

TPU-native equivalent of `src/operator/` (reference, 113.7 kLoC C++/CUDA):
each module registers pure jax-traceable compute functions with the central
registry (`registry.py`); XLA compiles them to TPU kernels, so there are no
per-backend kernel files.  Frontend namespaces (`nd.*`, `sym.*`) are generated
from this registry at import, like the reference generates Python ops from
`MXSymbolListAtomicSymbolCreators`.
"""
from . import registry
from .registry import register, get, list_ops, OpDef, REQUIRED

# op definition modules — import order only matters for alias collisions
from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import matrix        # noqa: F401
from . import init_ops      # noqa: F401
from . import random_ops    # noqa: F401
from . import nn            # noqa: F401
from . import attention     # noqa: F401
from . import lm_ops        # noqa: F401
from . import delta_rule    # noqa: F401
from . import experts       # noqa: F401
from . import loss_output   # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import linalg_ops    # noqa: F401
from . import contrib_ops   # noqa: F401
from . import ctc           # noqa: F401
from . import detection     # noqa: F401
from . import spatial       # noqa: F401
from . import image_ops     # noqa: F401
from . import control_flow  # noqa: F401
from . import contrib_tail  # noqa: F401
from . import quantization  # noqa: F401
