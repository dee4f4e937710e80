"""Op lowering library — importing this package populates the registry."""

from . import registry
from .registry import (LoweringContext, execute, get_op_def, is_registered,
                       register, registered_ops)

from . import math_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import reduce_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import decoder_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import proposal_ops  # noqa: F401
from . import delegate_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import linalg_ops  # noqa: F401
from . import image_ops  # noqa: F401
from . import index_ops  # noqa: F401
from . import ctr_ops  # noqa: F401
from . import structured_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import ps_ops  # noqa: F401
