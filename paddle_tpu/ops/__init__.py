"""Operator library: importing this package registers all lowering rules."""
from . import math  # noqa: F401
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import loss  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import control_flow  # noqa: F401
from . import amp_ops  # noqa: F401
from . import recompute  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import generation  # noqa: F401
from . import moe  # noqa: F401
from . import gdn  # noqa: F401
from . import ssd  # noqa: F401
from . import latent_attention  # noqa: F401
from . import hyper_connection  # noqa: F401
from . import block_diffusion  # noqa: F401
from . import detection  # noqa: F401
from . import quant_ops  # noqa: F401
from . import fused_attention  # noqa: F401
from . import pipeline_op  # noqa: F401
from . import image  # noqa: F401
from . import misc  # noqa: F401
from . import misc2  # noqa: F401
from . import structured  # noqa: F401

from ..core.registry import all_ops, get_op_def, has_op, register_op  # noqa: F401
