"""Op library: importing this package registers every op lowering.

Organization mirrors the reference's operator groups (SURVEY.md §2.2):
math/elementwise/activations, tensor manipulation, NN (conv/pool/norm/
embedding), optimizers, metrics, sequence (LoD), control flow, detection.
"""
from . import math_ops        # noqa: F401
from . import tensor_ops      # noqa: F401
from . import nn_ops          # noqa: F401
from . import optimizer_ops   # noqa: F401
from . import metric_ops      # noqa: F401
from . import control_ops     # noqa: F401
from . import array_ops       # noqa: F401
from . import decode_ops      # noqa: F401
from . import quant_ops       # noqa: F401
from . import sequence_ops    # noqa: F401
from . import rnn_ops         # noqa: F401
from . import sparse_ops      # noqa: F401
from . import detection_ops   # noqa: F401
from . import moe_ops         # noqa: F401
from . import llm_ops         # noqa: F401
from . import linear_attention_ops  # noqa: F401
from . import state_space_ops  # noqa: F401
from . import pipeline_ops    # noqa: F401
