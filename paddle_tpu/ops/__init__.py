"""Operator library: JAX kernels for every op family the reference ships.

Reference parity: paddle/fluid/operators/ (~170 op families, 437 files).
Importing this package registers all kernels with core.registry. Each module
header cites the reference files it covers.
"""

from . import util
from . import math_ops
from . import activation_ops
from . import tensor_ops
from . import bn_pool
from . import nn_ops
from . import lm_ops
from . import optimizer_ops
from . import sequence_ops
from . import loss_ops
from . import beam_search_ops
from . import rnn_ops
from . import control_flow_ops
from . import concurrency_ops
from . import io_ops
from . import metric_ops
from . import detection_ops
from . import collective_ops
from . import sparse_ops
from . import rpc_ops
from . import reader_ops
