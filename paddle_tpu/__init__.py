"""paddle_tpu — a TPU-native deep learning framework.

Re-design of PaddlePaddle's capability surface (reference snapshot at
/root/reference, see SURVEY.md) on jax/XLA/pallas: imperative (dygraph) API
with tape autograd, whole-program XLA compilation via @to_static, device-mesh
parallelism (dp/mp/pp/sharding) through GSPMD + shard_map, bf16-first AMP,
and pallas kernels for the fused hot ops.
"""
import time as _time

_import_t0 = _time.perf_counter_ns()  # `import_ns`, at the end of the file

__version__ = "0.1.0"

# core
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.autograd import no_grad, enable_grad, grad  # noqa: F401
from .core.device import (  # noqa: F401
    set_device, get_device, is_compiled_with_tpu, device_count,
    CPUPlace, TPUPlace, Place,
)
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .core import dtype as _dtype_mod
from .core.dtype import (  # noqa: F401
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128,
)

# ops (also patches Tensor methods)
from . import ops  # noqa: F401
from . import onnx  # noqa: F401
from .ops import *  # noqa: F401,F403
from . import linalg  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .ops.math import (  # noqa: F401
    add, subtract, multiply, divide, matmul, mean, sum, max, min,
)
from .ops.manipulation import concat  # noqa: F401

# subpackages
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import jit  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import static  # noqa: F401
from . import distributed  # noqa: F401
from . import autograd  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import checkpoint  # noqa: F401
from . import testing  # noqa: F401
from . import incubate  # noqa: F401

from . import recompute  # noqa: F401
from . import profiler  # noqa: F401
from . import monitor  # noqa: F401
from . import observability  # noqa: F401
from . import analysis  # noqa: F401
from . import distribution  # noqa: F401
from . import text  # noqa: F401
from . import dataset  # noqa: F401
from . import quantization  # noqa: F401
from . import sparsity  # noqa: F401
from .core.flags import set_flags, get_flags  # noqa: F401
from .core import enforce  # noqa: F401
from .core import op_version  # noqa: F401

from .nn.layer.layers import ParamAttr  # noqa: F401
from .serialization import save, load  # noqa: F401
from .hapi.model import Model, summary  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .hapi import hub  # noqa: F401
from .regularizer import L1Decay, L2Decay  # noqa: F401

from .core.tensor import Tensor as _T

# paddle-style aliases
disable_static = lambda *a, **k: None  # dygraph is the default mode
enable_static = static._enable_static


def is_grad_enabled():
    from .core import autograd as _ag
    return _ag.grad_enabled()


def in_dynamic_mode():
    return not static._static_mode()


def get_default_dtype():
    return "float32"


def set_default_dtype(dtype):
    raise NotImplementedError("float32 is the fixed default; cast per-tensor")


def set_grad_enabled(flag):
    from .core import autograd as _ag
    _ag._state.enabled = bool(flag)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.model import flops as _flops
    return _flops(net, input_size)


# from this file's first statement to here, once a process: what
# `import paddle_tpu` costs a start (jax's own import too, where this is
# the first to ask for it)
monitor.stat_add("import_ns", _time.perf_counter_ns() - _import_t0)
del _time, _import_t0
