"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle (reference: data-mining/Paddle), built from scratch on
JAX/XLA/Pallas/pjit.

Usage mirrors paddle::

    import paddle_tpu as paddle
    paddle.set_device('tpu')
    x = paddle.to_tensor([[1., 2.], [3., 4.]], stop_gradient=False)
    y = (x * x).sum()
    y.backward()
    x.grad  # Tensor([[2., 4.], [6., 8.]])

See SURVEY.md at the repo root for the layer map from the reference onto this
design.
"""

from . import flags as _flags_mod
from .flags import get_flags, set_flags, define_flag  # noqa: F401


def _compile_cache_dir(environ, platforms):
    """The directory this package points JAX's persistent compilation cache
    at, or ``None`` when it sets none.

    ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from outside,
    jax reads the variable itself and no code sets a directory. Unset: a
    fixed path inside the checkout — the path is part of the cache key, so
    it is never a tempdir, a pid or a timestamp — except when the process
    is pinned to the CPU backend (``JAX_PLATFORMS=cpu``, the test tier):
    CPU executables served from a cache shared by heterogeneous processes
    were found unsound (tests/conftest.py), and a CPU compile is cheap."""
    import os as _os

    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if (platforms or "").strip().lower() == "cpu":
        return None
    return _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")


def compile_cache_dir():
    """The compile-cache directory in effect for this process — the
    environment's, else the one this package set — or ``None`` (benches
    report warm compiles, and ``chip_smoke.py`` counts entries, only where
    there is one)."""
    import os as _os

    import jax as _jax

    return _os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        _compile_cache_dir(_os.environ, _jax.config.jax_platforms)


def _wire_compile_cache() -> None:
    """The one place a compile-cache directory is set (fleet workers,
    benches and tests inherit the environment variable instead). Where a
    cache is in effect the size/time thresholds drop to zero so small
    per-op programs round-trip too — the cache is content-addressed, so
    sharing a directory across configurations is safe."""
    import os as _os

    import jax as _jax

    d = _compile_cache_dir(_os.environ, _jax.config.jax_platforms)
    if d is not None:
        _jax.config.update("jax_compilation_cache_dir", d)
    if compile_cache_dir():
        _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


_wire_compile_cache()

from .device import (  # noqa: F401
    Place, CPUPlace, TPUPlace, CUDAPlace, CustomPlace,
    XPUPlace, MLUPlace, IPUPlace, CUDAPinnedPlace,
    set_device, get_device, device_count,
    is_compiled_with_cuda, is_compiled_with_tpu,
    is_compiled_with_xpu, is_compiled_with_rocm, is_compiled_with_ipu,
    is_compiled_with_mlu, is_compiled_with_cinn, is_compiled_with_distribute,
    is_compiled_with_custom_device,
)

from .core.dtype import (  # noqa: F401
    bfloat16, float16, float32, float64,
    int8, int16, int32, int64, uint8, uint16, uint32, uint64,
    bool_ as bool8, complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype, finfo, iinfo, promote_types,
    is_floating_point, is_integer, is_complex,
)
# paddle exposes `paddle.bool`
bool = bool8  # noqa: A001

from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.tracing import no_grad, enable_grad, set_grad_enabled  # noqa: F401
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .core import autograd as _autograd_mod
from .core.autograd import grad  # noqa: F401

# install the op surface (also populates Tensor methods)
from . import ops as _ops_pkg
from .ops import OP_REGISTRY as _OP_REGISTRY


def _install_ops() -> None:
    g = globals()
    for name, fn in _OP_REGISTRY.items():
        if name not in g:
            g[name] = fn


_install_ops()

# subpackage namespaces (imported lazily-ish at the end: they use the ops)
from . import distributed  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402
from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import linalg  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import framework  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import models  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import observability  # noqa: F401,E402
# PADDLE_TPU_TRACE=on at import: the per-op trace hook could not install
# while the core was still importing — re-sync now that it exists
observability.trace._sync_op_hook()
from . import resilience  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import static  # noqa: F401,E402
from .static import enable_static, disable_static  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from .hapi import Model, summary  # noqa: F401,E402
from .hapi import callbacks  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import version  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from .nn.layer import LazyGuard, ParamAttr  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import tensor  # noqa: F401,E402
from .flops_counter import flops  # noqa: F401,E402
from .framework.io import save, load  # noqa: F401,E402
from .framework import in_dynamic_mode, in_pir_mode  # noqa: F401,E402
from .framework.random import (  # noqa: F401,E402
    get_cuda_rng_state, set_cuda_rng_state,
)
from .core.tracing import grad_enabled as _grad_enabled  # noqa: E402


def is_grad_enabled() -> bool:
    """Whether autograd is recording (parity: paddle.is_grad_enabled)."""
    return _grad_enabled()


def in_static_mode() -> bool:
    return not in_dynamic_mode()

__version__ = "0.1.0"


def disable_signal_handler() -> None:
    """Parity no-op: the reference installs SIGSEGV/SIGBUS handlers in C++;
    this runtime does not install signal handlers at all."""


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None) -> None:
    """Configure numpy-backed tensor printing (parity:
    paddle.set_printoptions)."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)
