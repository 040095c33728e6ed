"""mxnet_tpu: a TPU-native deep learning framework with the API surface of
dmlc-era MXNet (reference at /root/reference), rebuilt from scratch on
jax/XLA/pjit/Pallas.

Layer map (vs SURVEY.md §1): the reference's engine/storage/graph-executor
layers collapse into XLA's runtime and compiler; what remains user-visible —
NDArray, Symbol, Executor, KVStore, DataIter, FeedForward — is re-implemented
TPU-first here.
"""
from __future__ import annotations

import jax as _jax

# Honor explicit float64 dtypes (the reference supports f64 arrays; JAX
# truncates to f32 unless x64 is enabled). Python scalars stay weakly typed,
# so f32/bf16 compute paths are unaffected. NOTE: this is process-global; a
# host program mixing its own JAX code with this library will also see x64
# honored. Framework-internal code must therefore pass explicit dtypes (or
# python-float scalars) everywhere — never numpy float64 scalars.
_jax.config.update("jax_enable_x64", True)

# A persistent compilation cache, where one is on, is keyed by the
# programs' scope names too (compile_cache.py says why, and why here and
# not only in compile_cache.enable()).
from . import compile_cache as _compile_cache  # noqa: E402
_compile_cache.names_in_key()

from .base import MXNetError  # noqa: E402
from .context import Context, current_context, cpu, gpu, tpu, cpu_pinned  # noqa: E402
from . import ndarray  # noqa: E402
from . import ndarray as nd  # noqa: E402
from .ndarray import NDArray  # noqa: E402
from . import random  # noqa: E402
from . import symbol  # noqa: E402
from . import symbol as sym  # noqa: E402
from .symbol import Symbol, Group  # noqa: E402
from . import executor  # noqa: E402
from .executor import Executor  # noqa: E402
from . import operator  # noqa: E402
from .attribute import AttrScope  # noqa: E402
from .name import NameManager, Prefix  # noqa: E402
from . import optimizer  # noqa: E402
from . import metric  # noqa: E402
from . import initializer  # noqa: E402
from .initializer import Uniform, Normal, Orthogonal, Xavier, MSRAPrelu  # noqa: E402
from . import lr_scheduler  # noqa: E402
from . import misc  # noqa: E402
from . import telemetry  # noqa: E402
from . import profiler  # noqa: E402
from . import io  # noqa: E402
from . import kvstore  # noqa: E402
from . import kvstore as kv  # noqa: E402
# NOTE: kvstore_server is intentionally NOT imported here — importing it
# in a server/scheduler-role process joins the server loop (reference
# python/mxnet/kvstore_server.py:57-68 semantics); use
# `import mxnet_tpu.kvstore_server` explicitly, as the reference does.
from . import executor_manager  # noqa: E402
from . import callback  # noqa: E402
from . import monitor  # noqa: E402
from .monitor import Monitor  # noqa: E402
from . import model  # noqa: E402
from .model import FeedForward  # noqa: E402
from . import parallel  # noqa: E402
from .parallel import ParallelTrainer  # noqa: E402
from . import recordio  # noqa: E402
from . import image_io  # noqa: E402
from .image_io import ImageRecordIter, DeviceAugmentIter  # noqa: E402
from .io import DevicePrefetchIter  # noqa: E402
from . import distributed  # noqa: E402
from . import visualization  # noqa: E402
# reference short aliases (/root/reference/python/mxnet/__init__.py):
# mx.init, mx.viz, mx.mon, mx.rnd, mx.th
from . import initializer as init  # noqa: E402
from . import visualization as viz  # noqa: E402
from . import monitor as mon  # noqa: E402
from . import random as rnd  # noqa: E402
from . import rtc  # noqa: E402
from . import torch  # noqa: E402
from . import torch as th  # noqa: E402
from . import predict  # noqa: E402
from .predict import Predictor  # noqa: E402
from . import serving  # noqa: E402
from .serving import InferenceEngine  # noqa: E402
# after serving: the exposition server's /requests//healthz endpoints
# walk the engine registry, and MXNET_TELEMETRY_PORT arms it at import
from . import telemetry_http  # noqa: E402

__version__ = "0.1.0"
