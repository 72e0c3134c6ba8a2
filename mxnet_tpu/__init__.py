"""mxnet_tpu: a TPU-native deep-learning framework with the MXNet 1.x API.

A ground-up rebuild of the capabilities of ROCmSoftwarePlatform/mxnet
(Apache MXNet 1.x, HIP/ROCm fork) designed for TPU hardware: NDArray storage
backs onto XLA/PjRt device buffers, operators lower to XLA HLO (with Pallas
kernels for hot fused ops), hybridized Gluon blocks JIT-compile into single
XLA computations, and KVStore('device') rides ICI collectives instead of
NCCL/RCCL.  See SURVEY.md for the component-by-component mapping.

Usage mirrors the reference::

    import mxnet_tpu as mx           # or: import mxnet as mx (shim package)
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""
__version__ = "0.1.0"

import os as _os

import jax as _jax

# The ONE compile-cache rule.  Where JAX_COMPILATION_CACHE_DIR is set, jax
# has read it and nothing in this tree sets another directory.  Otherwise
# the persistent compilation cache lives at one fixed path inside the
# checkout (.gitignore lists it): the path is part of the cache key, so a
# directory that moves between runs never hits.
if _jax.config.jax_compilation_cache_dir is None:
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

from .base import MXNetError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, current_context,
                      num_gpus, num_tpus)
from . import engine
from . import random
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import lr_scheduler
from . import metric
from . import kvstore
from . import kvstore as kv  # reference alias: mx.kv.create(...)
from .kvstore import KVStore
from . import recordio
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import module
from . import module as mod
from . import gluon
from . import parallel
from . import precision
from . import passes
from . import io
from . import image
from . import callback
from . import model
from . import operator
from . import rnn
from . import monitor
from . import name
from . import attribute
from .attribute import AttrScope
from .monitor import Monitor
from . import profiler
from . import telemetry
from . import memwatch
from . import metrics_server
from . import runtime
from . import util
from .util import is_np_array
from . import env_vars
from . import subgraph
from . import visualization
from . import visualization as viz
from . import checkpoint
from . import fault
from . import rtc
from . import test_utils
from . import contrib
from . import models

# Multi-process rendezvous must run BEFORE any computation initializes the
# jax backends, so when the launcher env (tools/launch.py: MX_COORDINATOR /
# DMLC_PS_ROOT_URI) is present, connect at import time (reference analog:
# ps::Postoffice::Start, which launch.py's env likewise triggers).
parallel.dist.init_from_env()

# surface set-but-ineffective MXNET_* env vars in logs (env_vars.describe()
# has the full disposition table)
env_vars.check()

# live metrics endpoint (docs/OBSERVABILITY.md §Live metrics): serves
# /metrics /healthz /statusz when MX_METRICS_PORT enables it — after the
# rendezvous above so telemetry.rank() (the port offset + portfile name)
# reflects this process's gang rank
metrics_server.maybe_start()


def waitall():
    engine.wait_all()
