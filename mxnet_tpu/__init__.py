"""mxnet_tpu: a TPU-native deep learning framework with MXNet's capabilities.

A ground-up rebuild of Apache MXNet (~v1.1) for TPU: JAX/XLA is the execution
engine (replacing the dependency engine + graph executor + kernel library,
reference: src/engine, src/executor, src/operator), ``jax.sharding`` over
device meshes replaces KVStore/ps-lite/NCCL (reference: src/kvstore), and the
imperative/symbolic/Gluon API surfaces are re-implemented natively on top.

Usage mirrors the reference:

    import mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu(0))
    with mx.autograd.record():
        y = (x + 1).sum()
    y.backward()
"""
import time as _time
_T_IMPORT = _time.perf_counter()    # import_s.setup: this import, timed
__version__ = "0.1.0"

from . import base
from .base import MXNetError
# JAX decides at its first compile whether its persistent cache is in
# use, so the cache is placed before anything below can compile
from .compile.cache import wire_jax_cache as _wire_jax_cache
_wire_jax_cache()
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import operator  # registers the Custom op before nd codegen runs
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .random import seed
from . import name
from . import attribute
from .attribute import AttrScope
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from .symbol.fusion import fusion_report
from .symbol.passes import pass_report
from . import executor
from .executor import Executor
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import io
from . import recordio
from . import image
from .io_native import CSVIter, LibSVMIter
from . import kvstore
from . import kvstore as kv
from . import callback
from . import model
from . import module
from . import module as mod
from . import monitor
from . import monitor as mon
from . import telemetry
from .telemetry import memory_report
from . import profiler
from . import rtc
from . import config
from . import engine
from . import runtime
from . import kvstore_server
from . import test_utils
from . import visualization
from . import visualization as viz
from . import serving
from .serving import serving_report
from . import fault
from .fault import fault_report
from . import data
from .data import data_report
from . import faultinject
from . import compile  # noqa: A004 — package named for mxnet_tpu.compile
from .compile import compile_report
from . import checkpoint
from .checkpoint import CheckpointManager
from . import sparse
from .sparse import sparse_report
from . import tune
from .tune import tune_report
from . import quant
from .quant import quant_report
from . import contrib
from . import gluon
from . import rnn
from . import parallel
from .io import DataBatch, DataIter
telemetry.registry.timer("prof::setup::import").record(
    _time.perf_counter() - _T_IMPORT)
