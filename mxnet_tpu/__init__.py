"""mxnet_tpu — a TPU-native deep learning framework.

API-parity target: Apache MXNet 1.4.x (the reference at /root/reference);
architecture: JAX/XLA/Pallas-first (see ARCHITECTURE.md). Import as::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""
from __future__ import annotations

from . import config
# float32/int32 by default (mshadow default_real_t); float64/int64 are
# opt-in via MXNET_ENABLE_X64=1 because x64 doubles every index array and
# pushes XLA onto f64 paths the MXU doesn't have.
if config.flags.enable_x64:
    import jax as _jax
    _jax.config.update("jax_enable_x64", True)

import os as _os

# Persistent XLA compilation cache: a fused step compiles for about a
# minute on the chip, and every process would pay it. Where the
# environment names a directory (JAX_COMPILATION_CACHE_DIR), JAX reads it
# itself and nothing is set here. Otherwise the cache sits beside the
# package at a path that never moves (the path is part of the key), and
# is off for CPU-pinned processes: XLA:CPU persists AOT machine code
# whose feature stamps (+prefer-no-scatter etc.) fail host verification
# on reload and can SIGILL, and CPU compiles are cheap. Pure config, no
# backend work, so import hygiene holds.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import jax as _jax_cc
    from .context import cpu_pinned as _cpu_pinned
    if not _cpu_pinned():
        _jax_cc.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(_os.path.dirname(_os.path.dirname(
                _os.path.abspath(__file__))), ".jax_cache"))

# Under a launcher (tools/launch.py sets MXNET_COORDINATOR_ADDRESS /
# DMLC_PS_ROOT_URI), join the process group NOW — jax.distributed must
# initialize before any JAX call touches a backend, and user scripts touch
# arrays long before they create a kvstore. No-op outside a launcher.
if _os.environ.get("MXNET_COORDINATOR_ADDRESS") \
        or _os.environ.get("DMLC_PS_ROOT_URI"):
    from .parallel import dist as _dist
    _dist.init(strict=False)

# ps-lite launcher compatibility: server/scheduler-role processes run the
# (no-op) server module and exit at import, exactly like the reference
# (python/mxnet/kvstore_server.py:85) — they must not fall through and
# execute the training script as stray singleton workers
import os as _os_role
if _os_role.environ.get("DMLC_ROLE", "") in ("server", "scheduler"):
    from . import kvstore_server as _kvs
    _kvs._init_kvstore_server_module()

from .base import MXNetError
from .attribute import AttrScope
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import engine
from . import random
from . import autograd
from . import ndarray
from . import ndarray as nd

from .ndarray import NDArray

__version__ = "0.1.0"


def waitall():
    engine.waitall()


# submodules loaded lazily to keep import light and avoid cycles
def __getattr__(name):
    import importlib
    lazy = {
        "sym": ".symbol", "symbol": ".symbol",
        "gluon": ".gluon",
        "mod": ".module", "module": ".module",
        "optimizer": ".optimizer",
        "metric": ".metric",
        "initializer": ".initializer",
        "init": ".initializer",
        "lr_scheduler": ".lr_scheduler",
        "callback": ".callback",
        "io": ".io",
        "recordio": ".recordio",
        "image": ".image",
        "kvstore": ".kvstore",
        "kv": ".kvstore",
        "monitor": ".monitor",
        "operator": ".operator",
        "name": ".name",
        "attribute": ".attribute",
        "util": ".util",
        "log": ".log",
        "libinfo": ".libinfo",
        "rtc": ".rtc",
        "registry": ".registry",
        "kvstore_server": ".kvstore_server",
        "executor_manager": ".executor_manager",
        "rnn": ".rnn",
        "model": ".model",
        "checkpoint": ".checkpoint",
        "subgraph": ".subgraph",
        "parallel": ".parallel",
        "profiler": ".profiler",
        "test_utils": ".test_utils",
        "executor": ".executor",
        "visualization": ".visualization",
        "viz": ".visualization",
        "serving": ".serving",
        "serve": ".serve",
        "contrib": ".contrib",
    }
    if name in lazy:
        m = importlib.import_module(lazy[name], __name__)
        globals()[name] = m
        return m
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
