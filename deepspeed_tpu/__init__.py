"""deepspeed_tpu — TPU-native distributed training/inference framework.

Re-implements the capability surface of DeepSpeed (reference:
``deepspeed/__init__.py`` [K]) as an idiomatic JAX/XLA/Pallas stack: ZeRO
stages are GSPMD sharding policies, parallelism modes are mesh axes, the hot
path is one jitted train step.
"""

import time

#: ``perf_counter()`` at this file's first and last line: the telemetry
#: package, when it is first imported, enters them in the start-up record
#: as ``startup/package_import`` (no import is added here for it)
_IMPORT_STAMPS = [time.perf_counter(), None]

from .version import __version__
from . import comm
from .parallel import MeshLayout, build_mesh
from .utils import logger

__all__ = ["__version__", "comm", "MeshLayout", "build_mesh", "logger",
           "initialize", "init_inference", "init_distributed",
           "tp_model_init", "zero"]


def initialize(*args, **kwargs):
    """Public factory — mirrors ``deepspeed.initialize`` [L ACC:2358-2439].

    Returns ``(engine, optimizer, dataloader, lr_scheduler)``.  Imported
    lazily so light uses (comm/mesh only) don't pay engine import cost:
    that import is the start's first phase, so its two stamps are taken
    here and handed to the start-up record, whose package it loads.
    """
    entered = time.perf_counter()
    from .runtime.entry import initialize as _initialize

    return _initialize(*args, **kwargs,
                       _entered=(entered, time.perf_counter()))


def init_inference(*args, **kwargs):
    """Mirrors ``deepspeed.init_inference`` (SURVEY §3.6)."""
    from .inference import init_inference as _init_inference

    return _init_inference(*args, **kwargs)


def init_distributed(*args, **kwargs):
    return comm.init_distributed(*args, **kwargs)


def tp_model_init(*args, **kwargs):
    """Mirrors ``deepspeed.tp_model_init`` [L HF-DS:468-473]."""
    from .runtime.tensor_parallel import tp_model_init as _tp

    return _tp(*args, **kwargs)


def __getattr__(name):
    if name == "zero":
        from .runtime import zero as _zero

        return _zero
    raise AttributeError(f"module 'deepspeed_tpu' has no attribute {name!r}")


_IMPORT_STAMPS[1] = time.perf_counter()
