"""Abstract accelerator interface.

Reference: ``deepspeed/accelerator/abstract_accelerator.py`` [K] — its
~90-method surface mapped onto XLA semantics: device/memory/RNG queries
answer through jax; CUDA stream/event micromanagement collapses to
ordered-dispatch no-op objects (Events still time via host clocks, the
use DeepSpeed's timers put them to); ``*Tensor`` constructors build jnp
arrays; profiler ranges map to ``jax.named_scope``.  Methods the reference needs only for CUDA stream/event
micromanagement collapse to no-ops under XLA's async dispatch model and
are still present so accelerator-generic caller code ports unchanged.
"""

from __future__ import annotations

import abc
from typing import Any, Optional


class DeepSpeedAccelerator(abc.ABC):
    _name: str = "abstract"
    _communication_backend_name: str = "none"

    # -- identity ----------------------------------------------------------

    def device_name(self, device_index: Optional[int] = None) -> str:
        return (self._name if device_index is None
                else f"{self._name}:{device_index}")

    def current_device_name(self) -> str:
        return self.device_name(self.current_device())

    @abc.abstractmethod
    def current_device(self) -> int: ...

    @abc.abstractmethod
    def device_count(self) -> int: ...

    @abc.abstractmethod
    def is_available(self) -> bool: ...

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # -- capabilities ------------------------------------------------------

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def supported_dtypes(self) -> list:
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16]

    # -- device handles / execution ---------------------------------------

    @abc.abstractmethod
    def device(self, device_index: Optional[int] = None) -> Any: ...

    def synchronize(self, device_index: Optional[int] = None) -> None:
        """Drain outstanding work (XLA: block on a trivial computation)."""
        import jax
        import jax.numpy as jnp

        jnp.zeros(()).block_until_ready()
        jax.effects_barrier()

    # streams/events: XLA schedules async itself; kept as no-op objects
    class _NullStream:
        def __enter__(self):  # pragma: no cover - trivial
            return self

        def __exit__(self, *a):
            return False

        def synchronize(self):
            pass

    def Stream(self, *a, **k):
        return self._NullStream()

    def stream(self, s):
        return self._NullStream()

    def current_stream(self, device_index=None):
        return self._NullStream()

    def default_stream(self, device_index=None):
        return self._NullStream()

    # -- memory ------------------------------------------------------------

    @abc.abstractmethod
    def memory_stats(self, device_index: Optional[int] = None) -> dict: ...

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: Optional[int] = None) -> int:
        return self.total_memory(device_index) - self.memory_allocated(
            device_index)

    def empty_cache(self) -> None:
        pass

    def pin_memory(self, tensor: Any, align_bytes: int = 1) -> Any:
        return tensor  # host numpy is already DMA-able through dlpack

    # -- RNG ---------------------------------------------------------------

    def manual_seed(self, seed: int) -> None:
        self._seed = int(seed)

    def initial_seed(self) -> int:
        return getattr(self, "_seed", 0)

    # -- op builders -------------------------------------------------------

    def create_op_builder(self, class_name: str) -> Any:
        builder = self.get_op_builder(class_name)
        return builder() if builder is not None else None

    def get_op_builder(self, class_name: str) -> Any:
        from ..ops.op_builder.builder import get_op_builder

        return get_op_builder(class_name)

    # -- events ------------------------------------------------------------
    # XLA's dispatch is ordered per device; an Event reduces to a marker
    # that can synchronize (drain) and report elapsed wall time between
    # two recorded points — the uses DeepSpeed's timers put them to.

    class _Event:
        def __init__(self, enable_timing: bool = False):
            self._t = None
            self._timing = enable_timing

        @staticmethod
        def _drain():
            # XLA dispatch is async: a host timestamp taken without
            # draining outstanding device work measures dispatch latency,
            # not execution.  An Event holds no handle on the outstanding
            # work, so there is nothing to block_until_ready on: instead
            # fetch a freshly computed scalar, which completes only after
            # its own program executes — and per-device in-order execution
            # (true of XLA's per-device streams) sequences that after all
            # previously dispatched work.
            try:
                import jax
                import jax.numpy as jnp
                import numpy as _np

                _np.asarray(jnp.zeros(()) + 1.0)
                jax.effects_barrier()
            except Exception as e:
                # no device / not initialized — host-only semantics
                from ..utils.logging import debug_once

                debug_once("accelerator/event_drain",
                           f"Event drain skipped ({e!r}); "
                           f"host-only timing semantics")

        def record(self, stream=None):
            import time as _time

            if self._timing:
                self._drain()
            self._t = _time.perf_counter()

        def synchronize(self):
            self._drain()

        def query(self) -> bool:
            return True

        def elapsed_time(self, other) -> float:
            """Milliseconds from self.record() to other.record().

            Like ``torch.cuda.Event``, raises unless BOTH events were
            created with ``enable_timing=True`` — un-timed records don't
            drain async dispatch, so their stamps measure dispatch
            latency and would be confidently wrong."""
            if not (self._timing and getattr(other, "_timing", False)):
                raise RuntimeError(
                    "elapsed_time requires both events to be created "
                    "with enable_timing=True")
            if self._t is None or getattr(other, "_t", None) is None:
                return 0.0
            return (other._t - self._t) * 1e3

    def Event(self, enable_timing: bool = False):
        return self._Event(enable_timing)

    # -- execution-model queries (reference capability probes) -------------

    def is_synchronized_device(self) -> bool:
        return False  # XLA dispatch is async

    def use_host_timers(self) -> bool:
        # no CUDA-event timers; device timing comes from profiler traces
        return True

    def resolves_data_dependency(self) -> bool:
        return True  # XLA orders by data dependence, not stream order

    def handles_memory_backpressure(self) -> bool:
        return False

    def set_device(self, device_index: int) -> None:
        # one process drives all local chips under jax; per-device placement
        # is explicit via shardings, so this is bookkeeping only
        self._current_device = int(device_index)

    def device_properties(self, device_index: Optional[int] = None) -> dict:
        d = self.device(device_index)
        props = {"name": getattr(d, "device_kind", self._name),
                 "platform": getattr(d, "platform", self._name),
                 "id": getattr(d, "id", device_index or 0)}
        props["total_memory"] = self.total_memory(device_index)
        return props

    def get_device_name(self, device_index: Optional[int] = None) -> str:
        return str(self.device_properties(device_index)["name"])

    # -- memory (peak tracking + reference aliases) ------------------------

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get(
            "peak_bytes_in_use", self.memory_allocated(device_index)))

    def reset_peak_memory_stats(self, device_index=None) -> None:
        pass  # XLA exposes a monotone peak; nothing to reset

    def memory_reserved(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get(
            "bytes_reserved", self.memory_allocated(device_index)))

    def max_memory_reserved(self, device_index: Optional[int] = None) -> int:
        return self.max_memory_allocated(device_index)

    def memory_cached(self, device_index: Optional[int] = None) -> int:
        return self.memory_reserved(device_index)

    def max_memory_cached(self, device_index: Optional[int] = None) -> int:
        return self.max_memory_reserved(device_index)

    def mem_get_info(self, device_index: Optional[int] = None) -> tuple:
        total = self.total_memory(device_index)
        return (total - self.memory_allocated(device_index), total)

    def is_pinned(self, tensor: Any) -> bool:
        return True  # host numpy is DMA-able as-is

    # -- RNG (jax is explicit-key; these serve compat callers) -------------

    def random(self):
        import jax

        return jax.random

    def default_generator(self, device_index: Optional[int] = None):
        import jax

        return jax.random.PRNGKey(self.initial_seed())

    def manual_seed_all(self, seed: int) -> None:
        self.manual_seed(seed)

    # -- profiler range markers (reference nvtx surface) -------------------

    def range_push(self, msg: str):
        import jax

        scope = jax.named_scope(msg)
        scope.__enter__()
        self._scopes = getattr(self, "_scopes", [])
        self._scopes.append(scope)

    def range_pop(self):
        scopes = getattr(self, "_scopes", [])
        if scopes:
            scopes.pop().__exit__(None, None, None)

    def lazy_call(self, callback) -> None:
        callback()  # no CUDA-context laziness to defer around

    # -- dtype/tensor helpers (reference *Tensor constructors) -------------

    def BFloat16Tensor(self, data):
        import jax.numpy as jnp

        return jnp.asarray(data, dtype=jnp.bfloat16)

    def FloatTensor(self, data):
        import jax.numpy as jnp

        return jnp.asarray(data, dtype=jnp.float32)

    def HalfTensor(self, data):
        import jax.numpy as jnp

        return jnp.asarray(data, dtype=jnp.float16)

    def IntTensor(self, data):
        import jax.numpy as jnp

        return jnp.asarray(data, dtype=jnp.int32)

    def LongTensor(self, data):
        import jax.numpy as jnp

        return jnp.asarray(data, dtype=jnp.int64)

    def ByteTensor(self, data):
        import jax.numpy as jnp

        return jnp.asarray(data, dtype=jnp.uint8)

    # -- visibility / env --------------------------------------------------

    def visible_devices_envs(self) -> list:
        return ["TPU_VISIBLE_DEVICES", "JAX_PLATFORMS"]

    def set_visible_devices_envs(self, current_env: dict,
                                 local_accelerator_ids: list) -> None:
        current_env["TPU_VISIBLE_DEVICES"] = ",".join(
            str(i) for i in local_accelerator_ids)

    def export_envs(self) -> list:
        return ["TPU", "JAX", "XLA", "LIBTPU"]

    def is_triton_supported(self) -> bool:
        return False  # pallas is the kernel story

    def build_extension(self):
        from ..ops.op_builder import builder

        return builder

