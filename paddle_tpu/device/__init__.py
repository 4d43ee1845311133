"""paddle_tpu.device — device management API.

Reference analog: python/paddle/device/ (set_device, cuda streams). On TPU,
streams/events collapse into XLA's async dispatch; synchronize() is
block_until_ready over live arrays.
"""
from __future__ import annotations

from typing import NamedTuple

import jax

from ..framework.place import (Place, TPUPlace, CPUPlace, CUDAPlace,
                               _default_place)

_current_device = None


def is_tpu() -> bool:
    """True when jax's default backend is a TPU — the one test the kernel
    gates, the per-platform defaults and the compile cache branch on."""
    return jax.default_backend() == "tpu"


class ChipPeaks(NamedTuple):
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: float


# Published per-chip peaks, keyed by the `device_kind` jax reports.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s, 16 GB of HBM; the chip reports itself as "TPU v5 lite".
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The table row for `device_kind`. A device that is not in the table
    is an error, never a default: a utilization priced against a guessed
    peak is not a measurement."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); add a sourced row to "
            "paddle_tpu.device.CHIP_PEAKS") from None


def cpu_pin_env(n_devices: int, base_env=None) -> dict:
    """Environment for a CPU-pinned (child) process: JAX_PLATFORMS et al.
    plus XLA_FLAGS with any pre-existing host-device-count flag replaced.
    The one place the pin recipe's env half lives (pin_cpu applies it
    in-process; __graft_entry__'s re-exec path passes it to subprocess)."""
    import os
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    keep = [f for f in env.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        keep + [f"--xla_force_host_platform_device_count={n_devices}"])
    return env


def pin_cpu(n_devices: int = 1, verify: bool = True) -> bool:
    """Pin this process to the CPU platform with >= n_devices virtual
    devices. Must run before any jax backend initializes; returns True when
    the pin took effect. On failure every env/config mutation is rolled
    back, so a long-lived caller is never left half-pinned.

    The environment variables only count if jax has not been imported
    yet, and importing this package imports jax, so the pin also goes
    through the jax config API (tests/conftest.py, __graft_entry__.py and
    `bench.py --cpu` all route through here).
    """
    import os
    saved_env = {k: os.environ.get(k)
                 for k in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME",
                           "XLA_FLAGS")}
    saved_cfg = getattr(jax.config, "jax_platforms", None)
    os.environ.update(cpu_pin_env(n_devices))

    def _rollback():
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            jax.config.update("jax_platforms", saved_cfg)
        except Exception:
            pass

    try:
        jax.config.update("jax_platforms", "cpu")
        if not verify:
            # verification initializes the backend — callers that must run
            # jax.distributed.initialize afterwards (launch workers) pin
            # blind and let distributed init be the first backend touch
            return True
        devs = jax.devices()
    except Exception:
        _rollback()
        return False
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        _rollback()
        return False
    return True


def set_device(device):
    global _current_device
    if isinstance(device, Place):
        _current_device = device
        return _current_device
    device = str(device)
    if device.startswith(("gpu", "cuda", "tpu", "xpu")):
        idx = 0
        if ":" in device:
            idx = int(device.split(":")[1])
        _current_device = TPUPlace(idx)
    elif device.startswith("cpu"):
        _current_device = CPUPlace()
    else:
        dtype = device.split(":")[0]
        if dtype in _CUSTOM_BACKENDS:
            from ..framework.place import CustomPlace
            idx = int(device.split(":")[1]) if ":" in device else 0
            _current_device = CustomPlace(dtype, idx)
        else:
            raise ValueError(f"unknown device {device!r}")
    return _current_device


def get_device() -> str:
    place = _current_device or _default_place()
    if isinstance(place, CPUPlace):
        return "cpu"
    from ..framework.place import CustomPlace
    if isinstance(place, CustomPlace):
        return f"{place.get_device_type()}:{place.get_device_id()}"
    return f"tpu:{place.get_device_id()}"


def get_current_place() -> Place:
    return _current_device or _default_place()


def device_count() -> int:
    return len(jax.devices())


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_distribute() -> bool:
    return True


def synchronize(device=None):
    """Block until all dispatched work completes (stream sync analog):
    a device runs what it is given in order, so a computation enqueued
    now on each one finishes after everything before it."""
    jax.effects_barrier()
    jax.block_until_ready([jax.device_put(0, d) + 0
                           for d in jax.local_devices()])


class Stream:
    """Compat shim: XLA on TPU has a single ordered compute stream."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


# ------------------------------------------------------------ memory stats
# Reference analog: paddle/fluid/memory/stats.h (DeviceMemoryStat
# Allocated/Reserved counters) surfaced as paddle.device.cuda.
# memory_allocated/max_memory_allocated. TPU-native: PJRT owns the
# allocator; its live counters come back through Device.memory_stats().
def _stats_device(device=None):
    import jax
    devs = jax.devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    s = str(device)
    if ":" in s:
        kind, _, idx = s.partition(":")
        cand = [d for d in devs if d.platform == kind or kind in ("gpu",
                                                                  "cuda")]
        if cand:
            return cand[int(idx) % len(cand)]
    return devs[0]


def memory_stats(device=None) -> dict:
    """Raw allocator counters for a device (PJRT memory_stats: keys like
    bytes_in_use, peak_bytes_in_use, bytes_limit...). Empty dict when the
    backend doesn't report (CPU)."""
    try:
        return dict(_stats_device(device).memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    """Bytes currently held by live arrays on the device (reference
    DeviceMemoryStatCurrentValue("Allocated"))."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """High-water mark of device bytes (reference
    DeviceMemoryStatPeakValue("Allocated"))."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved from the platform by the allocator pool; PJRT
    reports a hard limit rather than a growing reservation."""
    st = memory_stats(device)
    return int(st.get("bytes_reserved", st.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    st = memory_stats(device)
    return int(st.get("peak_bytes_reserved",
                      st.get("peak_bytes_in_use", 0)))


class cuda:
    """paddle.device.cuda compat namespace."""
    Stream = Stream
    Event = Event

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def stream_guard(stream):
        import contextlib
        return contextlib.nullcontext()

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def max_memory_reserved(device=None):
        return max_memory_reserved(device)


# ------------------------------------------------------- pluggable backends
# Reference analog: phi::DeviceManager + DeviceInterface
# (paddle/phi/backends/device_manager.h:128, device_base.h:26, and the
# CustomPlace plugin seam). On TPU-era jax the hardware plugin mechanism IS
# PJRT: a vendor ships a PJRT plugin package and jax discovers it. This
# registry is the paddle-shaped seam over that: register the platform name
# so paddle_tpu.set_device()/Place accept it, optionally pointing at a
# PJRT plugin library to load.
_CUSTOM_BACKENDS = {}


def register_custom_device(device_type: str, pjrt_plugin_path=None,
                           priority: int = 0):
    """Register a custom hardware backend (reference DeviceManager::
    Register). `device_type` must match the PJRT platform name; when
    `pjrt_plugin_path` is given the plugin is registered with jax's
    plugin loader so the platform becomes available."""
    if pjrt_plugin_path is not None:
        try:
            from jax._src.xla_bridge import register_plugin
        except ImportError as e:
            raise NotImplementedError(
                "this jax version does not expose a runtime PJRT plugin "
                "registration hook; ship the plugin as a jax_plugins "
                "entry-point package instead (jax's supported discovery "
                "mechanism)") from e
        register_plugin(device_type, library_path=str(pjrt_plugin_path))
    _CUSTOM_BACKENDS[device_type] = {
        "plugin": pjrt_plugin_path, "priority": priority}
    return device_type


def get_all_custom_device_type():
    """Reference device_manager GetAllCustomDeviceTypes."""
    return sorted(_CUSTOM_BACKENDS)


def is_custom_device(device_type: str) -> bool:
    return device_type in _CUSTOM_BACKENDS


def get_cudnn_version():
    """reference device get_cudnn_version — None: no cuDNN in the XLA
    TPU stack."""
    return None


class XPUPlace:
    def __init__(self, dev_id=0):
        raise NotImplementedError(
            "XPU (Kunlun) hardware is not available on the TPU backend")


class IPUPlace:
    def __init__(self, dev_id=0):
        raise NotImplementedError(
            "IPU (GraphCore) hardware is not available on the TPU "
            "backend")


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    # XLA is the compiler here; CINN is the reference's own stack
    return False


def is_compiled_with_custom_device(device_type):
    return is_custom_device(device_type)


def get_all_device_type():
    import jax
    kinds = {d.platform for d in jax.devices()}
    return sorted(kinds | set(_CUSTOM_BACKENDS))


def set_stream(stream=None):
    """reference device.set_stream — PJRT schedules streams; returns the
    previous (nominal) stream for API parity."""
    return Stream()


import contextlib as _ctx


@_ctx.contextmanager
def stream_guard(stream=None):
    """reference device.stream_guard — no-op scope (PJRT async
    dispatch owns ordering)."""
    yield
