"""Device API. ≙ reference «python/paddle/device/» [U]: set/get device,
synchronize, stream shims, memory stats. On TPU there are no user-visible
streams (XLA owns scheduling); the stream/event classes are functional no-ops
kept for API parity."""
from __future__ import annotations

import os

import jax

_current_device = None
# the persistent compile cache of a run that was not handed one from
# outside: one fixed directory in the checkout (ignored by git). The
# path is part of the cache key, so it must never move between runs
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def describe_devices() -> dict:
    """What JAX found, as every measurement names it: platform,
    `device_kind` and count of `jax.devices()`, plus the installed
    jax / jaxlib / libtpu versions."""
    import importlib.metadata as md
    import jaxlib
    devs = jax.devices()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"platform": devs[0].platform,
            "kind": str(devs[0].device_kind), "count": len(devs),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def require_tpu() -> dict:
    """The chip gate: the ONE device check of every measurement path
    (chip_smoke.py, bench.py). Returns `describe_devices()` when the
    first JAX device is a TPU and raises otherwise, naming what was
    found — no probe child (a chip belongs to one process), no retry,
    no cached verdict, no fallback to the CPU."""
    info = describe_devices()
    if info["platform"] != "tpu":
        raise RuntimeError(
            f"this path runs on a TPU only; jax.devices() = "
            f"{jax.devices()} (platform {info['platform']!r})")
    return info


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its
    directory. `JAX_COMPILATION_CACHE_DIR` set from outside wins and
    nothing is touched (JAX reads it itself); otherwise the cache goes
    to one fixed directory inside the checkout. Call before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    return _REPO_CACHE_DIR


def get_all_devices():
    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def get_available_device():
    return get_all_devices()


def get_device() -> str:
    global _current_device
    if _current_device is None:
        d = jax.devices()[0]
        _current_device = f"{d.platform}:0"
    return _current_device


def set_device(device: str):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' (alias for accelerator)."""
    global _current_device
    plat = device.split(":")[0].lower()
    idx = int(device.split(":")[1]) if ":" in device else 0
    alias = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}
    plat = alias.get(plat, plat)
    devs = jax.devices(plat)   # raises when that backend is absent
    d = devs[min(idx, len(devs) - 1)]
    jax.config.update("jax_default_device", d)
    _current_device = f"{d.platform}:{idx}"
    return d


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_custom_device(name: str = "tpu") -> bool:
    return name == "tpu" or any(d.platform == name
                                for d in jax.devices())


def device_count() -> int:
    return len(jax.devices())


def synchronize(device=None):
    """Block until all queued device work completes (≙ cudaDeviceSynchronize)."""
    jax.effects_barrier()


class Stream:
    """No-op stream for API parity: XLA schedules asynchronously itself."""

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

    def query(self):
        return True


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_default_stream = Stream()


def current_stream(device=None) -> Stream:
    return _default_stream


def set_stream(stream):
    return _default_stream


def stream_guard(stream):
    from contextlib import nullcontext
    return nullcontext()


class cuda:
    """Compat shim namespace (paddle.device.cuda): memory stats map to the
    TPU allocator's live stats via jax device memory_stats()."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return len([d for d in jax.devices() if d.platform != "cpu"])

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def current_stream(device=None):
        return _default_stream

    @staticmethod
    def max_memory_allocated(device=None):
        st = jax.devices()[0].memory_stats() or {}
        return st.get("peak_bytes_in_use", 0)

    @staticmethod
    def max_memory_reserved(device=None):
        st = jax.devices()[0].memory_stats() or {}
        return st.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        st = jax.devices()[0].memory_stats() or {}
        return st.get("bytes_in_use", 0)

    @staticmethod
    def memory_reserved(device=None):
        st = jax.devices()[0].memory_stats() or {}
        return st.get("bytes_limit", 0)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def get_device_properties(device=None):
        d = jax.devices()[0]
        class _P:
            name = str(d.device_kind)
            major, minor = 0, 0
            total_memory = (d.memory_stats() or {}).get("bytes_limit", 0)
            multi_processor_count = getattr(d, "num_cores", 1)
        return _P()
