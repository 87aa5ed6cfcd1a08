"""What the run is allowed to measure on: the chips the cell asks for,
of a kind the peak table knows, and a compiled program that holds the
kernels. Anything else refuses the run; nothing falls back to the CPU.

The rehearsal test patches ``require_chips`` and ``require_kernels``
(the test steers them, ``run.py`` has no option for it)."""

import os

from benchmarks.lib import peaks


class Refused(RuntimeError):
    """The run may not be measured here: no result line, exit code 2."""


def require_chips(chips: int):
    """(devices to use, the device record of the result line, peaks)."""
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        raise Refused(
            f"this benchmark needs a TPU; jax reports platform "
            f"{platform!r} ({kind!r} x{len(devices)})"
        )
    if len(devices) < chips:
        raise Refused(
            f"the cell asks for {chips} chips; jax reports {len(devices)}"
        )
    try:
        chip = peaks.chip_peaks(kind)
    except KeyError as exc:
        raise Refused(str(exc)) from exc
    record = {"platform": platform, "kind": kind, "count": len(devices)}
    return devices[:chips], record, chip


def require_kernels(compiled, what: str) -> int:
    """How many Pallas kernels (``tpu_custom_call``) the compiled
    program holds. None means it took a reference path: its time is not
    the system's."""
    n = compiled.as_text().count("tpu_custom_call")
    if n == 0:
        raise Refused(
            f"the compiled {what} holds no tpu_custom_call: it took a "
            "reference path, not the kernels"
        )
    return n


def enable_compile_cache(root: str) -> str:
    """jax's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where that is set (jax reads it itself), else at a fixed path inside
    the checkout: the path is part of the cache's key. Every program is
    cached, however quick its compile, so that a second run of a cell
    compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_compile_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
