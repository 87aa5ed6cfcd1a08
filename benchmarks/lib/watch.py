"""Small probes copied from ``chip_smoke.py`` (PR 21), so that the
benchmark reads compilations, device memory and its synthetic tokens
with code no later change to the program can alter."""

import numpy as np


class CompileWatch:
    """What jax's own monitoring says of this process's compiles: how
    many executables came out of the persistent cache (hits) or out of
    the compiler (misses), and the seconds spent tracing, lowering and
    compiling — or fetching, on a hit."""

    _SECONDS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.seconds = 0.0
        self.compiles = 0  # backend compiles or cache fetches, either way
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def _on_event(self, event, **_kw):
        self.hits += event == "/jax/compilation_cache/cache_hits"
        self.misses += event == "/jax/compilation_cache/cache_misses"

    def _on_duration(self, event, seconds, **_kw):
        if event in self._SECONDS:
            self.seconds += seconds
            self.compiles += event == self._SECONDS[-1]

    def since(self, mark=(0, 0, 0.0, 0)):
        """(hits, misses, seconds, compiles) since ``mark``, itself a
        ``since()``."""
        now = (self.hits, self.misses, self.seconds, self.compiles)
        return tuple(a - b for a, b in zip(now, mark))


def hbm(devices):
    """Per-device memory counters. Arrays are "in use"; a running
    program's temporaries are "reserved": the peak a chip must hold is
    ``peak_bytes_in_use`` where the backend counts both there."""
    stats = [d.memory_stats() or {} for d in devices]
    return {
        key: [s.get(key, 0) for s in stats]
        for key in (
            "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved",
            "bytes_limit",
        )
    }


def synthetic_batch(seed, index, batch, seq, vocab):
    """Batch ``index`` of the run seeded ``seed``: uniform random token
    ids, targets shifted by one. The same (seed, index) gives the same
    tokens; every batch of a run has the same shape, so the seed never
    changes the work."""
    data = np.random.default_rng([int(seed), int(index)]).integers(
        0, vocab, size=(batch, seq + 1), dtype=np.int32
    )
    return {"tokens": data[:, :-1], "targets": data[:, 1:]}
