"""Meters the benchmark takes itself: compiles, device memory, the
program's registry sums (``CompileMeter`` and ``peak_hbm`` copied from
``chip_smoke.py``, PR 24)."""

from __future__ import annotations


class CompileMeter:
    """Counts backend compiles and persistent-cache hits/misses through
    ``jax.monitoring`` (a compile that hits the cache still reports its
    retrieval time under the compile event)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.trace_lower_s = 0.0  # Python tracing + lowering: never cached
        self.cache_hits = 0
        self.cache_misses = 0
        self.names: list = []  # the function each backend compile was for
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
            self.names.append(str(kw.get("fun_name", "?")))
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_lower_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "trace_lower_s": self.trace_lower_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, base: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - base[k] for k in now}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the
    backend does not report it, as the CPU's does not)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use") or 0))
    return peak


def registry_sum(family: str, labels: dict, field: str = "sum") -> float:
    """One series of the program's process-wide registry: a histogram's
    sum or count, or a counter's value; 0 where the family is not there."""
    from backuwup_tpu.obs import metrics as obs_metrics
    fam = obs_metrics.registry().get(family)
    if fam is None:
        return 0.0
    if field == "sum":
        return float(fam.sum_value(**labels))
    if field == "count":
        return float(fam.count_value(**labels))
    return float(fam.value(**labels))
