"""The device gate, the table of peaks, compile accounting, peak memory."""

from __future__ import annotations

import pathlib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: Published peaks per chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 393 TOP/s int8, 819 GB/s HBM, 16 GB HBM per chip).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(kind: str) -> dict:
    """The peak table's row for ``kind``; an unknown kind is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def check_device(need: int, devices=None) -> dict:
    """Platform, kind and count of the devices JAX sees.

    Anything but a TPU, or fewer chips than ``need``, raises SystemExit
    (non-zero) before any other phase runs."""
    if devices is None:
        import jax

        devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{d.platform!r}); nothing was run")
    if len(devices) < need:
        raise SystemExit(f"chipbench: the cell needs {need} chips, JAX "
                         f"sees {len(devices)}")
    return info


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at the fixed
    ``<checkout>/.jax_cache``, whatever ``JAX_COMPILATION_CACHE_DIR``
    says, so that two checkouts share nothing.  Every program is cached,
    however short its compile and however small its entry, so the eager
    ops of the engine's set-up are found again by the next run."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return self.seconds, self.compiles, self.hits, self.misses

    def since(self, mark) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.compiles - mark[1],
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None
