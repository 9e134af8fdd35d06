"""What the benchmark takes from the runtime besides answers: the device it
runs on, JAX's compile events, and the guards that the answer came from the
Pallas engine on a TPU (copied from the repository's ``chip_smoke.py``)."""


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit counts
    its retrieval time) and counts compiles and cache hits."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        """``(seconds, compiles, hits)`` since the last take."""
        out = (self.seconds, self.compiles, self.hits)
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        return out


def device_problem(devices, chips: int):
    """Why these devices cannot run a cell on ``chips`` TPU chips, or None."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else None
        return f"JAX sees no TPU (platform {platform!r})"
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    return None


def engine_problems(stats, engine_name: str = "pallas") -> list:
    """Guard violations of one answered query's ``ExecStats``."""
    out = []
    if stats.engine_used != engine_name:
        out.append(f"answered by {stats.engine_used!r}, not {engine_name!r}")
    if stats.fallbacks:
        out.append(f"fallback events: {stats.fallbacks}")
    return out


def kernel_problems(texts) -> list:
    """Every compiled executor that ran must hold a Mosaic kernel."""
    if not texts:
        return ["no pallas executor ran"]
    missing = sum("tpu_custom_call" not in t for t in texts)
    if missing:
        return [f"{missing} of {len(texts)} compiled executors hold no "
                "tpu_custom_call"]
    return []
