"""Compile requests and persistent-cache hits, from JAX's own monitoring
events (the listener of ``chip_smoke.py``, PR 22).  A request that is not a
hit is a compilation."""

REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        self.requests = 0
        self.hits = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == REQUEST:
            self.requests += 1
        elif event == HIT:
            self.hits += 1

    def install(self) -> "CompileCounter":
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
