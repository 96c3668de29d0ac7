"""What JAX builds inside a block: the test files that pin "a warm call
traces and lowers nothing" share this listener."""

import contextlib

from jax._src import monitoring
from jax._src.interpreters import partial_eval as pe

BUILD_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


@contextlib.contextmanager
def builds():
    """The traces and lowerings JAX makes inside the block, by event name.

    ``jax.jit``'s Python dispatch reports a ``jaxpr_trace_duration`` around
    its look-up in the tracing cache, hit or miss, on every call that does
    not take the C++ fast path -- and one eager ``vmap`` of a dense sketch
    earlier in the process (``tests/test_admm_routes.py`` calls
    ``admm._block`` outside a ``jit``) leaves the eager
    ``convert_element_type`` to f32 off that path for good: every later
    ``jnp.asarray(tol, float32)`` then reported a "trace" that traced
    nothing, and a warm solve failed in a worker that had run that file.
    A trace is counted when the tracing cache (``pe.trace_to_jaxpr``)
    really missed."""
    seen = []
    missed = [pe.trace_to_jaxpr.cache_info().misses]

    def listener(name, secs, **_):
        if name == BUILD_EVENTS[0]:
            now = pe.trace_to_jaxpr.cache_info().misses
            if now > missed[0]:
                seen.append(name)
            missed[0] = now
        elif name in BUILD_EVENTS:
            seen.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listener)
