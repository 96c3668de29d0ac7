"""What JAX builds inside a block: the test files that pin "a warm call
traces and lowers nothing" share this listener."""

import contextlib

from jax._src import monitoring

BUILD_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


@contextlib.contextmanager
def builds():
    """The traces and lowerings JAX makes inside the block, by event name."""
    seen = []

    def listener(name, secs, **_):
        if name in BUILD_EVENTS:
            seen.append(name)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listener)
