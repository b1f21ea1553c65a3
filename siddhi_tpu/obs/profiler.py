"""Profiler hooks: ``runtime.profile(path)`` around
``jax.profiler.start_trace/stop_trace``, and the ``jax.named_scope``
labels inside step traces.

Every operator of a step body runs under ``op_scope("<q>/<OpClass>")``,
so each compiled op's ``op_name`` metadata names its query and operator
and a device profile attributes time per operator. The scopes are
debug info only: JAX strips debug info from the module before hashing
it into the persistent compile-cache key (``jax/_src/cache_key.py``,
``compilation_cache_include_metadata_in_key`` off by default), so a
scoped step hits the cache entry of the same step lowered without them.
"""
from __future__ import annotations

import contextlib

import jax


def op_scope(name: str):
    """``jax.named_scope(name)`` around one operator inside a step
    trace."""
    return jax.named_scope(name)


@contextlib.contextmanager
def profile(path: str):
    """Capture a device profile of the enclosed block into ``path``
    (TensorBoard/XProf trace directory)::

        with runtime.profile('/tmp/prof'):
            handler.send_arrays(ts, cols)

    While the app's tracer records (``runtime.trace_start()``), its
    chunk spans appear in the trace's host plane beside the device
    ops."""
    jax.profiler.start_trace(path)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()
