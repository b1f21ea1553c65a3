"""siddhi_tpu — a TPU-native streaming / Complex Event Processing framework.

A from-scratch re-design of the capabilities of the reference Siddhi engine
(see SURVEY.md): SiddhiQL over unbounded event streams executed as columnar
micro-batches through pure, jitted (state, batch) -> (state', out) step
functions on TPU.
"""
import os

import jax

# Java long/double semantics (bit-parity with the reference) require 64-bit
# types; must be set before any array is created.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache (docs/compile_cache.md). Where
# JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing here
# names a directory; otherwise the cache lives at a fixed
# <checkout>/.jax_cache, whatever the working directory (the path is part
# of the cache key, so a moving directory never hits). Every compile
# persists (min compile time / entry size 0) so warm starts hit for small
# programs too. JAX_ENABLE_COMPILATION_CACHE=false turns it off.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

from .core.manager import SiddhiManager  # noqa: E402
from .core.persistence import (  # noqa: E402
    FileSystemPersistenceStore,
    InMemoryPersistenceStore,
    PersistenceStore,
)
from .core.stream import Event, QueryCallback, StreamCallback  # noqa: E402
from .core.types import AttrType  # noqa: E402
from .lang import parser as compiler  # noqa: E402
from .obs.explain import ExplainReport, explain_diff  # noqa: E402
from .lang.parser import (  # noqa: E402
    parse,
    parse_expression,
    parse_on_demand_query,
    parse_query,
)
from .resilience import (  # noqa: E402
    CheckpointSupervisor,
    ErroredEvent,
    ErrorStore,
    FaultInjector,
    FileSystemErrorStore,
    InMemoryErrorStore,
    PoolCheckpointSupervisor,
)
from .serving import (  # noqa: E402
    AdmissionError,
    Template,
    TemplateRegistry,
    TenantPool,
)

__all__ = [
    "AdmissionError",
    "AttrType",
    "ExplainReport",
    "explain_diff",
    "CheckpointSupervisor",
    "ErrorStore",
    "ErroredEvent",
    "Event",
    "FaultInjector",
    "FileSystemErrorStore",
    "FileSystemPersistenceStore",
    "InMemoryErrorStore",
    "InMemoryPersistenceStore",
    "PersistenceStore",
    "PoolCheckpointSupervisor",
    "QueryCallback",
    "SiddhiManager",
    "StreamCallback",
    "Template",
    "TemplateRegistry",
    "TenantPool",
    "compiler",
    "parse",
    "parse_expression",
    "parse_on_demand_query",
    "parse_query",
]

__version__ = "0.1.0"
