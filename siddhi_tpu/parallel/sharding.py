"""Regex partition-rule tables: state pytree -> PartitionSpec -> placed
shards (the `match_partition_rules` pattern from large-model training
codebases, applied to streaming state).

Every multi-chip surface in this framework shards STATE along one
leading axis — the partition key-slot axis ([K]-leading, see
parallel/partition.py), the tenant-pool slot axis ([slots]-leading,
serving/pool.py), or the data-parallel shard axis ([n_devices]-leading,
parallel/mesh.py). Instead of each consumer hand-rolling `device_put`
calls, a rule table maps state paths (``qstates/q1/0/buf/ts``) to
actions by regex, first match wins:

- ``SHARD``      -> ``PartitionSpec(axis, None, ...)``: split the
                    leading axis over the mesh; the rest stays local.
- ``REPLICATE``  -> ``PartitionSpec()``: every device holds a copy
                    (overflow counters, small lookup tables).

Scalars and single-element leaves always replicate regardless of rules
(they cannot be split, and XLA would just broadcast them anyway).

Placement is DEDUPLICATED: `shard_pytree` checks each leaf's current
sharding and skips the `jax.device_put` when the leaf is already placed
as requested — so re-placement only ever transfers on the events that
actually change layout (slot-axis growth, snapshot restore), never on
steady-state rebuilds. `placement_stats` counts real puts vs skips;
tests/test_mesh.py pins the counts.

Restore contract: a host (numpy) snapshot passed through `shard_pytree`
lands directly as device shards — ONE `device_put` per leaf, already
fresh buffers (donation-safe: a sharded put never aliases the numpy
payload), and never a gather-then-scatter round trip.
"""
from __future__ import annotations

import re
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# rule actions
SHARD = "shard"           # split the leading axis over the mesh axis
REPLICATE = "replicate"   # full copy on every device

# -- default rule tables ----------------------------------------------------
# Partition blocks (parallel/partition.py): every operator state under
# qstates/ carries the [K] slot axis first and shards with it. The
# open-addressing key-slot table REPLICATES: the batch->slot map is
# computed over the whole ingest batch BEFORE the slot-vmap, on every
# device (its overflow counter is a scalar and auto-replicates).
PARTITION_STATE_RULES = (
    (r"(^|/)slot_tbl(/|$)", REPLICATE),
    (r"(^|/)qstates(/|$)", SHARD),
    (r"", SHARD),
)

# Tenant pools (serving/pool.py): stacked per-query operator states and
# the per-slot emitted counters all lead with the tenant-slot axis.
POOL_STATE_RULES = (
    (r"(^|/)(states|emitted)(/|$)", SHARD),
    (r"", SHARD),
)

# Data-parallel shard-axis stacking (parallel/mesh.py): everything leads
# with the shard axis — window pools, NFA pending tables, group-by
# tables, and the banded-join sorted pools (ops/join.py keeps the sorted
# key view per shard; see JOIN_STATE_RULES there for the key-axis view).
DATA_PARALLEL_RULES = (
    (r"", SHARD),
)


class PlacementStats:
    """Host-side counters of real vs skipped placements (the dedupe
    regression guard: tests monkeypatch nothing, they just read this)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.device_puts = 0
        self.skipped = 0

    def note(self, placed: bool) -> None:
        with self._lock:
            if placed:
                self.device_puts += 1
            else:
                self.skipped += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"device_puts": self.device_puts,
                    "skipped": self.skipped}

    def reset(self) -> None:
        with self._lock:
            self.device_puts = 0
            self.skipped = 0


placement_stats = PlacementStats()


def _path_str(path) -> str:
    """jax key path -> '/'-joined readable name (dict keys, tuple
    indices, attribute names)."""
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        elif isinstance(p, jax.tree_util.FlattenedIndexKey):
            parts.append(str(p.key))
        else:  # pragma: no cover - future key kinds degrade readably
            parts.append(str(p).strip(".[]'\""))
    return "/".join(parts)


def _leaf_shape(leaf) -> tuple:
    return tuple(getattr(leaf, "shape", np.shape(leaf)))


def spec_for_path(name: str, leaf, rules, axis: str) -> PartitionSpec:
    """The PartitionSpec one state leaf gets under a rule table: scalars
    replicate unconditionally; otherwise the first rule whose regex
    ``search``es the path decides. No match is an ERROR — silent
    replication of a big state array is exactly the bug class this
    table exists to prevent."""
    shape = _leaf_shape(leaf)
    if len(shape) == 0 or int(np.prod(shape)) == 1:
        return PartitionSpec()
    for rule, action in rules:
        if re.search(rule, name) is None:
            continue
        if isinstance(action, PartitionSpec):
            return action
        if action == REPLICATE:
            return PartitionSpec()
        return PartitionSpec(axis, *([None] * (len(shape) - 1)))
    raise ValueError(f"no partition rule matched state path '{name}'")


def match_partition_rules(rules, tree, axis: str):
    """Pytree of PartitionSpec mirroring ``tree``, by regex rule table
    (SNIPPETS.md [1] `match_partition_rules`, state-path flavored)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = [spec_for_path(_path_str(path), leaf, rules, axis)
             for path, leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, specs)


def describe_placement(tree, rules, axis: str) -> dict:
    """Human/machine-readable placement per state leaf: ``{path:
    "shard(<axis>)" | "replicate"}`` from the rule table — the mesh
    section of the explain report (obs/explain.py). Pure path + shape
    metadata: no device reads, no placement side effects; paths are
    stable across slot-axis growth so the plan hash never moves on
    churn."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in leaves:
        spec = spec_for_path(_path_str(path), leaf, rules, axis)
        out[_path_str(path)] = (f"shard({axis})" if len(spec) and
                                spec[0] is not None else "replicate")
    return out


def check_divisible(n: int, mesh: Mesh, what: str) -> None:
    axis = mesh.axis_names[0]
    nd = int(mesh.shape[axis])
    if n % nd:
        raise ValueError(
            f"{what} ({n}) must divide evenly over mesh axis "
            f"'{axis}' ({nd} devices)")


def device_of_index(index: int, n: int, mesh: Mesh, axis=None) -> int:
    """Mesh position that owns row ``index`` of a length-``n`` leading
    axis sharded over ``axis`` (first axis by default) — the host-side
    twin of the ``PartitionSpec(axis)`` block layout. The tenant pool's
    slot->device math (placement budgets, migration and evacuation
    targets) routes through here so it can never drift from the rule-
    table placement that `shard_pytree` actually applies."""
    axis = axis or mesh.axis_names[0]
    nd = int(mesh.shape[axis])
    if not 0 <= index < n:
        raise ValueError(f"index {index} out of range for axis of "
                         f"length {n}")
    return index // (n // nd)


def _already_placed(leaf, sharding: NamedSharding) -> bool:
    cur = getattr(leaf, "sharding", None)
    if cur is None:          # host numpy: never placed
        return False
    try:
        return cur.is_equivalent_to(sharding, leaf.ndim)
    except Exception:  # noqa: BLE001 — conservative: re-place
        return cur == sharding


def shard_pytree(tree, mesh: Mesh, rules, axis=None, stats=None):
    """Place every leaf of a state pytree per the rule table: ONE
    ``jax.device_put`` per leaf that is not already laid out as
    requested, zero for leaves that are (the dedupe contract — see
    module docstring). Host (numpy) leaves land directly as device
    shards without an intermediate single-device copy."""
    axis = axis or mesh.axis_names[0]
    stats = stats or placement_stats
    specs = match_partition_rules(rules, tree, axis)

    def place(x, spec):
        ns = NamedSharding(mesh, spec)
        if _already_placed(x, ns):
            stats.note(False)
            return x
        stats.note(True)
        return jax.device_put(x, ns)

    return jax.tree_util.tree_map(place, tree, specs)


def place_leading(arr, mesh: Mesh, axis=None):
    """ONE sharded ``jax.device_put`` of a host array (or array
    pytree) split over ``axis`` on the LEADING dimension — each device
    receives only its rows, and the transfer is still a single put
    call. The tenant pool's round inputs route through here: the
    packed (slots, total) ingest buffer and the stacked EventBatch
    both place with the identical slot-axis layout the POOL_STATE_RULES
    give the states they meet inside the vmapped step."""
    axis = axis or mesh.axis_names[0]
    return jax.device_put(
        arr, NamedSharding(mesh, PartitionSpec(axis)))


def build_mesh(n_devices=None, axis: str = "shards",
               devices=None) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices (default:
    all of them). Tests get N virtual CPU devices from
    `JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N`."""
    devs = list(devices if devices is not None else jax.devices())
    n = int(n_devices) if n_devices else len(devs)
    if len(devs) < n:
        raise ValueError(
            f"mesh wants {n} devices but only {len(devs)} are visible")
    return Mesh(np.array(devs[:n]), (axis,))


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — the local steps
    intentionally mix sharded state with replicated clocks."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
