"""Keyed device state: vectorized open-addressing hash table + segmented
prefix scans. The TPU-native replacement for the reference's per-key State
maps (util/snapshot/state/PartitionStateHolder.java:36 — HashMap keyed by
(partitionFlowId, groupByFlowId)) and GroupByKeyGenerator
(query/selector/GroupByKeyGenerator.java:37 — string key concatenation).

Keys here are 64-bit mixes of the group-by columns (dictionary codes for
strings, bit patterns for floats). A key is assigned a stable slot in a
fixed-capacity table by linear probing; slot state lives in dense [K, ...]
arrays so per-key aggregation is pure gather/scatter — no host round-trip
per key. The probe loop runs on the device in rounds over the whole batch
and stops once every row is placed: a table holding the batch's keys at
their home slots costs one round; a row that finds neither its key nor a
free slot within max_probes slots overflows after max_probes rounds.

Collision note: 64-bit mixing makes key collisions vanishingly unlikely but
not impossible; the reference's string keys cannot collide. Accepted
trade-off for device-resident grouping (documented in README).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# sentinel for "row not placed in any slot"
from .sentinels import NO_SLOT  # noqa: F401


def mix64(h, v):
    """splitmix64-style mixing of an int64 lane into a running hash."""
    h = h ^ (v + jnp.int64(-7046029254386353131))  # 0x9E3779B97F4A7C15
    h = (h ^ (h >> jnp.int64(30))) * jnp.int64(-4658895280553007687)
    h = (h ^ (h >> jnp.int64(27))) * jnp.int64(-7723592293110705685)
    return h ^ (h >> jnp.int64(31))


def hash_columns(cols, nulls) -> jnp.ndarray:
    """[B] int64 key from parallel lists of value arrays and null masks."""
    B = cols[0].shape[0]
    h = jnp.full((B,), 1469598103934665603, dtype=jnp.int64)
    for values, null in zip(cols, nulls):
        if values.dtype == jnp.float64:
            lane = jax.lax.bitcast_convert_type(values, jnp.int64)
        elif values.dtype == jnp.float32:
            lane = jax.lax.bitcast_convert_type(values, jnp.int32).astype(
                jnp.int64)
        else:
            lane = values.astype(jnp.int64)
        lane = jnp.where(null, jnp.int64(-987654321987654321), lane)
        h = mix64(h, lane)
    return h


def lookup_or_insert(table_keys, used, keys, active, max_probes: int = 16):
    """Vectorized open-addressing insert/lookup with linear probing.

    table_keys: [K] int64, used: [K] bool, keys: [B] int64,
    active: [B] bool (rows that need a slot).
    Returns (slots [B] int32 — NO_SLOT when overflowed, table_keys', used',
    overflow_count, rounds — int32 probe rounds run).

    Each round every still-pending row (a) matches its key against the
    probed slot, (b) races to claim it when free (winner = lowest row
    index, via scatter-min), (c) re-checks after claims land (two rows
    inserting the SAME new key resolve on the re-check), else advances to
    the next slot. A row placed in round r sits r slots past its home
    slot. The loop ends once every active row is placed, or after
    max_probes rounds (the rows still pending then overflow): a round
    with no pending row would change nothing.
    """
    K = table_keys.shape[0]
    B = keys.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    slot = (jnp.abs(keys) % K).astype(jnp.int32)
    placed = ~active
    result = jnp.full((B,), NO_SLOT, dtype=jnp.int32)

    def pending_rows(carry):
        i, _, _, _, placed, _ = carry
        return (i < max_probes) & jnp.any(~placed)

    def round_body(carry):
        i, table_keys, used, slot, placed, result = carry
        pending = ~placed
        occ = used[slot]
        match = pending & occ & (table_keys[slot] == keys)
        # race to claim free probed slots
        want = pending & ~occ
        claim_req = jnp.full((K,), B, dtype=jnp.int32).at[
            jnp.where(want, slot, 0)].min(jnp.where(want, rows, B))
        winner = want & (claim_req[slot] == rows)
        table_keys = table_keys.at[jnp.where(winner, slot, K)].set(
            jnp.where(winner, keys, 0), mode="drop")
        used = used.at[jnp.where(winner, slot, K)].set(True, mode="drop")
        # re-check: occupant may now hold our key (own claim or same-key row)
        match = match | (pending & used[slot] & (table_keys[slot] == keys))
        result = jnp.where(match, slot, result)
        placed = placed | match
        slot = jnp.where(placed, slot, (slot + 1) % K)
        return i + 1, table_keys, used, slot, placed, result

    rounds, table_keys, used, slot, placed, result = jax.lax.while_loop(
        pending_rows, round_body,
        (jnp.int32(0), table_keys, used, slot, placed, result))
    overflow = jnp.sum((active & (result == NO_SLOT)).astype(jnp.int64))
    return result, table_keys, used, overflow, rounds


# ---------------------------------------------------------------------------
# segmented prefix scans (rows must be sorted so equal seg_ids are adjacent)
# ---------------------------------------------------------------------------


def cumsum_fast(vals):
    """Inclusive prefix sum via associative_scan.

    jnp.cumsum lowers to a reduce_window whose TPU compile time explodes
    for emulated 64-bit dtypes (f64 cumsum at 4096: ~108s on v5-lite);
    the log-depth associative_scan tree compiles in ~1s and runs equally
    fast. Always use this for accumulator lanes."""
    return jax.lax.associative_scan(jnp.add, vals, axis=0)


RUNNING_COUNT_LIMIT = 2 ** 24   # running_count is exact below this length


def running_count(mask):
    """Inclusive running count of a [n] bool mask, as int32.

    A prefix sum as triangular matmuls over blocks of 128, recursing on
    the block totals; exact for n below RUNNING_COUNT_LIMIT, where every
    count fits f32's 24-bit significand and the products are taken at
    full f32 precision. Callers bound n at plan time. At 1,048,576 rows the TPU
    compiler takes ~150 s over ``cumsum_fast`` and ~35 s over
    ``jnp.cumsum``; these few dots compile in seconds."""
    return _block_prefix(mask.astype(jnp.float32)).astype(jnp.int32)


def _block_prefix(x, w: int = 128):
    n = x.shape[0]
    if n <= w:
        return cumsum_fast(x)
    rows = -(-n // w)
    blocks = jnp.pad(x, (0, rows * w - n)).reshape(rows, w)
    tri = jnp.triu(jnp.ones((w, w), x.dtype))   # tri[i, j] = i <= j
    inner = jnp.matmul(blocks, tri, precision=jax.lax.Precision.HIGHEST)
    total = inner[:, -1]
    before = _block_prefix(total, w) - total
    return (inner + before[:, None]).reshape(rows * w)[:n]


def cummax_fast(vals):
    """Inclusive running max via associative_scan — the cumsum_fast twin.
    jax.lax.cummax on int64 did not compile within 80 s at 65536 rows
    for a described v5e; this form compiles in ~2 s."""
    return jax.lax.associative_scan(jnp.maximum, vals, axis=0)


def segmented_cumsum(vals, seg_ids):
    """Inclusive prefix sum within runs of equal seg_ids."""
    cs = cumsum_fast(vals)
    n = vals.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    boundary = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                seg_ids[1:] != seg_ids[:-1]])
    seg_start = cummax_fast(jnp.where(boundary, idx, 0))
    # cumsum value just before the segment start
    before = jnp.where(seg_start > 0, cs[jnp.maximum(seg_start - 1, 0)], 0)
    return cs - before


def segmented_cummin(vals, seg_ids):
    return _segmented_scan(vals, seg_ids, jnp.minimum)


def segmented_cummax(vals, seg_ids):
    return _segmented_scan(vals, seg_ids, jnp.maximum)


def _segmented_scan(vals, seg_ids, op):
    def combine(a, b):
        av, aseg = a
        bv, bseg = b
        return (jnp.where(aseg == bseg, op(av, bv), bv),
                jnp.maximum(aseg, bseg))

    out, _ = jax.lax.associative_scan(combine, (vals, seg_ids), axis=0)
    return out
