"""The group-key probe loop (ops/keyed.py lookup_or_insert) against the
fixed 16-round linear-probing scan it replaced, kept here verbatim as the
oracle. The loop stops once every active row is placed; the rounds it
skips must be no-ops, so slots, table and overflow are equal bit for bit,
and the round count it returns is the largest probe distance + 1 (16 when
a row overflows, 0 when no row is active).
"""
import jax
import numpy as np
import pytest

import siddhi_tpu  # noqa: F401  (enables x64 before any array exists)
import jax.numpy as jnp

from siddhi_tpu.core.types import StringTable
from siddhi_tpu.ops.keyed import NO_SLOT, hash_columns, lookup_or_insert

MAX_PROBES = 16


def scan_oracle(table_keys, used, keys, active, max_probes: int = 16):
    K = table_keys.shape[0]
    B = keys.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    slot = (jnp.abs(keys) % K).astype(jnp.int32)
    placed = ~active
    result = jnp.full((B,), NO_SLOT, dtype=jnp.int32)

    def round_body(carry, _):
        table_keys, used, slot, placed, result = carry
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
        return (table_keys, used, slot, placed, result), None

    (table_keys, used, slot, placed, result), _ = jax.lax.scan(
        round_body, (table_keys, used, slot, placed, result), None,
        length=max_probes)
    overflow = jnp.sum((active & (result == NO_SLOT)).astype(jnp.int64))
    return result, table_keys, used, overflow


def _empty(K):
    return jnp.zeros((K,), jnp.int64), jnp.zeros((K,), jnp.bool_)


def _all_active(keys):
    keys = jnp.asarray(keys, jnp.int64)
    return keys, jnp.ones(keys.shape, jnp.bool_)


def _prefilled(K, keys):
    """A table already holding ``keys`` (inserted by the oracle)."""
    k, a = _all_active(keys)
    _, table_keys, used, _ = scan_oracle(*_empty(K), k, a)
    return table_keys, used


# each case: (table_keys, used, keys, active, expected rounds or None)
def case_empty_new_keys():
    keys = np.random.default_rng(7).integers(-2**62, 2**62, 200)
    return (*_empty(1024), *_all_active(keys), None)


def case_keys_present():
    keys = np.random.default_rng(8).integers(-2**62, 2**62, 300)
    rows = np.random.default_rng(9).choice(keys, 5000)
    return (*_prefilled(1024, keys), *_all_active(rows), None)


def case_home_collisions():
    # one home slot (5) for four keys: distances 0..3, four rounds
    K = 1024
    keys = np.array([5, 5 + K, 5 + 2 * K, 5 + 3 * K, 5, 5 + 3 * K])
    return (*_empty(K), *_all_active(keys), 4)


def case_home_collisions_present():
    # the same four keys, already placed: the looked-up rows need
    # distances 1..3 only
    K = 1024
    table_keys, used = _prefilled(K, [5, 5 + K, 5 + 2 * K, 5 + 3 * K])
    rows = np.array([5 + K, 5 + 3 * K, 5 + 2 * K] * 50)
    return (table_keys, used, *_all_active(rows), 4)


def case_one_key_many_rows():
    return (*_empty(1024), *_all_active(np.full(4096, 123456789)), 1)


def case_inactive_rows():
    rng = np.random.default_rng(10)
    keys = rng.integers(-2**40, 2**40, 2048)
    active = jnp.asarray(rng.random(2048) < 0.3)
    return (*_empty(4096), jnp.asarray(keys, jnp.int64), active, None)


def case_no_active_rows():
    keys = np.arange(512)
    return (*_empty(1024), jnp.asarray(keys, jnp.int64),
            jnp.zeros((512,), jnp.bool_), 0)


def case_negative_hashes():
    rng = np.random.default_rng(11)
    keys = -rng.integers(1, 2**62, 400)
    keys[:3] = [-1, -1024, -(2**62)]
    return (*_empty(2048), *_all_active(keys), None)


def case_fills_and_overflows():
    # 48 distinct keys for 32 slots: the table fills, the rest overflow
    keys = np.arange(48) * 7919 + 3
    return (*_empty(32), *_all_active(np.repeat(keys, 3)), MAX_PROBES)


def case_ysb_campaigns():
    # 100 campaign labels' dictionary codes, hashed as `group by
    # campaign_id` hashes them, over the window output's mix of valid rows
    strings = StringTable()
    codes = np.array([strings.encode(f"campaign-{i:03d}") for i in range(100)],
                     np.int32)
    rng = np.random.default_rng(12)
    col = jnp.asarray(rng.choice(codes, 8192))
    keys = hash_columns([col], [jnp.zeros((8192,), jnp.bool_)])
    active = jnp.asarray(rng.random(8192) < 0.25)
    return (*_empty(1024), keys, active, None)


CASES = {f.__name__[5:]: f for f in (
    case_empty_new_keys, case_keys_present, case_home_collisions,
    case_home_collisions_present, case_one_key_many_rows,
    case_inactive_rows, case_no_active_rows, case_negative_hashes,
    case_fills_and_overflows, case_ysb_campaigns)}


@pytest.mark.parametrize("name", sorted(CASES))
def test_lookup_or_insert_equals_16_round_scan(name):
    table_keys, used, keys, active, want_rounds = CASES[name]()
    K = table_keys.shape[0]
    got = jax.jit(lookup_or_insert)(table_keys, used, keys, active)
    ref = jax.jit(scan_oracle)(table_keys, used, keys, active)
    slots, new_keys, new_used, overflow, rounds = jax.device_get(got)
    r_slots, r_keys, r_used, r_overflow = jax.device_get(ref)
    np.testing.assert_array_equal(slots, r_slots)
    np.testing.assert_array_equal(new_keys, r_keys)
    np.testing.assert_array_equal(new_used, r_used)
    assert int(overflow) == int(r_overflow)
    assert rounds.dtype == np.int32

    # the round count: largest probe distance + 1, or every round when a
    # row overflowed, or none when no row is active
    act = np.asarray(active)
    home = np.abs(np.asarray(keys)) % K
    placed = act & (r_slots != NO_SLOT)
    if (act & ~placed).any():
        expected = MAX_PROBES
    elif placed.any():
        expected = int(((r_slots - home) % K)[placed].max()) + 1
    else:
        expected = 0
    assert int(rounds) == expected
    if want_rounds is not None:
        assert int(rounds) == want_rounds
    if name == "fills_and_overflows":
        assert int(overflow) > 0
    else:
        assert int(overflow) == 0
    if name == "ysb_campaigns":
        assert int(rounds) <= 3


def test_vmapped_tenants_stop_together_with_their_own_answers():
    """Under vmap (the tenant pool) the loop runs until every tenant's
    rows are placed; each tenant's answer is still the oracle's, and its
    round count its own (the batched loop holds a finished tenant's
    carry)."""
    cases = [case_home_collisions(), case_one_key_many_rows()]
    B = 4096
    padded = []
    for table_keys, used, keys, active, _ in cases:
        pad = B - keys.shape[0]
        padded.append((table_keys, used,
                       jnp.pad(keys, (0, pad)),
                       jnp.pad(active, (0, pad))))
    stacked = [jnp.stack(xs) for xs in zip(*padded)]
    slots, new_keys, new_used, overflow, rounds = jax.device_get(
        jax.jit(jax.vmap(lookup_or_insert))(*stacked))
    for t, args in enumerate(padded):
        r_slots, r_keys, r_used, r_overflow = jax.device_get(
            scan_oracle(*args))
        np.testing.assert_array_equal(slots[t], r_slots)
        np.testing.assert_array_equal(new_keys[t], r_keys)
        np.testing.assert_array_equal(new_used[t], r_used)
        assert int(overflow[t]) == int(r_overflow)
    assert rounds.tolist() == [4, 1]
