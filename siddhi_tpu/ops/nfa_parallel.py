"""Batch-parallel NFA engine — the TPU-shaped fast path for pattern and
sequence queries.

The scan engine (ops/nfa.py) replays the reference's per-event semantics
with a lax.scan over events: correct, but sequential — thousands of tiny
iterations per batch, each microseconds of real work. This engine computes
the SAME state evolution with a fixed number of vectorized rounds:

  - each pending row's trajectory through a batch is independent of every
    other row's (the reference's StateEvents never interact either:
    StreamPreStateProcessor.java:364-403 iterates them independently), so
    rows advance in parallel;
  - per round, a row at state s finds the FIRST eligible event satisfying
    s's condition and advances; R rounds cover any chain of R states
    consuming the same stream. A state no row can wait at (an
    always-armed start) runs no round;
  - counting states (A<m:n>, A+) absorb ALL their eligible matching events
    in one round with a per-row cumulative-sum placement;
  - in-batch spawns from an always-armed start state form a second
    population (one candidate row per event) that advances through the
    same rounds and is folded into the pending table at the end.

A round finds the first matching event one of two ways, chosen from the
state's condition at plan time (``round_key``):

  - keyed: the condition has an equality conjunct between the incoming
    event and a captured slot (``vid == e1.vid``). The sub-batch's events
    are sorted once by a hash of the event sides, with the event index as
    tiebreak (``sorted_key_view``), with a bucket directory of the
    hashes' top bits; each row finds its band of same-hash events by a
    binary search inside its bucket, once a sub-step for the rounds that
    key alike, and its first entry after ``last`` (vector ops and
    one-value gathers over the whole population). Only
    the rows with an entry left there walk the band, compacted into
    blocks of WALK_BLOCK rows: a block walks until an entry passes
    ``within`` and the WHOLE condition (so a hash collision never
    matches), captures it and scatters back the fields the round
    changes. O(B log B + M log W) a sub-step for M rows, B events and
    W events in the widest bucket, plus per round the walk and capture
    of the N rows with an entry left, in ceil(N / WALK_BLOCK) blocks,
    times the longest walk;
  - grid: every other state evaluates its condition on a [rows, events]
    grid, O(M * B) per round, over sub-batches of at most PB events.

A step whose rounds are all keyed takes sub-batches of as many events as
the table has rows, between PB and KB.

Supported shapes (the planner falls back to the scan engine otherwise —
`parallel_supported` below): linear chains of stream/count states, pattern
and sequence, 'every' only where it collapses to an always-armed start
(every around the leading state / the whole chain when single-scoped),
`within`, cross-state predicates. NOT supported: live mid-chain 'every'
re-arms, counting states whose condition references their own earlier
captures (self-referential Kleene), counting states followed by a state on
the SAME stream (absorb/advance races), logical and/or, absent.

Semantics parity with the scan engine (and the reference) is bit-exact
except under overflow pressure: the scan engine frees completed rows
mid-batch event-by-event, this engine allocates spawned survivors at batch
end, so a saturated table drops (and counts) more re-arms here.

The table carries counters the step accumulates on the device and only
``PatternQueryRuntime.stats()`` reads (``COUNTERS``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.event import CURRENT, EventBatch
from ..core.types import AttrType, NUMERIC_TYPES, np_dtype, promote
from ..lang import ast as A
from .expr import Col, CompileError, CompiledExpr, Scope, compile_expression
from .join import _flatten_and
from .keyed import RUNNING_COUNT_LIMIT, hash_columns, running_count
from .nfa import (NfaEngine, NfaStateSpec, POS_INF, PatternScope, SlotSpec,
                  _map_children)
from .table import sorted_key_view

BIG = np.int32(2 ** 30)  # numpy, not jnp: see ops/sentinels.py
NO_EMIT = np.int64(2 ** 62)  # emission key of a row that emits nothing

# device counters in the table, read by PatternQueryRuntime.stats():
# steps; valid events consumed; live rows summed over steps at a step's
# start and end; the most live rows after any sub-step; rows spawned
# alive; rows that completed (or a sequence mismatch killed) in the
# rounds; rows `within` pruned; row x event pairs whose condition a
# round evaluated; rows a keyed round placed in a walk block; iterations
# of the band lookups' binary search. Per step, live_in + spawned -
# completed - pruned - rows the fold dropped (counted in `overflow`) =
# live_out.
COUNTERS = ("nfa_steps", "nfa_events", "nfa_live_in", "nfa_live_out",
            "nfa_live_peak", "nfa_spawned", "nfa_completed", "nfa_pruned",
            "nfa_candidates", "nfa_walk_rows", "nfa_band_iters")


def _cond_refs_own_indexed(st: NfaStateSpec, slots: list[SlotSpec]) -> bool:
    """Does the state's condition reference its OWN slot with an explicit
    event index (self-referential Kleene, e.g. A[v > e1[last].v]+)?"""
    own = slots[st.slot]
    names = {own.ref, own.stream_id} - {None}
    found = []

    def walk(e):
        if isinstance(e, A.Variable):
            if e.stream_ref in names and e.index is not None:
                found.append(e)
        for f in getattr(e, "__dataclass_fields__", {}):
            v = getattr(e, f)
            if isinstance(v, A.Expression):
                walk(v)
            elif isinstance(v, list):
                for x in v:
                    if isinstance(x, A.Expression):
                        walk(x)

    if st.cond_ast is not None:
        walk(st.cond_ast)
    return bool(found)


def _waiting_states(states: list[NfaStateSpec]) -> set:
    """Indices of the states at which table rows can ever wait."""
    reach = set()
    for st in states:
        if st.armed_once:
            reach.add(st.idx)
        if st.always_armed:
            if st.is_counting:
                reach.add(st.idx)
            elif st.next_idx >= 0:
                reach.add(st.next_idx)
    changed = True
    while changed:
        changed = False
        for st in states:
            if st.idx in reach and st.next_idx >= 0 \
                    and st.next_idx not in reach:
                reach.add(st.next_idx)
                changed = True
    return reach


def parallel_supported(slots: list[SlotSpec],
                       states: list[NfaStateSpec],
                       state_type: str = "pattern") -> bool:
    """Can the batch-parallel engine run this compiled chain?"""
    # logical groups and absent states run on the scan engine
    if any(st.partner >= 0 or st.is_absent for st in states):
        return False
    # sequences with armed-once starts need the scan engine's per-round
    # pending lifecycle (one-shot starts, cross-stream staleness —
    # SequenceMultiProcessStreamReceiver.stabilizeStates); counting-start
    # sequences keep the parallel path (their absorb lifecycle is exempt)
    if state_type == "sequence" and any(
            st.armed_once or st.rearm_each_round for st in states):
        return False
    reach = _waiting_states(states)
    for st in states:
        if st.every_arm >= 0:
            # live re-arm edge? dead iff no rows ever reach this state, or
            # it is a min==1 counting state entered only with n>=1 rows
            if st.idx in reach and not (
                    st.is_counting and st.min_count == 1
                    and not st.armed_once):
                return False
        if st.is_counting:
            if _cond_refs_own_indexed(st, slots):
                return False
            if st.next_idx >= 0 and \
                    states[st.next_idx].stream_id == st.stream_id:
                return False
    return True


# ---------------------------------------------------------------------------
# keyed rounds: equality conjuncts between the event and a captured slot
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundKey:
    """The equality conjuncts of one state's condition that pair the
    incoming event with captured slots (``vid == e1.vid``): both sides of
    each are cast to the type the conjunct compares in and hashed
    together. States whose event sides read the same (``signature``)
    share one sorted view of a sub-batch; those whose row sides read the
    same too (``band_key``) share each row's band, until a round writes
    one of the slots the row side reads (``row_slots``)."""

    event: tuple       # CompiledExpr over the incoming event
    row: tuple         # CompiledExpr over the row's captured slots
    dtypes: tuple      # numpy dtype each conjunct compares in
    signature: tuple
    band_key: tuple
    row_slots: frozenset


class _SlotRecorder(Scope):
    """Wraps a PatternScope and records the slot keys an expression's
    variables resolve to."""

    def __init__(self, base: Scope):
        self.base = base
        self.keys: list = []

    def resolve(self, var: A.Variable):
        key, t = self.base.resolve(var)
        self.keys.append(key)
        return key, t


def _side(keys: list, own: int) -> Optional[str]:
    """'event' when every reference is the incoming event (the state's
    own slot, current copy), 'row' when none is the own slot."""
    if not keys:
        return None
    if all(j == own and c == 0 for _, j, _, c in keys):
        return "event"
    if all(j != own for _, j, _, _ in keys):
        return "row"
    return None


def _key_dtype(a: AttrType, b: AttrType):
    if a in NUMERIC_TYPES and b in NUMERIC_TYPES:
        return np.dtype(np_dtype(promote(a, b)))
    if a is b and a in (AttrType.STRING, AttrType.BOOL):
        return np.dtype(np_dtype(a))
    return None


def _unref(e):
    """The expression with stream references dropped: an event side reads
    the incoming event whichever name the state gives it."""
    if isinstance(e, A.Variable):
        return dataclasses.replace(e, stream_ref=None)
    return _map_children(e, _unref)


def round_key(st: NfaStateSpec, slots: list[SlotSpec]) -> Optional[RoundKey]:
    """The keyed form of a plain state's condition, or None where it has
    no equality conjunct between the incoming event and a captured slot
    (such a state runs the grid)."""
    if st.cond_ast is None or st.is_counting:
        return None
    ev, row, dts, sig, row_sig = [], [], [], [], []
    for conj in _flatten_and(st.cond_ast):
        if not (isinstance(conj, A.Compare) and conj.op == "=="):
            continue
        sides = {}
        for e in (conj.left, conj.right):
            rec = _SlotRecorder(PatternScope(slots, own_slot=st.slot))
            try:
                ce = compile_expression(e, rec)
            except CompileError:
                break
            sides[_side(rec.keys, st.slot)] = (ce, e, tuple(rec.keys))
        if set(sides) != {"event", "row"}:
            continue
        ce_e, ast_e, _ = sides["event"]
        ce_r, ast_r, keys_r = sides["row"]
        dt = _key_dtype(ce_e.type, ce_r.type)
        if dt is None:
            continue
        ev.append(ce_e)
        row.append(ce_r)
        dts.append(dt)
        sig.append((repr(_unref(ast_e)), dt.str))
        row_sig.append((repr(ast_r), keys_r))
    if not ev:
        return None
    signature = (st.stream_id,) + tuple(sig)
    return RoundKey(tuple(ev), tuple(row), tuple(dts), signature,
                    signature + tuple(row_sig),
                    frozenset(j for r in row_sig for _, j, _, _ in r[1]))


def _high32(h):
    return (h >> 32).astype(jnp.int32)


def _key_hash(exprs, dtypes, env, shape):
    """-> ([shape] int64 hash of the key parts, [shape] any part null)."""
    vals, nulls = [], []
    for ce, dt in zip(exprs, dtypes):
        c = ce.fn(env)
        v = jnp.broadcast_to(c.values, shape).astype(dt)
        if np.issubdtype(dt, np.floating):
            v = jnp.where(v == 0, jnp.zeros((), dt), v)   # -0.0 == 0.0
        vals.append(v)
        nulls.append(jnp.broadcast_to(c.nulls, shape))
    return hash_columns(vals, nulls), functools.reduce(jnp.logical_or, nulls)


def _dir_bits(B: int) -> int:
    """Bits of the bucket directory over a sub-batch of B events: about
    four buckets an event."""
    return max(B - 1, 1).bit_length() + 2


def _bucket(key, bits: int):
    """The top ``bits`` bits of int32 keys as a bucket in [0, 2**bits),
    in the keys' order."""
    return (key >> (32 - bits)) + np.int32(2 ** (bits - 1))


def _sorted_view(keys, live):
    """The sub-batch's events sorted by (int32 key, event index), live
    events first: order, sorted keys, live count; per position, where its
    run of equal keys ends and where each event sits; and a bucket
    directory of the sorted keys' top ``_dir_bits`` bits, each bucket's
    span [start, stop) of positions (an empty bucket's span is empty).
    Equal buckets are contiguous, so a span is a run's first and last
    position: two scatters of distinct indices, no search over the
    buckets and no scan."""
    B = keys.shape[0]
    order, sk, n_live = sorted_key_view(keys, live)
    order, n_live = order.astype(jnp.int32), n_live.astype(jnp.int32)
    pos = jnp.arange(B, dtype=jnp.int32)
    end = jnp.minimum(jnp.searchsorted(sk, sk, side="right")
                      .astype(jnp.int32), n_live)
    at = jnp.zeros((B,), jnp.int32).at[order].set(pos, unique_indices=True)
    bits = _dir_bits(B)
    t = _bucket(sk, bits)
    edge = jnp.full((1,), -1, jnp.int32)
    in_run = pos < n_live
    opens = in_run & (t != jnp.concatenate([edge, t[:-1]]))
    closes = in_run & ((t != jnp.concatenate([t[1:], edge])) |
                       (pos == n_live - 1))

    def span(mask, vals):
        # the other positions write past the end (dropped), each its own
        dst = jnp.where(mask, t, 2 ** bits + pos)
        return jnp.zeros((2 ** bits,), jnp.int32).at[dst].set(
            vals, mode="drop", unique_indices=True)

    return {"order": order, "keys": sk, "live": n_live, "end": end,
            "at": at, "start": span(opens, pos), "stop": span(closes, pos + 1)}


def _band_lookup(view, key):
    """Each int32 ``key``'s band in ``view`` (``_sorted_view``): the
    positions [lo, hi) of the live events with that key, empty where it
    has none. A binary search inside the key's bucket, run while some
    key's interval is non-empty: as many iterations as the widest bucket
    takes, at most bit_length(B) for B events. Returns (lo, hi,
    iterations)."""
    keys = view["keys"]
    B = keys.shape[0]
    t = _bucket(key, _dir_bits(B))

    def searching(c):
        return jnp.any(c[0] < c[1])

    def halve(c):
        lo, top, n = c
        mid = (lo + top) // 2
        more = lo < top
        below = keys[jnp.minimum(mid, B - 1)] < key
        return (jnp.where(more & below, mid + 1, lo),
                jnp.where(more & ~below, mid, top), n + 1)

    lo, _, n = jax.lax.while_loop(
        searching, halve, (view["start"][t], view["stop"][t], jnp.int64(0)))
    at_lo = jnp.minimum(lo, B - 1)
    hit = (lo < view["live"]) & (keys[at_lo] == key)
    return lo, jnp.where(hit, view["end"][at_lo], lo), n


def _lexsort32(key, seq):
    """Positions in (key, seq) order, for keys below 2**31 or NO_EMIT and
    non-negative int64 seqs, sorted on 32-bit parts (a 64-bit sort key
    takes the TPU compiler minutes at tens of thousands of rows)."""
    k = jnp.minimum(key, np.int64(2 ** 31 - 1)).astype(jnp.int32)
    return jnp.lexsort(((seq & 0xFFFFFFFF).astype(jnp.uint32),
                        (seq >> 32).astype(jnp.int32), k)).astype(jnp.int32)


def _nth_true(mask, K: int, count=None, skip=0):
    """[K] positions of set entries ``skip`` + 1 to ``skip`` + K of
    ``mask``, in order; len(mask) where there are fewer. A search over
    the running count (``count``, where the caller holds it), so no
    scatter and no sort of indices."""
    if count is None:
        count = running_count(mask)
    want = skip + jnp.arange(1, K + 1, dtype=jnp.int32)
    return jnp.searchsorted(count, want, side="left").astype(jnp.int32)


def _concat_pops(a, b):
    """One population of ``a``'s rows then ``b``'s (the fields ``a``
    has)."""
    return jax.tree_util.tree_map(lambda x, y: jnp.concatenate([x, y]), a,
                                  {k: v for k, v in b.items() if k in a})


def _split_pops(pop, m: int, born_rel):
    """Undo ``_concat_pops``: the first ``m`` rows, then the rest (which
    get back their ``born_rel``)."""
    def cut(tree, lo, hi):
        return jax.tree_util.tree_map(lambda x: x[lo:hi], tree)
    n = pop["state"].shape[0]
    return cut(pop, 0, m), {**cut(pop, m, n), "born_rel": born_rel}


def _first_true(mask):
    """[P, B] bool -> ([P] first-true index (0 if none), [P] any)."""
    j = jnp.argmax(mask, axis=1).astype(jnp.int32)
    return j, jnp.any(mask, axis=1)


class ParallelNfaEngine(NfaEngine):
    """Same table pytree, match schema, and outputs as NfaEngine; only the
    per-stream step is rebuilt round-parallel. Sub-batches of at most PB
    events bound the [rows, events] grid of a grid round; a keyed walk
    examines one band entry per row per iteration, over blocks of at most
    WALK_BLOCK rows that have an entry left."""

    PB = 4096
    KB = 65536
    WALK_BLOCK = 4096
    # the fold and the emission order count over the table's rows and a
    # sub-batch's (at most KB): running_count must stay exact
    MAX_PENDING = RUNNING_COUNT_LIMIT - KB - 1

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._waiting = _waiting_states(self.states)
        # plan-time choice per state: keyed when the condition pairs the
        # event with a captured slot by equality, grid otherwise
        self.keys = {st.idx: round_key(st, self.slots)
                     for st in self.states
                     if self.state_type == "pattern"}

    def init_state(self):
        table = super().init_state()
        for name in COUNTERS:
            table[name] = jnp.int64(0)
        return table

    def _keyed_sub_batch(self) -> int:
        """Events per sub-batch of a step whose rounds are all keyed: as
        many as the table has rows, between PB and KB. A sub-step sweeps
        the whole table, so a large table takes large sub-batches; a
        small one keeps its event sort small (the TPU compiler's time
        for a sort grows with its length)."""
        return min(max(self.M, self.PB), self.KB)

    def round_kind(self, st: NfaStateSpec) -> str:
        """'keyed' or 'grid': how a round at ``st`` finds each row's first
        matching event."""
        return "grid" if self.keys.get(st.idx) is None else "keyed"

    # -- env builders ----------------------------------------------------
    def _env_pairs(self, pop, ev_cols, ev_nulls, own_slot: int, shape):
        """Env of Cols broadcastable to ``shape`` = [P, X]: row captures
        [P, 1] against event values already shaped to broadcast ([1, B]
        for the grid, the [P, W] entries of a keyed walk); the own slot's
        current view is the incoming event. Unused entries are
        dead-code-eliminated by XLA."""
        env = {}
        for j, spec in enumerate(self.slots):
            buf = pop["slots"][j]
            n = buf["n"]
            for a in range(len(spec.schema.types)):
                for c in range(spec.cap):
                    vals = buf["cols"][a][:, c][:, None]
                    nulls = buf["nulls"][a][:, c][:, None]
                    if j == own_slot:
                        at_n = (n == c)[:, None]
                        vals = jnp.where(at_n, ev_cols[a], vals)
                        nulls = jnp.where(at_n, ev_nulls[a], nulls)
                    env[("slot", j, a, c)] = Col(vals, nulls)
            n_eff = n + (1 if j == own_slot else 0)
            for a in range(len(spec.schema.types)):
                for k in range(min(spec.cap, 4)):
                    pos = jnp.clip(n_eff - 1 - k, 0, spec.cap - 1)
                    vals = jnp.take_along_axis(
                        buf["cols"][a], pos[:, None], axis=1)
                    nulls = jnp.take_along_axis(
                        buf["nulls"][a], pos[:, None], axis=1)
                    if j == own_slot and k == 0:
                        vals = jnp.broadcast_to(ev_cols[a], shape)
                        nulls = jnp.broadcast_to(ev_nulls[a], shape)
                    env[("slot_last", j, a, k)] = Col(vals, nulls)
        return env

    def _virtual_env_b(self, st, ev_cols, ev_nulls):
        """[B] env for start-state spawn conditions (own slot = event,
        everything else null)."""
        env = {}
        for j, spec in enumerate(self.slots):
            for a, t in enumerate(spec.schema.types):
                for c in range(spec.cap):
                    if j == st.slot and c == 0:
                        env[("slot", j, a, c)] = Col(ev_cols[a],
                                                     ev_nulls[a])
                    else:
                        env[("slot", j, a, c)] = Col(
                            jnp.zeros((), dtype=np_dtype(t)),
                            jnp.ones((), dtype=jnp.bool_))
                for k in range(min(spec.cap, 4)):
                    key = ("slot_last", j, a, k)
                    if j == st.slot and k == 0:
                        env[key] = Col(ev_cols[a], ev_nulls[a])
                    else:
                        env[key] = Col(jnp.zeros((), dtype=np_dtype(t)),
                                       jnp.ones((), dtype=jnp.bool_))
        return env

    # -- population helpers ----------------------------------------------
    def _empty_pop(self, P: int):
        slots = []
        for s in self.slots:
            slots.append({
                "cols": tuple(jnp.zeros((P, s.cap), dtype=np_dtype(t))
                              for t in s.schema.types),
                "nulls": tuple(jnp.ones((P, s.cap), dtype=jnp.bool_)
                               for _ in s.schema.types),
                "ts": jnp.zeros((P, s.cap), dtype=jnp.int64),
                "n": jnp.zeros((P,), dtype=jnp.int32),
            })
        return {
            "state": jnp.full((P,), len(self.states), jnp.int32),
            "valid": jnp.zeros((P,), jnp.bool_),
            "last": jnp.full((P,), -1, jnp.int32),
            "ts0": jnp.zeros((P,), jnp.int64),
            "has_ts0": jnp.zeros((P,), jnp.bool_),
            "min_prev": jnp.zeros((P,), jnp.bool_),
            "minrel": jnp.full((P,), BIG, jnp.int32),
            "seq": jnp.zeros((P,), jnp.int64),
            "emit_at": jnp.full((P,), -1, jnp.int32),
            "emit_n": jnp.zeros((P,), jnp.int32),
            "slots": tuple(slots),
        }

    def _capture_at(self, pop, slot_j: int, pos, ev_cols, ev_nulls, ev_ts,
                    j, mask):
        """Capture event j (per-row index) into slot_j at per-row pos."""
        spec = self.slots[slot_j]
        buf = pop["slots"][slot_j]
        P = mask.shape[0]
        pos = jnp.clip(pos, 0, spec.cap - 1)
        # cap-bounded one-hot scatter, not a data cross product
        onehot = (
            (jnp.arange(spec.cap)[None, :] == pos[:, None])  # lint: disable=quadratic-grid-hazard
            & mask[:, None])
        cols = tuple(jnp.where(onehot, c[j][:, None], col)
                     for c, col in zip(ev_cols, buf["cols"]))
        nulls = tuple(jnp.where(onehot, nl[j][:, None], nu)
                      for nl, nu in zip(ev_nulls, buf["nulls"]))
        ts = jnp.where(onehot, ev_ts[j][:, None], buf["ts"])
        new_buf = {"cols": cols, "nulls": nulls, "ts": ts, "n": buf["n"]}
        return {**pop, "slots": tuple(
            new_buf if k == slot_j else b
            for k, b in enumerate(pop["slots"]))}

    # -- the round engine ------------------------------------------------
    def _advance_rounds(self, pop, ev, rounds, views, B: int):
        """One pass over the states rows can wait at, IN CHAIN ORDER,
        advances every row as far as it can go in this batch: linear
        chains compile to increasing state indices, so a row that advances
        at state k is picked up again by the state-(k+1) round with
        eligibility starting after its captured event. ev = (ts, kind,
        valid, cols, nulls); ``views`` holds the sub-batch's sorted view
        per key signature. Returns (pop, candidates evaluated, rows placed
        in a walk block, band search iterations)."""
        ev_ts, ev_kind, ev_valid, ev_cols, ev_nulls = ev
        idx_b = jnp.arange(B, dtype=jnp.int32)
        is_cur = ev_valid & (ev_kind == CURRENT)
        seqmode = self.state_type == "sequence"
        cands = walk_rows = band_iters = jnp.int64(0)
        # band key -> (slots its row side reads, each row's band): looked
        # up once for the rounds that key alike, until a round writes one
        # of those slots
        bands = {}
        for st in rounds:
            personas = [cs for cs in self.states
                        if cs.is_counting and cs.next_idx == st.idx]
            rk = self.keys.get(st.idx)
            if rk is not None and rk.band_key not in bands:
                band = self._row_band(pop, rk, views[rk.signature])
                bands[rk.band_key] = (rk.row_slots, band)
                band_iters = band_iters + band[3]
            pop, n, w = self._state_round(
                pop, st, personas, ev, is_cur, idx_b, B, seqmode, views,
                bands)
            cands, walk_rows = cands + n, walk_rows + w
            bands = {k: b for k, b in bands.items() if st.slot not in b[0]}
        return pop, cands, walk_rows, band_iters

    def _within_ok(self, pop, ts):
        """Rows [P, 1] x event times ``ts`` (broadcastable to [P, X]):
        inside ``within`` of the row's first capture, inclusive."""
        if self.within_ms is None:
            return jnp.ones((), jnp.bool_)
        ok = jnp.abs(ts - pop["ts0"][:, None]) <= self.within_ms
        return ~pop["has_ts0"][:, None] | ok

    def _eligible(self, pop, is_cur, idx_b, ev_ts):
        # pending-table grid: bounded by PB, and only for states with no
        # equality conjunct to key on (see round_key)
        elig = (
            is_cur[None, :]  # lint: disable=quadratic-grid-hazard
            & (idx_b[None, :] > pop["last"][:, None]))
        return elig & self._within_ok(pop, ev_ts[None, :])

    @staticmethod
    def _cond_ok(st, env, shape):
        if st.cond is None:
            return jnp.ones(shape, jnp.bool_)
        c = st.cond.fn(env)
        return jnp.broadcast_to(c.values & ~c.nulls, shape)

    def _key_view(self, st, rk: RoundKey, ev, B: int):
        """The sub-batch's events sorted by (key hash, event index), with
        their bucket directory (``_sorted_view``). The hash is cut to its
        high 32 bits, as the sort keys are all 32-bit (a 64-bit sort key
        at 65,536 events takes the TPU compiler minutes); events that are
        not CURRENT or whose key is null sort last."""
        _, ev_kind, ev_valid, ev_cols, ev_nulls = ev
        h, null = _key_hash(rk.event, rk.dtypes,
                            self._virtual_env_b(st, ev_cols, ev_nulls), (B,))
        return _sorted_view(_high32(h),
                            ev_valid & (ev_kind == CURRENT) & ~null)

    def _row_band(self, pop, rk: RoundKey, view):
        """Each row's band of same-hash events in ``view``, whatever its
        state: the row side reads captured slots only (no own slot, no
        events). Returns (lo, hi, key null, search iterations)."""
        P = pop["state"].shape[0]
        h, null = _key_hash(rk.row, rk.dtypes,
                            self._env_pairs(pop, (), (), -1, (P, 1)), (P, 1))
        lo, hi, n = _band_lookup(view, _high32(h[:, 0]))
        return lo, hi, null[:, 0], n

    def _keyed_round(self, pop, st, view, band, at_rows, ev, B: int):
        """A keyed round. Over the whole population, each row at the
        state takes its band of same-hash events (index order within it;
        ``band``, from ``_row_band``) and finds the band's first entry
        after ``last``: vector ops and gathers of one value per row. Only
        the rows with an entry left there walk and capture, compacted
        into blocks of WALK_BLOCK rows, as many blocks as those rows fill
        (a count read on the device, so none is dropped); the fields the
        round changes are scattered back. Every other row keeps its
        state. Returns (pop, entries walked, rows placed in a walk
        block)."""
        ev_ts, _, ev_valid, _, _ = ev
        order = view["order"]
        P = at_rows.shape[0]
        lo, hi, null, _ = band
        active = at_rows & ~null
        hi = jnp.where(active, hi, lo)
        # the band's first entry after `last`: next to `last` itself where
        # that event lies in the band (it does where both sides of the key
        # read the same values, vid == e1.vid); else a binary search,
        # run only while some row needs it
        last = pop["last"]
        q = view["at"][jnp.clip(last, 0, B - 1)]
        inside = (last >= 0) & (q >= lo) & (q < hi)
        search = (last >= 0) & ~inside & (lo < hi)

        def searching(c):
            lo, top = c
            return jnp.any(search & (lo < top))

        def halve(c):
            lo, top = c
            mid = (lo + top) // 2
            right = order[jnp.minimum(mid, B - 1)] <= last
            more = search & (lo < top)
            return (jnp.where(more & right, mid + 1, lo),
                    jnp.where(more & ~right, mid, top))

        found_lo, _ = jax.lax.while_loop(searching, halve, (lo, hi))
        p0 = jnp.where(inside, q + 1, jnp.where(search, found_lo, lo))
        deadline = jnp.full((P,), POS_INF)
        if self.within_ms is not None:
            # event time in order: the valid events are a prefix and
            # never go back (else no walk ends early)
            in_order = jnp.all(ev_valid[1:] <= ev_valid[:-1]) & jnp.all(
                ~ev_valid[1:] | (ev_ts[1:] >= ev_ts[:-1]))
            deadline = jnp.where(pop["has_ts0"] & in_order,
                                 pop["ts0"] + self.within_ms, POS_INF)

        # the rows with an entry left in their band, in blocks of W
        need = active & (p0 < hi)
        n_need = jnp.sum(need, dtype=jnp.int32)
        count = running_count(need)
        W = min(self.WALK_BLOCK, P)
        lanes = jnp.arange(W, dtype=jnp.int32)

        def pending(c):
            return c[0] * W < n_need

        def block(c):
            b, fields, walked = c
            rows = _nth_true(need, W, count, b * W)
            real = rows < P
            at = jnp.minimum(rows, P - 1)
            # a block reads only its own rows, which no other block writes
            sub = jax.tree_util.tree_map(
                lambda x: x[at], self._with_round_fields(pop, st, fields))
            j, hit, n = self._keyed_first(sub, st, order, p0[at], hi[at],
                                          deadline[at], real, ev, B)
            sub = self._advance(sub, st, j, hit,
                                jnp.zeros((W,), jnp.bool_), ev)
            # padding lanes write past the end (dropped), each its own
            dst = jnp.where(real, rows, P + lanes)
            fields = jax.tree_util.tree_map(
                lambda full, part: full.at[dst].set(
                    part, mode="drop", indices_are_sorted=True,
                    unique_indices=True),
                fields, self._round_fields(sub, st))
            return b + 1, fields, walked + n

        _, fields, walked = jax.lax.while_loop(
            pending, block,
            (jnp.int32(0), self._round_fields(pop, st), jnp.int64(0)))
        return (self._with_round_fields(pop, st, fields), walked,
                n_need.astype(jnp.int64))

    def _keyed_first(self, pop, st, order, p0, hi, deadline, live, ev,
                     B: int):
        """The first matching event of each of a block's rows: the row's
        band of the sorted view from position ``p0`` to ``hi``, walked one
        entry per iteration until every ``live`` row has a match or has
        left its band — a long band takes more iterations, never a cut.
        Each entry walked is checked against ``within`` and the state's
        whole condition; an entry past the row's ``deadline`` (finite
        where the sub-batch's event time never decreases) ends the walk
        too, and is not counted. ``pop`` holds the block's rows only.
        Returns (j, found, entries walked)."""
        ev_ts, _, _, ev_cols, ev_nulls = ev
        P = p0.shape[0]

        def walking(c):
            return jnp.any(~c[1])

        def walk(c):
            p, done, j, found, walked = c
            inband = p < hi
            e = order[jnp.minimum(p, B - 1)]
            env = self._env_pairs(pop, [x[e][:, None] for x in ev_cols],
                                  [x[e][:, None] for x in ev_nulls],
                                  st.slot, (P, 1))
            ok = inband & (self._cond_ok(st, env, (P, 1)) &
                           self._within_ok(pop, ev_ts[e][:, None]))[:, 0]
            # a later entry of the band is no earlier: past the deadline,
            # nothing after this entry can match either
            inband = inband & (ev_ts[e] <= deadline)
            live = ~done
            walked = walked + jnp.sum(live & (ok | inband), dtype=jnp.int64)
            hit = live & ok
            j = jnp.where(hit, e, j)
            return (p + 1, done | ~inband | ok, j, found | hit, walked)

        _, _, j, found, walked = jax.lax.while_loop(
            walking, walk,
            (p0, ~live, jnp.zeros((P,), jnp.int32),
             jnp.zeros((P,), jnp.bool_), jnp.int64(0)))
        return j, found, walked

    @staticmethod
    def _round_fields(pop, st):
        """The fields a plain round at ``st`` can change: its slot, the
        first capture's time, ``last``, and the state, or at the final
        state the emission and ``valid``."""
        names = ("ts0", "has_ts0", "last") + (
            ("emit_at", "emit_n", "valid") if st.next_idx == -1
            else ("state",))
        return {**{k: pop[k] for k in names}, "slot": pop["slots"][st.slot]}

    @staticmethod
    def _with_round_fields(pop, st, fields):
        """``pop`` with the fields ``_round_fields`` names replaced."""
        rest = {k: v for k, v in fields.items() if k != "slot"}
        return {**pop, **rest, "slots": tuple(
            fields["slot"] if k == st.slot else b
            for k, b in enumerate(pop["slots"]))}

    def _advance(self, pop, st, j, adv, kill, ev):
        """Rows ``adv`` capture event ``j`` into the state's slot and move
        on, or emit at the final state; rows ``kill`` die."""
        ev_ts, _, _, ev_cols, ev_nulls = ev
        P = adv.shape[0]
        pop = self._capture_at(pop, st.slot, jnp.zeros((P,), jnp.int32),
                               ev_cols, ev_nulls, ev_ts, j, adv)
        buf = pop["slots"][st.slot]
        new_n = jnp.where(adv, jnp.int32(1), buf["n"])
        pop = {**pop, "slots": tuple(
            {**b, "n": new_n} if k == st.slot else b
            for k, b in enumerate(pop["slots"]))}
        got_first = adv & ~pop["has_ts0"]
        pop = {**pop,
               "ts0": jnp.where(got_first, ev_ts[j], pop["ts0"]),
               "has_ts0": pop["has_ts0"] | got_first,
               "last": jnp.where(adv, j, pop["last"])}
        if st.next_idx == -1:
            return {**pop,
                    "emit_at": jnp.where(adv, j, pop["emit_at"]),
                    "emit_n": jnp.where(adv, jnp.int32(1), pop["emit_n"]),
                    "valid": pop["valid"] & ~adv & ~kill}
        return {**pop,
                "state": jnp.where(adv, jnp.int32(st.next_idx),
                                   pop["state"]),
                "valid": pop["valid"] & ~kill}

    def _state_round(self, pop, st, personas, ev, is_cur, idx_b, B,
                     seqmode, views, bands):
        """One round at ``st``. Returns (pop, candidates evaluated, rows
        placed in a walk block)."""
        ev_ts, _, _, ev_cols, ev_nulls = ev
        P = pop["state"].shape[0]
        normal = pop["valid"] & (pop["state"] == st.idx)
        persona = jnp.zeros((P,), jnp.bool_)
        for cs in personas:
            persona = persona | (
                pop["valid"] & (pop["state"] == cs.idx) &
                (pop["slots"][cs.slot]["n"] >= cs.min_count) &
                pop["min_prev"])
        at_rows = normal | persona
        rk = self.keys.get(st.idx)
        if rk is not None:
            return self._keyed_round(pop, st, views[rk.signature],
                                     bands[rk.band_key][1], at_rows, ev, B)
        no_walk = jnp.int64(0)
        cands = jnp.int64(P * B)
        # cheap short-circuit is not possible under jit; grids are DCE'd
        env = self._env_pairs(pop, [c[None, :] for c in ev_cols],
                              [n[None, :] for n in ev_nulls], st.slot,
                              (P, B))
        cond_ok = self._cond_ok(st, env, (P, B))
        elig = self._eligible(pop, is_cur, idx_b, ev_ts)

        if st.is_counting:
            return self._counting_round(
                pop, st, at_rows, persona, elig & cond_ok, ev_ts,
                ev_cols, ev_nulls, B), cands, no_walk

        kill = jnp.zeros((P,), jnp.bool_)
        if seqmode:
            # sequence: a NORMAL row's fate is decided by its first
            # eligible event (advance on match, die on mismatch);
            # PERSONA rows test every event and are never
            # sequence-killed (the scan engine's seq_kill applies to
            # `normal` only)
            j0, has0 = _first_true(elig)
            cond_at = jnp.take_along_axis(
                cond_ok, j0[:, None].astype(jnp.int64), axis=1)[:, 0]
            jm, hasm = _first_true(elig & cond_ok)
            adv = (normal & has0 & cond_at) | (persona & hasm)
            kill = normal & has0 & ~cond_at
            j = jnp.where(persona, jm, j0)
        else:
            j, has = _first_true(elig & cond_ok)
            adv = at_rows & has
        return self._advance(pop, st, j, adv, kill, ev), cands, no_walk

    def _counting_round(self, pop, st, at_rows, persona, cand, ev_ts,
                        ev_cols, ev_nulls, B):
        """Absorb ALL eligible matching events into the counting slot in
        one pass (cumulative-sum placement)."""
        P = at_rows.shape[0]
        spec = self.slots[st.slot]
        buf = pop["slots"][st.slot]
        n = jnp.where(persona, jnp.int32(0), buf["n"])  # personas restart
        cap_limit = spec.cap if st.max_count == -1 \
            else min(st.max_count, spec.cap)
        room = jnp.maximum(cap_limit - n, 0)
        cand = cand & at_rows[:, None]
        csum = jnp.cumsum(cand.astype(jnp.int32), axis=1)
        take = cand & (csum <= room[:, None])
        # dtype=int32: jnp.sum promotes int32 inputs to int64 under x64
        # (NumPy accumulator promotion), which would widen the carried
        # slot count and break the fori_loop carry contract
        k = jnp.where(at_rows, jnp.sum(take, axis=1, dtype=jnp.int32), 0)
        absorbed = at_rows & (k > 0)

        # place the r-th taken event at slot position n + r - 1
        cols = list(buf["cols"])
        nulls = list(buf["nulls"])
        ts = buf["ts"]
        for c in range(spec.cap):
            want = (c + 1) - n  # the rank that lands at position c
            sel = take & (csum == want[:, None])
            j_c, has_c = _first_true(sel)
            # cap-bounded one-hot scatter, not a data cross product
            onehot = (
                (jnp.arange(spec.cap)[None, :] == c)  # lint: disable=quadratic-grid-hazard
                & (has_c & at_rows)[:, None])
            for a in range(len(spec.schema.types)):
                cols[a] = jnp.where(onehot, ev_cols[a][j_c][:, None],
                                    cols[a])
                nulls[a] = jnp.where(onehot, ev_nulls[a][j_c][:, None],
                                     nulls[a])
            ts = jnp.where(onehot, ev_ts[j_c][:, None], ts)
        new_n = n + k
        new_buf = {"cols": tuple(cols), "nulls": tuple(nulls), "ts": ts,
                   "n": jnp.where(at_rows, new_n, buf["n"])}
        pop = {**pop, "slots": tuple(
            new_buf if m == st.slot else b
            for m, b in enumerate(pop["slots"]))}

        # first absorbed event (ts0 / last bookkeeping)
        j_first, _ = _first_true(take)
        j_last_rank = jnp.maximum(k, 1)
        sel_last = take & (csum == j_last_rank[:, None])
        j_last, _ = _first_true(sel_last)
        got_first = absorbed & ~pop["has_ts0"]
        pop = {**pop,
               "ts0": jnp.where(got_first, ev_ts[j_first], pop["ts0"]),
               "has_ts0": pop["has_ts0"] | got_first,
               "last": jnp.where(absorbed, j_last, pop["last"]),
               "state": jnp.where(absorbed, jnp.int32(st.idx),
                                  pop["state"])}

        # min crossing: rank (min_count - n) among taken events
        crossed = absorbed & (n < st.min_count) & (new_n >= st.min_count)
        min_rank = st.min_count - n
        sel_min = take & (csum == min_rank[:, None])
        j_min, _ = _first_true(sel_min)
        pop = {**pop,
               "minrel": jnp.where(crossed, j_min, pop["minrel"])}

        maxed = absorbed & (st.max_count != -1) & (new_n >= st.max_count)
        if st.next_idx == -1:
            pop = {**pop,
                   "emit_at": jnp.where(crossed, j_min, pop["emit_at"]),
                   "emit_n": jnp.where(crossed, jnp.int32(st.min_count),
                                       pop["emit_n"]),
                   "valid": pop["valid"] & ~maxed}
        else:
            pop = {**pop,
                   "state": jnp.where(maxed, jnp.int32(st.next_idx),
                                      pop["state"])}
        return pop

    # -- spawns ----------------------------------------------------------
    def _spawn_pop(self, start, ev, B, next_seq):
        """One candidate row per event for the always-armed start state.
        Returns (pop, n_spawned)."""
        ev_ts, ev_kind, ev_valid, ev_cols, ev_nulls = ev
        env = self._virtual_env_b(start, ev_cols, ev_nulls)
        if start.cond is not None:
            c = start.cond.fn(env)
            ok = jnp.broadcast_to(c.values & ~c.nulls, (B,))
        else:
            ok = jnp.ones((B,), jnp.bool_)
        hit = ok & ev_valid & (ev_kind == CURRENT)

        pop = self._empty_pop(B)
        idx = jnp.arange(B, dtype=jnp.int32)
        rank = running_count(hit).astype(jnp.int64) - 1

        if start.is_counting:
            min_now = start.min_count <= 1
            maxed_now = start.max_count != -1 and 1 >= start.max_count
            spawns = hit          # all hits become rows (seq consumed)
            if start.next_idx == -1:
                as_state = start.idx
                emit_at = jnp.where(hit, idx, -1) if min_now \
                    else jnp.full((B,), -1, jnp.int32)
                alive = jnp.zeros((B,), jnp.bool_) if maxed_now else hit
            else:
                as_state = start.next_idx if maxed_now else start.idx
                emit_at = jnp.full((B,), -1, jnp.int32)
                alive = hit
            minrel = jnp.where(hit, idx, BIG) if min_now \
                else jnp.full((B,), BIG, jnp.int32)
            n0 = jnp.where(hit, jnp.int32(1), 0)
        else:
            if start.next_idx == -1:
                # single-state pattern: every hit emits, no row persists
                spawns = jnp.zeros((B,), jnp.bool_)
                as_state = start.idx
                minrel = jnp.full((B,), BIG, jnp.int32)
                emit_at = jnp.where(hit, idx, -1)
                alive = jnp.zeros((B,), jnp.bool_)
            else:
                spawns = hit
                as_state = start.next_idx
                minrel = jnp.full((B,), BIG, jnp.int32)
                emit_at = jnp.full((B,), -1, jnp.int32)
                alive = hit
            n0 = jnp.where(hit, jnp.int32(1), 0)

        # own slot captures its event (identity gather)
        slot_bufs = []
        for j, spec in enumerate(self.slots):
            buf = pop["slots"][j]
            if j == start.slot:
                cols = tuple(
                    col.at[:, 0].set(jnp.where(hit, ev_cols[a],
                                               col[:, 0]))
                    for a, col in enumerate(buf["cols"]))
                nulls = tuple(
                    nl.at[:, 0].set(jnp.where(hit, ev_nulls[a],
                                              nl[:, 0]))
                    for a, nl in enumerate(buf["nulls"]))
                ts = buf["ts"].at[:, 0].set(jnp.where(hit, ev_ts,
                                                      buf["ts"][:, 0]))
                slot_bufs.append({"cols": cols, "nulls": nulls, "ts": ts,
                                  "n": n0})
            else:
                slot_bufs.append(buf)

        n_spawned = jnp.sum(spawns.astype(jnp.int64))
        # emit-only rows get post-spawn seqs (they sort after real spawns
        # at the same event, matching the scan engine's emit order)
        seq = jnp.where(spawns, next_seq + rank,
                        next_seq + n_spawned + idx.astype(jnp.int64))
        pop.update({
            "state": jnp.where(hit, jnp.int32(as_state), pop["state"]),
            "valid": alive,
            "last": jnp.where(hit, idx, pop["last"]),
            "born_rel": jnp.where(hit, idx, 0),
            "ts0": jnp.where(hit, ev_ts, pop["ts0"]),
            "has_ts0": hit,
            "minrel": minrel,
            "seq": seq,
            "emit_at": emit_at,
            "emit_n": jnp.where(emit_at >= 0, jnp.int32(1), 0),
            "slots": tuple(slot_bufs),
        })
        return pop, n_spawned


    # -- emission / table merge ------------------------------------------
    def _emission_order(self, key, seq):
        """Positions of the rows to emit, in (emit_at, seq) order, at most
        OUT of them: the emitting rows (key below NO_EMIT) in population
        order, up to OUT (past that they are lost and counted), then
        sorted, so no sort spans the whole table."""
        T = key.shape[0]
        at = _nth_true(key < NO_EMIT, self.OUT)
        keys = jnp.append(key, NO_EMIT)[at]
        seqs = jnp.append(seq, jnp.int64(0))[at]
        return jnp.minimum(at[_lexsort32(keys, seqs)], T - 1)

    def _collect_emissions(self, out, pops):
        """Write (emit_at, seq)-ordered emissions from the populations
        into the output buffer."""
        OUT = self.OUT
        sizes = [pop["state"].shape[0] for pop in pops]
        allkey = jnp.concatenate([
            jnp.where(pop["emit_at"] >= 0,
                      pop["emit_at"].astype(jnp.int64), NO_EMIT)
            for pop in pops])
        n_emit = jnp.sum((allkey < NO_EMIT).astype(jnp.int64))
        order = self._emission_order(
            allkey, jnp.concatenate([pop["seq"] for pop in pops]))
        # the emissions land at [n, n + n_emit) of the buffer, cut at OUT:
        # buffer slot i takes ordered row i - n (a contiguous write)
        n0 = out["n"]
        i = jnp.arange(OUT, dtype=jnp.int64)
        put = (i >= n0) & (i < n0 + n_emit)
        lost = jnp.maximum(n_emit - (OUT - n0), 0)

        def place(old, rows):
            shifted = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([jnp.zeros((OUT,), rows.dtype), rows]),
                (OUT - n0).astype(jnp.int32), OUT)
            return jnp.where(put, shifted, old)

        offs = np.cumsum([0] + sizes)

        def gather(arrays):
            """The K ordered rows of per-population arrays, with no
            concatenation of whole populations."""
            got = arrays[0][jnp.clip(order, 0, sizes[0] - 1)]
            for arr, o, P in zip(arrays[1:], offs[1:], sizes[1:]):
                got = jnp.where(order >= o, arr[jnp.clip(order - o, 0, P - 1)],
                                got)
            return got

        # a final counting slot nulls the copies past emit_n
        final_counting = {st.slot for st in self.states
                          if st.next_idx == -1 and st.is_counting}
        cols = list(out["cols"])
        nulls = list(out["nulls"])
        for j, spec in enumerate(self.slots):
            for a in range(len(spec.schema.types)):
                for c in range(spec.cap):
                    ci = self.col_index[(j, a, c)]
                    vs = [pop["slots"][j]["cols"][a][:, c] for pop in pops]
                    ns = [pop["slots"][j]["nulls"][a][:, c] for pop in pops]
                    if j in final_counting:
                        ns = [nl | (c >= pop["emit_n"])
                              for nl, pop in zip(ns, pops)]
                    cols[ci] = place(cols[ci], gather(vs))
                    nulls[ci] = place(nulls[ci], gather(ns))
        ts = place(out["ts"], gather([p["emit_ts"] for p in pops]))
        return {"cols": tuple(cols), "nulls": tuple(nulls), "ts": ts,
                "n": out["n"] + jnp.minimum(n_emit, OUT - out["n"]),
                "lost": out["lost"] + lost}

    def _fold_spawns(self, table, pop2, counter, sub_off: int):
        """Append surviving spawned rows into free table slots, lowest
        free slot first, in seq order; overflow counted. The survivors
        are gathered to the front, so the slots written ascend and are
        distinct: the scatters need no sort of their indices."""
        M = self.M
        B = pop2["state"].shape[0]
        want = pop2["valid"]
        n_want = jnp.sum(want.astype(jnp.int32))
        free = ~table["valid"]
        n_free = jnp.sum(free.astype(jnp.int32))
        lost = jnp.maximum(n_want - n_free, 0).astype(jnp.int64)
        r = jnp.arange(B, dtype=jnp.int32)
        src = jnp.minimum(_nth_true(want, B), B - 1)   # r-th survivor
        slot = _nth_true(free, B)                      # r-th free slot
        d = jnp.where(r < jnp.minimum(n_want, n_free), slot, M + r)

        def put(col, vals):
            return col.at[d].set(vals[src], mode="drop",
                                 indices_are_sorted=True,
                                 unique_indices=True)

        state = put(table["state"], pop2["state"])
        valid = put(table["valid"], jnp.ones((B,), jnp.bool_))
        born = put(table["born"],
                   counter + (sub_off + pop2["born_rel"]).astype(jnp.int64))
        seq = put(table["seq"], pop2["seq"])
        ts0 = put(table["ts0"], pop2["ts0"])
        has_ts0 = put(table["has_ts0"], pop2["has_ts0"])
        min_at = put(table["min_at"], jnp.where(
            pop2["minrel"] < BIG,
            counter + (sub_off + pop2["minrel"]).astype(jnp.int64),
            jnp.int64(-1)))
        deadline = put(table["deadline"], jnp.full((B,), POS_INF))
        slots = []
        for j in range(len(self.slots)):
            tb = table["slots"][j]
            pb = pop2["slots"][j]
            slots.append({
                "cols": tuple(put(tc, pc)
                              for tc, pc in zip(tb["cols"], pb["cols"])),
                "nulls": tuple(put(tn, pn)
                               for tn, pn in zip(tb["nulls"], pb["nulls"])),
                "ts": put(tb["ts"], pb["ts"]),
                "n": put(tb["n"], pb["n"]),
            })
        return {**table, "state": state, "valid": valid, "born": born,
                "seq": seq, "ts0": ts0, "has_ts0": has_ts0,
                "min_at": min_at, "deadline": deadline,
                "slots": tuple(slots),
                "overflow": table["overflow"] + lost}

    # -- the step --------------------------------------------------------
    def make_stream_step(self, stream_id: str):
        """(table, EventBatch, now) -> (table', match_batch). The step's
        parts run under the named scopes ``key_sort``, ``rounds``,
        ``spawn``, ``fold`` and ``prune`` (the runtime adds
        ``<query>/nfa``)."""
        rounds = [st for st in self.states
                  if st.stream_id == stream_id and st.idx in self._waiting]
        starts = [st for st in self.states
                  if st.always_armed and st.stream_id == stream_id]
        start = starts[0] if starts else None
        # a step whose rounds are all keyed needs no grid bound
        grid = any(self.keys.get(st.idx) is None for st in rounds)
        keyed = {}                   # signature -> (state, RoundKey)
        for st in rounds:
            rk = self.keys.get(st.idx)
            if rk is not None:
                keyed.setdefault(rk.signature, (st, rk))

        def live(table):
            return jnp.sum(table["valid"].astype(jnp.int64))

        def sub_step(table, out, ev, sub_off):
            (ev_ts, ev_kind, ev_valid, ev_cols, ev_nulls) = ev
            B = ev_ts.shape[0]
            counter = table["counter"]
            M = self.M

            with jax.named_scope("key_sort"):
                views = {sig: self._key_view(st, rk, ev, B)
                         for sig, (st, rk) in keyed.items()}

            # P1: the persistent table as a population. min<0:n> counting
            # states reach their minimum at birth — their rows answer the
            # next state without any absorbed event (min_at stays -1)
            min_prev = table["min_at"] >= 0
            for cs in self.states:
                if cs.is_counting and cs.min_count == 0:
                    min_prev = min_prev | (table["state"] == cs.idx)
            pop1 = {
                "state": table["state"],
                "valid": table["valid"],
                "last": jnp.full((M,), -1, jnp.int32),
                "ts0": table["ts0"],
                "has_ts0": table["has_ts0"],
                "min_prev": min_prev,
                "minrel": jnp.full((M,), BIG, jnp.int32),
                "seq": table["seq"],
                "emit_at": jnp.full((M,), -1, jnp.int32),
                "emit_n": jnp.zeros((M,), jnp.int32),
                "slots": table["slots"],
            }
            live_before = live(table)
            spawned = jnp.int64(0)
            if start is not None:
                with jax.named_scope("spawn"):
                    pop2, n_spawned = self._spawn_pop(
                        start, ev, B, table["next_seq"])
                    pop2 = {**pop2, "min_prev": jnp.zeros((B,), jnp.bool_)}
                    spawned = jnp.sum(pop2["valid"].astype(jnp.int64))
            with jax.named_scope("rounds"):
                if start is not None and rounds:
                    # the table's rows and the spawned ones advance
                    # alike, independently: one population, one program
                    both, cands, walk_rows, band_iters = \
                        self._advance_rounds(_concat_pops(pop1, pop2), ev,
                                             rounds, views, B)
                    pop1, pop2 = _split_pops(both, M, pop2["born_rel"])
                else:
                    pop1, cands, walk_rows, band_iters = \
                        self._advance_rounds(pop1, ev, rounds, views, B)
            pops = [pop1] if start is None else [pop1, pop2]
            completed = live_before + spawned - sum(
                jnp.sum(p["valid"].astype(jnp.int64)) for p in pops)

            with jax.named_scope("fold"):
                # emission timestamps (per-row gather of emit event ts)
                for pop in pops:
                    j = jnp.clip(pop["emit_at"], 0, B - 1)
                    pop["emit_ts"] = ev_ts[j]
                out = self._collect_emissions(out, pops)

            # within pruning at batch end: event time never goes back, so
            # a row whose first capture lies more than `within` before the
            # batch's latest event can never match again. A row born late
            # in a batch that spans more than `within` lives on.
            any_valid = jnp.any(ev_valid)
            tsmax = jnp.max(jnp.where(ev_valid, ev_ts, -POS_INF))

            def prune(pop):
                if self.within_ms is None:
                    return pop, jnp.int64(0)
                dead = pop["valid"] & pop["has_ts0"] & any_valid & \
                    (tsmax - pop["ts0"] > self.within_ms)
                return ({**pop, "valid": pop["valid"] & ~dead},
                        jnp.sum(dead.astype(jnp.int64)))

            with jax.named_scope("prune"):
                pop1, pruned = prune(pop1)

            # write P1 back into the table
            table = {
                **table,
                "state": pop1["state"],
                "valid": pop1["valid"],
                "ts0": pop1["ts0"],
                "has_ts0": pop1["has_ts0"],
                "min_at": jnp.where(
                    pop1["minrel"] < BIG,
                    counter + (sub_off + pop1["minrel"]).astype(jnp.int64),
                    table["min_at"]),
                "slots": pop1["slots"],
            }
            if start is not None:
                with jax.named_scope("prune"):
                    pop2, pruned2 = prune(pop2)
                pruned = pruned + pruned2
                with jax.named_scope("fold"):
                    table = self._fold_spawns(table, pop2, counter,
                                              sub_off)
                table = {**table,
                         "next_seq": table["next_seq"] + n_spawned}
            return {**table, "counter": counter + B,
                    "nfa_live_peak": jnp.maximum(table["nfa_live_peak"],
                                                 live(table)),
                    "nfa_spawned": table["nfa_spawned"] + spawned,
                    "nfa_completed": table["nfa_completed"] + completed,
                    "nfa_pruned": table["nfa_pruned"] + pruned,
                    "nfa_candidates": table["nfa_candidates"] + cands,
                    "nfa_walk_rows": table["nfa_walk_rows"] + walk_rows,
                    "nfa_band_iters": table["nfa_band_iters"] + band_iters,
                    }, out

        def step(table, batch: EventBatch, now):
            B = batch.capacity
            out = {
                "cols": tuple(jnp.zeros((self.OUT,), dtype=np_dtype(t))
                              for t in self.match_schema.types),
                "nulls": tuple(jnp.ones((self.OUT,), dtype=jnp.bool_)
                               for _ in self.match_schema.types),
                "ts": jnp.zeros((self.OUT,), dtype=jnp.int64),
                "n": jnp.int64(0),
                "lost": jnp.int64(0),
            }
            live_in = live(table)
            PB = min(self.PB if grid else self._keyed_sub_batch(), B)
            n_sub = (B + PB - 1) // PB

            if n_sub == 1:
                ev = (batch.ts, batch.kind, batch.valid,
                      tuple(batch.cols), tuple(batch.nulls))
                table, out = sub_step(table, out, ev, 0)
            else:
                def body(k, carry):
                    table, out = carry
                    o = k * PB
                    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, o, PB)
                    ev = (sl(batch.ts), sl(batch.kind), sl(batch.valid),
                          tuple(sl(c) for c in batch.cols),
                          tuple(sl(nl) for nl in batch.nulls))
                    return sub_step(table, out, ev, o)

                # B is a multiple of PB (bucket capacities are powers of
                # two >= PB here)
                table, out = jax.lax.fori_loop(
                    0, n_sub, lambda k, c: body(k, c), (table, out))

            match_batch = EventBatch(
                ts=out["ts"],
                cols=out["cols"],
                nulls=out["nulls"],
                kind=jnp.zeros((self.OUT,), jnp.int32),
                valid=jnp.arange(self.OUT) < out["n"],
            )
            events = jnp.sum((batch.valid & (batch.kind == CURRENT))
                             .astype(jnp.int64))
            table = {**table, "overflow": table["overflow"] + out["lost"],
                     "nfa_steps": table["nfa_steps"] + 1,
                     "nfa_events": table["nfa_events"] + events,
                     "nfa_live_in": table["nfa_live_in"] + live_in,
                     "nfa_live_out": table["nfa_live_out"] + live(table)}
            return table, match_batch

        return step
