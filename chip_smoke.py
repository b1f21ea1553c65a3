"""Chip smoke: the SiddhiQL runtime's main path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases window, seq5, pool
    python chip_smoke.py --chips 4   # four chips: partitioned app on a
                                     # 4-device mesh vs the same app on one

Everything runs in THIS process (a chip belongs to one process; no child
ever touches it) through the entry points a user calls: SiddhiManager ->
create_siddhi_app_runtime -> start() -> warmup() (core/compile.py AOT) ->
InputHandler.send_arrays -> jitted steps -> StreamCallback, and
TenantPool.add_tenant -> send -> pump() for the pool. Every phase compares
every emitted row with a plain numpy / Python reference built from
``--seed`` and fails on any mismatch, warm-up error or overflow, and on
any program compiled after warmup().

Each phase prints one JSON line (compile ms, cache hits/misses, warm-up
errors, events/s, peak device bytes, plan hash); the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU visible the script exits 2 and prints no result. No cost
table is present in any phase: the run-state directory
(``SIDDHI_TPU_STATE_DIR``, obs/costmodel.py) points at an empty temporary
directory that is removed on exit.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

TS0 = 1_700_000_000_000

# the flagship shape (filter -> 1 s sliding window -> group-by sum), with
# its window and group-key bounds raised through @cap so 1,024 symbols and
# a window of ~95k live rows fit without overflow
WINDOW_APP = """
@app:playback
define stream StockStream (symbol string, price float, volume long);
@info(name = 'q') @cap(window.size='{cap}', group.keys='{keys}')
from StockStream[price > 10.0]#window.time(1 sec)
select symbol, sum(volume) as total
group by symbol
insert into Out;
"""

# bench.py SEQ5_APP, the north-star 5-state chain
SEQ5_APP = """
@app:playback
define stream T (sym string, stage int, v int);
@info(name = 'q')
from every e1=T[stage == 1] -> e2=T[stage == 2 and sym == e1.sym]
  -> e3=T[stage == 3 and sym == e1.sym]
  -> e4=T[stage == 4 and sym == e1.sym]
  -> e5=T[stage == 5 and sym == e1.sym]
within 60 sec
select e1.sym as sym, e1.v as v1, e5.v as v5
insert into Out;
"""

POOL_TEMPLATE = """
define stream In (v double, k long);
@info(name='q')
from In[v > ${lo:double} and k < ${kmax:long}]
select v, k
insert into Out;
"""

# key-partitioned window sum: the [K] slot axis shards over the mesh
PARTITION_APP = """
@app:playback
define stream StockStream (symbol string, price float, volume long);
@slots('{slots}')
partition with (symbol of StockStream) begin
  @info(name = 'pq') @cap(window.size='{cap}')
  from StockStream[price > 10.0]#window.time(1 sec)
  select symbol, sum(volume) as total
  insert into Out;
end;
"""


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# -- device measurements -----------------------------------------------------

def peak_bytes(devices=None) -> list:
    """peak_bytes_in_use per device (None where the backend has no
    memory stats, e.g. the CPU)."""
    import jax
    out = []
    for d in devices or jax.devices()[:1]:
        st = d.memory_stats()
        out.append(None if st is None else int(st["peak_bytes_in_use"]))
    return out


def dispatch_us(n: int = 200) -> float:
    """Median host time to dispatch one tiny jitted program (enqueue
    only — the block comes after the timed loop)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: a + 1)
    x = jnp.zeros((1024,), jnp.int32)
    jax.block_until_ready(f(x))
    ts = []
    y = x
    for _ in range(n):
        t0 = time.perf_counter()
        y = f(y)
        ts.append(time.perf_counter() - t0)
    jax.block_until_ready(y)
    return statistics.median(ts) * 1e6


def compile_lookups() -> int:
    """Persistent-cache lookups so far in this process: one per program
    compiled (hit or miss). Its growth across a timed run counts the
    programs that warmup() did not cover."""
    from siddhi_tpu.core.compile import cache_counts
    c = cache_counts()
    return c["hits"] + c["misses"]


def warm_fields(wu: dict) -> dict:
    errors = wu.get("errors") or []
    check(not errors, f"warm-up errors: {errors}")
    return {"compile_ms": wu["compile_ms"], "programs": wu["programs"],
            "cache_hits": wu["cache_hits"],
            "cache_misses": wu["cache_misses"],
            "warmup_errors": len(errors)}


def sym_names(n_syms: int) -> list:
    return [f"S{i:04d}" for i in range(n_syms)]


def sym_codes(names) -> np.ndarray:
    from siddhi_tpu.core.types import GLOBAL_STRINGS
    return np.array([GLOBAL_STRINGS.encode(s) for s in names], np.int32)


# -- phase window ------------------------------------------------------------

def window_data(n: int, n_syms: int, per_ms: int, seed: int):
    rng = np.random.default_rng(seed)
    sym_idx = rng.integers(0, n_syms, n)
    price = rng.uniform(0, 200, n).astype(np.float32)
    volume = rng.integers(1, 1000, n, dtype=np.int64)
    ts = TS0 + np.arange(n, dtype=np.int64) // per_ms
    return ts, sym_idx, price, volume


def window_reference(ts, sym_idx, price, volume, names, span_ms=1000):
    """Per passing row, in row order: (symbol, sum of the volumes of the
    same symbol's passing rows with ts in (ts_i - span, ts_i])."""
    keep = np.flatnonzero(price > np.float32(10.0))
    totals = np.empty(len(keep), np.int64)
    ks = sym_idx[keep]
    order = np.argsort(ks, kind="stable")
    bounds = np.flatnonzero(np.diff(ks[order])) + 1
    for grp in np.split(order, bounds):
        rows = keep[grp]
        t = ts[rows]
        cs = np.concatenate([[0], np.cumsum(volume[rows])])
        lo = np.searchsorted(t, t - span_ms, side="right")
        totals[grp] = cs[1:] - cs[lo]
    return [(names[s], int(v)) for s, v in zip(ks, totals)]


def phase_window(n=1 << 20, chunk=1 << 16, n_syms=1024, per_ms=100,
                 cap=131072, keys=4096, seed=0) -> dict:
    from siddhi_tpu import SiddhiManager, StreamCallback
    ts, sym_idx, price, volume = window_data(n, n_syms, per_ms, seed)
    names = sym_names(n_syms)
    sym = sym_codes(names)[sym_idx]
    live = int(np.sum(price[ts > ts[-1] - 1000] > np.float32(10.0)))
    check(n < 1 << 20 or live >= 65536,
          f"a full-size window must hold >= 65,536 live rows, not {live}")
    rt = SiddhiManager().create_siddhi_app_runtime(
        WINDOW_APP.format(cap=cap, keys=keys))
    got = []
    rt.add_callback("Out", StreamCallback(
        fn=lambda evs: got.extend(tuple(e.data) for e in evs)))
    rt.start()
    sample = (ts[:chunk], [sym[:chunk], price[:chunk], volume[:chunk]])
    wu = rt.warmup(buckets=[chunk], samples={"StockStream": sample})
    h = rt.get_input_handler("StockStream")
    c0 = compile_lookups()
    t0 = time.perf_counter()
    for i in range(0, n, chunk):
        h.send_arrays(ts[i:i + chunk],
                      [sym[i:i + chunk], price[i:i + chunk],
                       volume[i:i + chunk]])
    dt = time.perf_counter() - t0
    lazy = compile_lookups() - c0
    stats = rt.queries["q"].stats()
    plan = rt.plan_hash()
    rt.shutdown()
    check(stats["overflow"] == 0, f"window overflow {stats['overflow']}")
    check(lazy == 0, f"{lazy} window program(s) compiled after warmup")
    want = window_reference(ts, sym_idx, price, volume, names)
    check(len(got) == len(want), f"rows {len(got)} != {len(want)}")
    bad = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
               None)
    check(bad is None, f"row {bad}: {got[bad] if bad is not None else ''}"
          f" != {want[bad] if bad is not None else ''}")
    return {"phase": "window", "events": n, "chunk": chunk,
            "symbols": n_syms, "window_capacity": cap,
            "window_live_rows_at_end": live, "group_key_capacity": keys,
            "rows_checked": len(want), "overflow": stats["overflow"],
            "plan_hash": plan, **warm_fields(wu),
            "compiles_after_warmup": lazy, "events_per_s": n / dt}


# -- phase seq5 --------------------------------------------------------------

def seq5_data(n: int, n_syms: int, ms_per_event: int, seed: int):
    rng = np.random.default_rng(seed)
    sym_idx = rng.integers(0, n_syms, n)
    stage = rng.integers(1, 6, n).astype(np.int32)
    v = rng.integers(0, 1000, n).astype(np.int32)
    ts = TS0 + ms_per_event * np.arange(n, dtype=np.int64)
    return ts, sym_idx, stage, v


def seq5_reference(ts, sym_idx, stage, v, names, within_ms=60_000):
    """`every e1 -> e2 -> e3 -> e4 -> e5` on one symbol, within 60 s of
    e1: every stage-1 event opens a partial match; each stage-k event
    advances every partial of its symbol waiting at stage k; a partial
    older than `within` is dropped. Matches in completion order, ties in
    the order their partials were opened."""
    waiting = collections.defaultdict(list)   # (sym, stage) -> [(t1, v1)]
    out = []
    ts_l, sym_l, st_l, v_l = (ts.tolist(), sym_idx.tolist(),
                              stage.tolist(), v.tolist())
    for t, s, k, val in zip(ts_l, sym_l, st_l, v_l):
        if k > 1:
            parts = waiting.pop((s, k), None)
            if parts:
                i = bisect.bisect_left(parts, (t - within_ms, -1))
                alive = parts[i:]
                if k == 5:
                    out.extend((names[s], v1, val) for _, v1 in alive)
                elif alive:
                    waiting[(s, k + 1)].extend(alive)
                    waiting[(s, k + 1)].sort()
        if k == 1:
            waiting[(s, 2)].append((t, val))
    return out


def phase_seq5(n=1 << 20, chunk=1 << 16, n_syms=1024, ms_per_event=4,
               seed=0) -> dict:
    from siddhi_tpu import SiddhiManager, StreamCallback
    ts, sym_idx, stage, v = seq5_data(n, n_syms, ms_per_event, seed)
    names = sym_names(n_syms)
    sym = sym_codes(names)[sym_idx]
    rt = SiddhiManager().create_siddhi_app_runtime(SEQ5_APP)
    got = []
    rt.add_callback("Out", StreamCallback(
        fn=lambda evs: got.extend(tuple(e.data) for e in evs)))
    rt.start()
    sample = (ts[:chunk], [sym[:chunk], stage[:chunk], v[:chunk]])
    wu = rt.warmup(buckets=[chunk], samples={"T": sample})
    h = rt.get_input_handler("T")
    c0 = compile_lookups()
    t0 = time.perf_counter()
    for i in range(0, n, chunk):
        h.send_arrays(ts[i:i + chunk],
                      [sym[i:i + chunk], stage[i:i + chunk],
                       v[i:i + chunk]])
    dt = time.perf_counter() - t0
    lazy = compile_lookups() - c0
    stats = rt.queries["q"].stats()
    plan = rt.plan_hash()
    rt.shutdown()
    check(stats["overflow"] == 0, f"seq5 overflow {stats['overflow']}")
    check(lazy == 0, f"{lazy} seq5 program(s) compiled after warmup")
    want = seq5_reference(ts, sym_idx, stage, v, names)
    check(len(want) > 0, "seq5 reference found no match")
    check(collections.Counter(got) == collections.Counter(want),
          f"seq5 matches differ: {len(got)} got vs {len(want)} expected")
    return {"phase": "seq5", "events": n, "chunk": chunk,
            "symbols": n_syms, "matches": len(want),
            "overflow": stats["overflow"], "plan_hash": plan,
            **warm_fields(wu), "compiles_after_warmup": lazy,
            "events_per_s": n / dt}


# -- phase pool --------------------------------------------------------------

def phase_pool(tenants=64, rows=16384, batch_max=1024, seed=0) -> dict:
    from siddhi_tpu import SiddhiManager, Template, TenantPool
    rng = np.random.default_rng(seed)
    pool = TenantPool(Template(POOL_TEMPLATE), manager=SiddhiManager(),
                      slots=tenants, max_tenants=tenants,
                      batch_max=batch_max)
    got, want = {}, {}
    sample = None
    for t in range(tenants):
        tid = f"t{t:02d}"
        lo, kmax = float(t), int(1000 + 100 * t)
        pool.add_tenant(tid, {"lo": lo, "kmax": kmax})
        got[tid] = []
        pool.add_callback(tid, lambda evs, g=got[tid]: g.extend(
            (e.timestamp, tuple(e.data)) for e in evs))
        ts = TS0 + np.arange(rows, dtype=np.int64)
        v = rng.uniform(0, 100, rows)
        k = rng.integers(0, 8000, rows, dtype=np.int64)
        m = (v > lo) & (k < kmax)
        want[tid] = [(int(a), (float(b), int(c)))
                     for a, b, c in zip(ts[m], v[m], k[m])]
        pool.send(tid, ts, [v, k])
        sample = sample or (ts[:batch_max], [v[:batch_max], k[:batch_max]])
    wu = pool.warmup(samples={"In": sample})
    c0 = compile_lookups()
    t0 = time.perf_counter()
    dispatched = 0
    rounds = 0
    while True:
        r = pool.pump()
        if not r:
            break
        dispatched += r
        rounds += 1
    dt = time.perf_counter() - t0
    lazy = compile_lookups() - c0
    plan = pool.plan_hash()
    stats = pool.statistics()
    pool.shutdown()
    check(dispatched == tenants * rows,
          f"pool dispatched {dispatched} of {tenants * rows} rows")
    check(lazy == 0, f"{lazy} pool program(s) compiled after warmup")
    # timestamps and LONG columns must be bit-equal; the DOUBLE column
    # is held to a relative bound and its largest error is reported
    # (the TPU has no native float64)
    max_rel = 0.0
    for tid in want:
        g, w = got[tid], want[tid]
        check([(t, d[1]) for t, d in g] == [(t, d[1]) for t, d in w],
              f"tenant {tid}: rows differ ({len(g)} vs {len(w)})")
        gv = np.array([d[0] for _, d in g], np.float64)
        wv = np.array([d[0] for _, d in w], np.float64)
        if len(wv):
            rel = np.abs(gv - wv) / np.maximum(np.abs(wv), 1e-300)
            max_rel = max(max_rel, float(rel.max()))
    check(max_rel <= 1e-9, f"pool DOUBLE column off by {max_rel} rel")
    packed = stats.get("packed_ingest", {})
    return {"phase": "pool", "tenants": tenants, "rows_per_tenant": rows,
            "rounds": rounds, "rows_checked": sum(map(len, want.values())),
            "double_max_rel_err": max_rel,
            "transfers_per_round": packed.get("transfers_per_round"),
            "plan_hash": plan, **warm_fields(wu),
            "compiles_after_warmup": lazy,
            "events_per_s": dispatched / dt}


# -- phase partition (four chips) --------------------------------------------

def phase_partition(n_devices=4, n=1 << 17, chunk=8192, n_syms=16,
                    per_ms=16, cap=4096, seed=0) -> dict:
    """The partitioned window app on an n-device mesh and on one device:
    outputs must be bit-equal; per-device peak bytes, read after the
    mesh arm and before the one-device arm, show where the sharded slot
    axis landed."""
    import jax
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.parallel.sharding import build_mesh
    ts, sym_idx, price, volume = window_data(n, n_syms, per_ms, seed)
    names = sym_names(n_syms)
    sym = sym_codes(names)[sym_idx]
    app = PARTITION_APP.format(slots=n_syms, cap=cap)

    def drive(mesh):
        rt = SiddhiManager().create_siddhi_app_runtime(
            app, partition_mesh=mesh)
        got = []
        rt.add_callback("Out", StreamCallback(
            fn=lambda evs: got.extend((e.timestamp, tuple(e.data))
                                      for e in evs)))
        rt.start()
        wu = rt.warmup(buckets=[chunk])
        h = rt.get_input_handler("StockStream")
        c0 = compile_lookups()
        t0 = time.perf_counter()
        for i in range(0, n, chunk):
            h.send_arrays(ts[i:i + chunk],
                          [sym[i:i + chunk], price[i:i + chunk],
                           volume[i:i + chunk]])
        dt = time.perf_counter() - t0
        lazy = compile_lookups() - c0
        blk = next(iter(rt.partitions.values()))
        ovf = blk.overflow_total()
        plan = rt.plan_hash()
        rt.shutdown()
        check(ovf == 0, f"partition overflow {ovf}")
        check(lazy == 0, f"{lazy} partition program(s) compiled after "
              "warmup")
        return got, {"plan_hash": plan, **warm_fields(wu),
                     "compiles_after_warmup": lazy,
                     "events_per_s": n / dt}

    mesh = build_mesh(n_devices, axis="keys")
    got_mesh, info_mesh = drive(mesh)
    info_mesh["peak_bytes_per_device"] = peak_bytes(
        jax.devices()[:n_devices])
    got_one, info_one = drive(None)
    check(len(got_one) > 0, "partitioned app emitted nothing")
    check(got_mesh == got_one,
          f"mesh output differs from one device ({len(got_mesh)} vs "
          f"{len(got_one)} rows)")
    want = window_reference(ts, sym_idx, price, volume, names)
    check(sorted(d for _, d in got_one) == sorted(want),
          "partitioned output differs from the numpy reference")
    return {"phase": "partition", "n_devices": n_devices, "events": n,
            "chunk": chunk, "symbols": n_syms, "rows_checked": len(want),
            "mesh": info_mesh, "one_device": info_one,
            "peak_bytes_per_device": peak_bytes(jax.devices()[:n_devices])}


# -- driver ------------------------------------------------------------------

def run(chips: int, seed: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{dev['platform']!r}); nothing was run", file=sys.stderr)
        raise SystemExit(2)
    import siddhi_tpu  # noqa: F401 — x64 before any array is built
    emit({"device": dev})
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} asked for {chips} devices, "
                           f"{len(devs)} visible")
    before = dispatch_us()
    jax.device_get(jax.numpy.zeros((8,), jax.numpy.int32))
    after = dispatch_us()
    emit({"dispatch_us_before_first_readback": before,
          "dispatch_us_after_first_readback": after})
    phases = ([lambda: phase_partition(n_devices=chips, seed=seed)]
              if chips > 1 else
              [lambda: phase_window(seed=seed),
               lambda: phase_seq5(seed=seed),
               lambda: phase_pool(seed=seed)])
    for ph in phases:
        res = ph()
        if "peak_bytes_per_device" not in res:
            res["peak_bytes"] = peak_bytes()[0]
        res["dispatch_us"] = dispatch_us()
        emit(res)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the partitioned mesh-vs-one-device "
                         "phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # libtpu would otherwise log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    state = tempfile.mkdtemp(prefix="siddhi_smoke_state_")
    os.environ["SIDDHI_TPU_STATE_DIR"] = state
    try:
        dev = run(args.chips, args.seed)
    finally:
        shutil.rmtree(state, ignore_errors=True)
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
