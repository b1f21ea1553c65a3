"""Multi-chip execution model on the virtual 8-device CPU mesh
(conftest pins JAX_PLATFORMS=cpu with 8 host devices).

Covers SURVEY §2.6 beyond the partition block: non-partitioned group-by
keyed state AND NFA pattern pending state sharded over a
jax.sharding.Mesh with real cross-shard key routing (all-gather +
owner-hash mask), both asserted equal to a single-chip replay of the
union of all shard inputs. The steps under shard_map are the PLANNER's
own compiled steps (QueryRuntime._make_step /
PatternQueryRuntime._step_for_stream), not test doubles.
"""
import jax

import __graft_entry__ as graft


def test_dryrun_multichip_group_by_and_pattern():
    assert len(jax.devices()) == 8
    # bench=False: the equivalence sweep only — the measured scaling
    # arms do not fit the tier-1 budget and are
    # guarded by tests/test_bench_smoke.py::test_bench_multichip instead
    graft._dryrun_multichip_impl(8, bench=False)
