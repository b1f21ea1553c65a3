"""Replay the reference's test corpus.

Fixtures in this directory are machine-extracted from the reference's
TestNG cases (modules/siddhi-core/src/test/java/io/siddhi/core/query/
{pattern,sequence,window,join}/**) by tools/extract_ref_corpus.py: one
JSON per reference test class, 443 cases in all. The skipped remainder
are loop-driven or API-built tests, listed with reasons inside each
JSON. Each case replays the reference's exact app text, event data, and
inter-send sleeps under @app:playback with a virtual clock, then asserts
the reference's own expected rows/counts — the BASELINE.md "bit-equal
outputs on the pattern test suite" claim, case by case.

Each fixture is collected as its own test file (node id
``tests/ref_corpus/<stem>.json::<stem>.<name>``), so ``--dist loadfile``
hands each reference test class to a worker as one unit of work instead
of the whole corpus as one.

Queries using SiddhiQL features this framework rejects at compile time
xfail with the CompileError message, keeping the remaining gap inventory
visible in the test report instead of hidden.
"""
import json
import pathlib

import pytest

from siddhi_tpu import Event, QueryCallback, SiddhiManager, StreamCallback
from siddhi_tpu.lang.tokens import SiddhiParserException
from siddhi_tpu.ops.expr import CompileError

DIR = pathlib.Path(__file__).parent
T0 = 1_500_000_000_000

# Cases where this framework's output does not yet match the reference —
# the live parity worklist (each fix prunes lines). Listed cases still
# REPLAY every run; a mismatch xfails, an unexpected pass XPASSes so
# stale entries surface.
def _id_set(fname):
    p = DIR / fname
    if not p.exists():
        return frozenset()
    return frozenset(
        ln.strip().split("|")[0].strip()
        for ln in p.read_text().splitlines()
        if ln.strip() and not ln.startswith("#"))


KNOWN_FAILURES = _id_set("known_failures.txt")
# Cases this framework rejects at compile time, tracked explicitly: a
# CompileError on any case NOT in this list is a REGRESSION (it fails
# the run instead of silently joining the xfail bucket), and a listed
# case that now compiles surfaces as an xpass-style failure so the
# stale entry gets pruned.
COMPILE_GATED = _id_set("compile_gated.txt")


def fixture_cases(path):
    """(case id, case) for every case of one fixture JSON; the id is
    ``<stem>.<name>``, the form the .txt lists use."""
    for c in json.loads(path.read_text())["cases"]:
        yield f"{path.stem}.{c['name']}", c


def pytest_collect_file(file_path, parent):
    if file_path.suffix == ".json" and file_path.parent == DIR:
        return CorpusFile.from_parent(parent, path=file_path)
    return None


class CorpusFile(pytest.File):
    def collect(self):
        for cid, case in fixture_cases(self.path):
            item = CorpusCase.from_parent(self, name=cid, case=case)
            if cid in KNOWN_FAILURES:
                item.add_marker(pytest.mark.xfail(
                    reason="known output divergence (known_failures.txt)",
                    strict=False))
            yield item


class CorpusCase(pytest.Item):
    def __init__(self, *, case, **kw):
        super().__init__(**kw)
        self.case = case

    def runtest(self):
        replay(self.case, self.name)

    def repr_failure(self, excinfo):
        # report from this module's frames down, not pytest's own
        excinfo.traceback = excinfo.traceback.cut(path=__file__)
        return super().repr_failure(excinfo)

    def reportinfo(self):
        return self.path, None, self.name


def _rows_match(got, exp):
    if len(got) != len(exp):
        return False
    for g, e in zip(got, exp):
        if isinstance(e, float):
            if g != pytest.approx(e, rel=1e-5, abs=1e-6):
                return False
        elif g != e:
            return False
    return True


def _is_ordered_subset(got_rows, exp_rows):
    i = 0
    for g in got_rows:
        if i < len(exp_rows) and _rows_match(list(g), exp_rows[i]):
            i += 1
    return i == len(exp_rows)


def replay(case, cid):
    mgr = SiddhiManager()
    if case.get("expect_error"):
        # reference @Test(expectedExceptions=SiddhiAppCreationException):
        # app creation must be REJECTED
        with pytest.raises((CompileError, SiddhiParserException)):
            mgr.create_siddhi_app_runtime("@app:playback " + case["app"])
        return
    try:
        rt = mgr.create_siddhi_app_runtime("@app:playback " + case["app"])
    except CompileError as e:
        if cid in COMPILE_GATED:
            pytest.xfail(f"unsupported construct: {e}")
        raise AssertionError(
            f"COMPILE REGRESSION: case not in compile_gated.txt now "
            f"fails to compile: {e}") from e
    if cid in COMPILE_GATED:
        raise AssertionError(
            "STALE compile_gated.txt entry: case now compiles — run it "
            "and prune the entry")
    state = {"in": 0, "rm": 0, "in_rows": [], "rm_rows": []}

    def on_query(_ts, in_events, rm_events):
        if in_events:
            state["in"] += len(in_events)
            state["in_rows"] += [tuple(e.data) for e in in_events]
        if rm_events:
            state["rm"] += len(rm_events)
            state["rm_rows"] += [tuple(e.data) for e in rm_events]

    def on_stream(events):
        state["in"] += len(events)
        state["in_rows"] += [tuple(e.data) for e in events]

    targets = case["callbacks"] or list(rt.queries)
    q_targets = [t for t in targets if t in rt.queries]
    if q_targets:
        for t in q_targets:
            rt.add_callback(t, QueryCallback(fn=on_query))
    else:
        for t in targets:
            rt.add_callback(t, StreamCallback(fn=on_stream))
    rt.start()
    # the reference starts the runtime immediately before the first
    # action — anchor the virtual app-start clock at T0 so start-state
    # absent deadlines (partitionCreated) base correctly
    with rt.barrier:
        rt.on_ingest_ts(T0)

    clock = T0
    for act in case["actions"]:
        if act[0] == "send":
            _, sid, row = act
            rt.get_input_handler(sid).send(Event(clock, tuple(row)))
            clock += 1
        elif act[0] == "sleep":
            clock += act[1]
            with rt.barrier:
                rt.on_ingest_ts(clock)
        elif act[0] == "wait_in":
            # TestUtil.waitForInEvents: poll sleepTime ms per round,
            # stop when inEventCount == 1 or after retryCount rounds
            _, sleep_ms, retries = act
            for _ in range(retries):
                clock += sleep_ms
                with rt.barrier:
                    rt.on_ingest_ts(clock)
                if state["in"] == 1:
                    break
        elif act[0] == "wait_count":
            # SiddhiTestHelper.waitForEvents(sleep, expected, counter,
            # timeout): poll until the counter reaches `expected`
            _, sleep_ms, want, which, timeout_ms = act
            for _ in range(max(timeout_ms // max(sleep_ms, 1), 1)):
                if state["in" if which == "in" else "rm"] >= want:
                    break
                clock += sleep_ms
                with rt.barrier:
                    rt.on_ingest_ts(clock)
    rt.shutdown()

    if case["expected_in"] is not None:
        assert state["in"] == case["expected_in"], \
            f"in-events {state['in']} != {case['expected_in']} " \
            f"(rows={state['in_rows']})"
    if case["expected_removed"] is not None:
        assert state["rm"] == case["expected_removed"], \
            f"rm-events {state['rm']} != {case['expected_removed']} " \
            f"(rows={state['rm_rows']})"
    if case["event_arrived"] is not None:
        arrived = state["in"] > 0 or state["rm"] > 0
        assert arrived == case["event_arrived"]
    exp_rows = case["expected_in_rows"]
    if case["expected_in"] == 0 or case["event_arrived"] is False:
        # TestUtil.addQueryCallback row expectations assert INSIDE the
        # callback — with zero expected events they are unreachable
        exp_rows = None
    if exp_rows:
        got = state["in_rows"]
        if case["row_mode"] == "exact":
            assert len(got) == len(exp_rows) and all(
                _rows_match(list(g), e) for g, e in zip(got, exp_rows)), \
                f"rows {got} != {exp_rows}"
        else:
            assert _is_ordered_subset(got, exp_rows), \
                f"rows {got} missing expected {exp_rows}"
