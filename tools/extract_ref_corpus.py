"""Extract the reference pattern/sequence test corpus into JSON fixtures.

The reference's TestNG cases (modules/siddhi-core/src/test/java/io/siddhi/
core/query/{pattern,sequence}/**) all follow one idiom (e.g.
EveryPatternTestCase.java:48-99): build a SiddhiQL string, attach a
QueryCallback counting events and asserting row data, send Object[] rows
with Thread.sleep gaps, then assert final counts. This script parses that
idiom and emits data-driven fixtures replayed by
tests/ref_corpus/conftest.py under @app:playback with a virtual clock
(sleeps become clock advances), proving output parity case by case.

Run:  python tools/extract_ref_corpus.py   (writes tests/ref_corpus/*.json)
The fixtures are checked in; re-run only to refresh from the reference.
"""
from __future__ import annotations

import json
import pathlib
import re

REF = pathlib.Path("/root/reference/modules/siddhi-core/src/test/java/"
                   "io/siddhi/core/query")
OUT = pathlib.Path(__file__).resolve().parent.parent / "tests" / "ref_corpus"

FILES = [
    "pattern/EveryPatternTestCase.java",
    "pattern/ComplexPatternTestCase.java",
    "pattern/CountPatternTestCase.java",
    "pattern/LogicalPatternTestCase.java",
    "pattern/WithinPatternTestCase.java",
    "pattern/absent/AbsentPatternTestCase.java",
    "pattern/absent/AbsentWithEveryPatternTestCase.java",
    "pattern/absent/EveryAbsentPatternTestCase.java",
    "pattern/absent/LogicalAbsentPatternTestCase.java",
    "sequence/SequenceTestCase.java",
    "sequence/absent/AbsentSequenceTestCase.java",
    "sequence/absent/AbsentWithEverySequenceTestCase.java",
    "sequence/absent/EveryAbsentSequenceTestCase.java",
    "sequence/absent/LogicalAbsentSequenceTestCase.java",
    # window + join suites (same TestNG idiom; round-5 corpus extension)
    "window/LengthWindowTestCase.java",
    "window/LengthBatchWindowTestCase.java",
    "window/TimeWindowTestCase.java",
    "window/TimeBatchWindowTestCase.java",
    "window/TimeLengthWindowTestCase.java",
    "window/ExternalTimeWindowTestCase.java",
    "window/ExternalTimeBatchWindowTestCase.java",
    "window/SortWindowTestCase.java",
    "window/FrequentWindowTestCase.java",
    "window/LossyFrequentWindowTestCase.java",
    "window/CronWindowTestCase.java",
    "join/JoinTestCase.java",
    "join/OuterJoinTestCase.java",
]

STR_LIT = r'"((?:[^"\\]|\\.)*)"'


def _concat_literals(expr: str) -> str:
    """Java "a" + "b" + ... -> abab (ignores non-literal parts)."""
    return "".join(m.group(1) for m in re.finditer(STR_LIT, expr)) \
        .replace('\\"', '"').replace("\\n", "\n")


def _parse_value(tok: str):
    tok = tok.strip()
    if tok.startswith('"'):
        return tok[1:-1]
    if tok == "null":
        return None
    if tok in ("true", "false"):
        return tok == "true"
    m = re.fullmatch(r"([-+]?[0-9_]*\.?[0-9_]+(?:[eE][-+]?\d+)?)([fFlLdD]?)",
                     tok)
    if not m:
        raise ValueError(f"non-literal value: {tok!r}")
    num, suffix = m.groups()
    if suffix.lower() == "f" or suffix.lower() == "d" or "." in num \
            or "e" in num.lower():
        return float(num)
    return int(num)


def _split_args(s: str) -> list[str]:
    """Split a Java argument list at top-level commas."""
    out, depth, cur, in_str = [], 0, "", False
    i = 0
    while i < len(s):
        c = s[i]
        if in_str:
            cur += c
            if c == "\\":
                cur += s[i + 1]
                i += 1
            elif c == '"':
                in_str = False
        elif c == '"':
            in_str = True
            cur += c
        elif c in "({[":
            depth += 1
            cur += c
        elif c in ")}]":
            depth -= 1
            cur += c
        elif c == "," and depth == 0:
            out.append(cur)
            cur = ""
        else:
            cur += c
        i += 1
    if cur.strip():
        out.append(cur)
    return out


def extract_case(name: str, body: str, rel: str, line_no: int):
    reasons = []
    # disabled tests never run in the reference — their expectations are
    # not ground truth (e.g. LogicalAbsentPatternTestCase
    # testQueryAbsent48 `enabled = false`)
    if re.search(r"@Test\s*\([^)]*enabled\s*=\s*false", body):
        return None, "test disabled (enabled = false)"
    # validation tests: @Test(expectedExceptions = SiddhiAppCreation...)
    # expect app creation to FAIL — replayed as expect_error cases
    expect_error = bool(re.search(
        r"@Test\s*\(\s*expectedExceptions", body))
    # string variable definitions: String x = "" + "..." + "...";
    strvars = {}
    for m in re.finditer(
            r'String\s+(\w+)\s*=\s*((?:[^;"]|"(?:[^"\\]|\\.)*")*);', body):
        strvars[m.group(1)] = _concat_literals(m.group(2))
    # app text from createSiddhiAppRuntime(arg)
    m = re.search(r"createSiddhiAppRuntime\s*\(([^;]*)\)\s*;", body)
    if not m:
        return None, "no createSiddhiAppRuntime"
    arg = m.group(1)
    app = ""
    for tok in arg.split("+"):
        tok = tok.strip()
        if tok.startswith('"'):
            app += _concat_literals(tok)
        elif tok in strvars:
            app += strvars[tok]
        elif tok:
            return None, f"app arg not literal/var: {tok!r}"
    if "(app)" in arg or not app.strip():
        return None, "app built via API"

    # callbacks: count them; >1 query callback target is fine (we count all)
    cb_targets = re.findall(r'addCallback\s*\(\s*"(\w+)"', body)
    cb_targets += re.findall(
        r'TestUtil\.add(?:Query|Stream)Callback\s*\(\s*\w+\s*,\s*"(\w+)"',
        body)

    # TestUtil.addQueryCallback(rt, "q", new Object[]{...}, ...) carries
    # the expected rows as varargs, asserted per arrival in order
    # (TestUtil.java TestQueryCallback)
    testutil_rows = []
    for m in re.finditer(
            r"TestUtil\.add(?:Query|Stream)Callback\s*\(([^;]*)\)\s*;",
            body):
        for rm in re.finditer(r"new\s+Object\[\]\s*\{([^}]*)\}",
                              m.group(1)):
            try:
                testutil_rows.append(
                    [_parse_value(v) for v in _split_args(rm.group(1))])
            except ValueError:
                return None, "non-literal TestUtil expected row"

    # input handlers: var -> stream
    handlers = {}
    for m in re.finditer(
            r'(\w+)\s*=\s*\w+\.getInputHandler\s*\(\s*"(\w+)"\s*\)', body):
        handlers[m.group(1)] = m.group(2)

    # unsupported shapes
    if re.search(r"\bfor\s*\(", body):
        reasons.append("loop-driven sends")
    if ".persist()" in body or "restoreRevision" in body:
        reasons.append("persistence flow")
    if "setExtension" in body:
        reasons.append("custom extension")
    if re.search(r"\.send\s*\(\s*new\s+Event\b", body):
        reasons.append("Event[] sends")
    if reasons:
        return None, "; ".join(reasons)

    # actions in source order: sends, sleeps, and TestUtil poll-waits
    # (waitForInEvents(s, cb, r) sleeps s ms per poll, stopping when
    # inEventCount == 1 or after r polls — TestUtil.java:70-80; the
    # harness replays the same loop against the virtual clock)
    actions = []
    token_re = re.compile(
        r"(\w+)\.send\s*\(\s*new\s+Object\[\]\s*\{([^}]*)\}\s*\)\s*;"
        r"|Thread\.sleep\s*\(\s*(\d+)\s*\)"
        r"|TestUtil\.waitForInEvents\s*\(\s*(\d+)\s*,\s*\w+\s*,\s*(\d+)\s*\)"
        r"|SiddhiTestHelper\.waitForEvents\s*\(\s*(\d+)\s*,\s*(\d+)\s*,\s*"
        r"(inEventCount|removeEventCount)\b[^,]*,\s*(\d+)\s*\)")
    after_start = body[body.index(".start()"):] if ".start()" in body \
        else body
    # replay stops where the reference test starts asserting: sleeps after
    # the final assertion (or shutdown) must not advance the clock — for
    # recurring every-absent patterns they would inflate the fire count
    # (e.g. EveryAbsentPatternTestCase.java:75 sleeps 2 s AFTER shutdown)
    stop = len(after_start)
    for pat in (r"\bAssert(?:JUnit)?\s*\.\s*assert", r"\.shutdown\s*\(",
                r"\.throwAssertionErrors\s*\("):
        m = re.search(pat, after_start)
        if m:
            stop = min(stop, m.start())
    after_start = after_start[:stop]
    for m in token_re.finditer(after_start):
        if m.group(3):
            actions.append(["sleep", int(m.group(3))])
        elif m.group(4):
            actions.append(["wait_in", int(m.group(4)), int(m.group(5))])
        elif m.group(6):
            # SiddhiTestHelper.waitForEvents(sleep, expected, counter,
            # timeout): poll sleep ms per round until the counter reaches
            # `expected` or timeout elapses
            which = "in" if m.group(8) in ("inEventCount", "count") \
                else "rm"
            actions.append(["wait_count", int(m.group(6)),
                            int(m.group(7)), which, int(m.group(9))])
        else:
            var, vals = m.group(1), m.group(2)
            if var not in handlers:
                return None, f"send on unknown handler {var!r}"
            try:
                row = [_parse_value(v) for v in _split_args(vals)]
            except ValueError as e:
                return None, f"non-literal send: {e}"
            actions.append(["send", handlers[var], row])
    if expect_error:
        return {
            "name": name,
            "ref": f"{rel}:{line_no}",
            "app": app,
            "actions": [],
            "expect_error": True,
            "expected_in_rows": [], "expected_removed_rows": [],
            "expected_in": None, "expected_removed": None,
            "event_arrived": None, "row_mode": "exact", "callbacks": [],
        }, None
    if not any(a[0] == "send" for a in actions):
        return None, "no literal sends"

    # expected rows from assertArrayEquals(new Object[]{...}, inEvents[i]...)
    expected_in_rows = []
    expected_rm_rows = []
    for m in re.finditer(
            r"assertArrayEquals\s*\(\s*new\s+Object\[\]\s*\{([^}]*)\}\s*,\s*"
            r"(inEvents|removeEvents)\s*\[", body):
        try:
            row = [_parse_value(v) for v in _split_args(m.group(1))]
        except ValueError:
            return None, "non-literal expected row"
        (expected_in_rows if m.group(2) == "inEvents"
         else expected_rm_rows).append(row)

    def last_count(patterns):
        val = None
        for pat in patterns:
            for m in re.finditer(pat, body):
                val = int(m.group(1))
        return val

    n_in = last_count([
        r'assertEquals\s*\(\s*"Number of success events[^"]*"\s*,\s*(\d+)'
        r"\s*,\s*inEventCount",
        r"assertEquals\s*\(\s*inEventCount\s*,\s*(\d+)",
        r"assertEquals\s*\(\s*(\d+)\s*,\s*inEventCount",
        r'assertEquals\s*\(\s*"Number of success events[^"]*"\s*,\s*(\d+)'
        r"\s*,\s*\w+\.getInEventCount\(\)",
        r"assertEquals\s*\(\s*\w+\.getInEventCount\(\)\s*,\s*(\d+)",
        r"assertEquals\s*\(\s*(\d+)\s*,\s*inEventCount\.get\(\)",
        r"assertEquals\s*\(\s*inEventCount\.get\(\)\s*,\s*(\d+)",
        # NOTE: bare `count` counters are ambiguous (some tests count
        # callback INVOCATIONS, not events) — not extracted
    ])
    n_rm = last_count([
        r'assertEquals\s*\(\s*"Number of remove events[^"]*"\s*,\s*(\d+)'
        r"\s*,\s*removeEventCount",
        r"assertEquals\s*\(\s*removeEventCount\s*,\s*(\d+)",
        r"assertEquals\s*\(\s*(\d+)\s*,\s*removeEventCount",
        r'assertEquals\s*\(\s*"Number of remove events[^"]*"\s*,\s*(\d+)'
        r"\s*,\s*\w+\.getRemoveEventCount\(\)",
        r"assertEquals\s*\(\s*(\d+)\s*,\s*removeEventCount\.get\(\)",
        r"assertEquals\s*\(\s*removeEventCount\.get\(\)\s*,\s*(\d+)",
    ])
    arrived = None
    m = re.search(r'assertEquals\s*\(\s*"Event arrived"\s*,\s*(true|false)',
                  body)
    if m:
        arrived = m.group(1) == "true"
    m = re.search(r'assert(True|False)\s*\(\s*"Event (?:not )?arrived"\s*,'
                  r"\s*\w+\.isEventArrived\(\)", body)
    if m:
        arrived = m.group(1) == "True"
    m = re.search(r"assert(True|False)\s*\(\s*eventArrived\s*\)", body)
    if m:
        arrived = m.group(1) == "True"

    if testutil_rows and not expected_in_rows:
        expected_in_rows = testutil_rows

    if n_in is None and not expected_in_rows and arrived is None:
        return None, "no extractable assertions"

    # row_mode: 'exact' when the switch/sequence of asserted rows should
    # equal the full in-event stream; 'ordered_subset' when a single
    # assert covers repeated arrivals or only some rows are asserted
    row_mode = "exact" if (n_in is not None
                           and len(expected_in_rows) == n_in) \
        else "ordered_subset"

    return {
        "name": name,
        "ref": f"{rel}:{line_no}",
        "app": app,
        "actions": actions,
        "expected_in_rows": expected_in_rows,
        "expected_removed_rows": expected_rm_rows,
        "expected_in": n_in,
        "expected_removed": n_rm,
        "event_arrived": arrived,
        "row_mode": row_mode,
        "callbacks": sorted(set(cb_targets)),
    }, None


def extract_file(rel: str):
    src = (REF / rel).read_text()
    lines = src.splitlines()
    # split into @Test methods
    cases, skips = [], []
    idxs = [i for i, ln in enumerate(lines) if "@Test" in ln]
    for k, i in enumerate(idxs):
        end = idxs[k + 1] if k + 1 < len(idxs) else len(lines)
        block = "\n".join(lines[i:end])
        m = re.search(r"public\s+void\s+(\w+)\s*\(", block)
        if not m:
            continue
        name = m.group(1)
        case, skip = extract_case(name, block, rel, i + 1)
        if case:
            cases.append(case)
        else:
            skips.append({"name": name, "ref": f"{rel}:{i + 1}",
                          "reason": skip})
    return cases, skips


def main():
    OUT.mkdir(parents=True, exist_ok=True)
    total_c = total_s = 0
    for rel in FILES:
        cases, skips = extract_file(rel)
        stem = rel.replace("/", "_").replace(".java", "")
        (OUT / f"{stem}.json").write_text(json.dumps(
            {"source": rel, "cases": cases, "skipped": skips}, indent=1))
        total_c += len(cases)
        total_s += len(skips)
        print(f"{rel}: {len(cases)} extracted, {len(skips)} skipped")
    print(f"TOTAL: {total_c} cases, {total_s} skipped")


if __name__ == "__main__":
    main()
