"""Tier-1 CI gate: the TPU-hygiene linter over the whole siddhi_tpu
package must report ZERO findings beyond the checked-in baseline
(tools/lint_baseline.json) — the pytest twin of `python tools/lint.py`.

A failure here means a new TPU antipattern crept in: either fix it,
suppress it inline with `# lint: disable=<rule>` + a justification, or
(last resort) re-baseline via
`python tools/lint.py --baseline tools/lint_baseline.json --update-baseline`.
"""
import ast
import collections
import io
import json
import os
import subprocess

from siddhi_tpu.analysis import lint_paths, lint_project
from siddhi_tpu.analysis.baseline import filter_new, load
from siddhi_tpu.analysis.cli import main as lint_main
from siddhi_tpu.analysis.linter import iter_python_files

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "siddhi_tpu")
BASELINE = os.path.join(REPO, "tools", "lint_baseline.json")


def test_package_lints_clean_vs_baseline():
    findings = lint_paths([PKG], root=REPO)
    fresh, _ = filter_new(findings, load(BASELINE))
    assert not fresh, "new TPU-hygiene findings:\n" + "\n".join(
        f.render() for f in fresh)


def test_cli_gate_exits_zero():
    out = io.StringIO()
    rc = lint_main([PKG, "--root", REPO, "--baseline", BASELINE], stdout=out)
    assert rc == 0, out.getvalue()


def test_cli_exits_nonzero_on_new_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\nX = jnp.zeros((2,))\n")
    out = io.StringIO()
    rc = lint_main([str(bad), "--root", str(tmp_path),
                    "--baseline", BASELINE], stdout=out)
    assert rc == 1
    assert "module-device-array" in out.getvalue()


def test_baseline_grandfathers_then_catches_growth(tmp_path):
    mod = tmp_path / "legacy.py"
    mod.write_text("import jax.numpy as jnp\nX = jnp.zeros((2,))\n")
    bl = tmp_path / "bl.json"
    out = io.StringIO()
    assert lint_main([str(mod), "--root", str(tmp_path), "--baseline",
                      str(bl), "--update-baseline"], stdout=out) == 0
    # grandfathered: gate passes
    assert lint_main([str(mod), "--root", str(tmp_path),
                      "--baseline", str(bl)], stdout=out) == 0
    # an N+1th instance of the same pattern is a NEW finding
    mod.write_text("import jax.numpy as jnp\nX = jnp.zeros((2,))\n"
                   "Y = jnp.zeros((2,))\n")
    assert lint_main([str(mod), "--root", str(tmp_path),
                      "--baseline", str(bl)], stdout=out) == 1


# ---------------------------------------------------------------------
# semantic whole-repo gate (call graph + lock discipline + donation)
# ---------------------------------------------------------------------


def test_semantic_repo_gate_clean_within_budget(monkeypatch):
    """The full semantic sweep — per-module rules PLUS lock-discipline,
    lock-order, use-after-donate and the stale-suppression audit — must
    be finding-free on the tree AND cheap enough to live in tier-1:
    every pass shares ONE parse of each module (ast.parse counted, not
    timed — re-parsing per pass is what made the sweep slow)."""
    parsed = collections.Counter()
    real_parse = ast.parse

    def counting_parse(source, filename="<unknown>", *a, **kw):
        parsed[filename] += 1
        return real_parse(source, filename, *a, **kw)

    monkeypatch.setattr(ast, "parse", counting_parse)
    findings = lint_project([PKG], root=REPO)
    monkeypatch.undo()
    fresh, _ = filter_new(findings, load(BASELINE))
    assert not fresh, "new semantic findings:\n" + "\n".join(
        f.render() for f in fresh)
    modules = {os.path.abspath(p) for p in iter_python_files([PKG])}
    assert set(parsed) == modules, set(parsed) ^ modules
    assert max(parsed.values()) == 1, [
        (f, n) for f, n in parsed.items() if n > 1]


def test_shipped_baseline_is_empty():
    """Every historical finding is fixed or carries an inline justified
    pragma — the baseline must not quietly re-grow."""
    assert load(BASELINE) == {}


def test_sarif_output_validates_against_schema(tmp_path):
    """--sarif emits SARIF 2.1.0: validated against the vendored schema
    subset (property names / required sets / enums match the OASIS
    schema), with rule metadata and clickable locations present."""
    import jsonschema

    fixture = os.path.join(REPO, "tests", "lint_fixtures",
                           "bad_use_after_donate.py")
    sarif_path = tmp_path / "out.sarif"
    out = io.StringIO()
    rc = lint_main([fixture, "--root", REPO, "--sarif", str(sarif_path)],
                   stdout=out)
    assert rc == 1, out.getvalue()

    doc = json.loads(sarif_path.read_text())
    schema = json.loads(open(os.path.join(
        REPO, "tests", "sarif_schema_2.1.0.json")).read())
    jsonschema.validate(doc, schema)

    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "siddhi-tpu-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "use-after-donate" in rule_ids
    res = [r for r in run["results"] if r["ruleId"] == "use-after-donate"]
    assert res and res[0]["level"] == "error"
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad_use_after_donate.py")
    assert loc["region"]["startLine"] >= 1


def _git(cwd, *args):
    subprocess.run(["git", "-C", str(cwd), *args], check=True,
                   capture_output=True)


def test_changed_mode_lints_only_modified_files(tmp_path):
    """--changed scopes the run to git-dirty/untracked files: a clean
    checkout exits 0 even when committed files carry findings; dirtying
    such a file surfaces its findings; the exit-code contract holds."""
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "ci@local")
    _git(tmp_path, "config", "user.name", "ci")
    (tmp_path / "clean.py").write_text("x = 1\n")
    legacy = tmp_path / "legacy.py"
    legacy.write_text("import jax.numpy as jnp\nX = jnp.zeros((2,))\n")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")

    out = io.StringIO()
    assert lint_main(["--changed", "--root", str(tmp_path)],
                     stdout=out) == 0
    assert "nothing to lint" in out.getvalue()

    legacy.write_text(legacy.read_text() + "Y = jnp.ones((3,))\n")
    out = io.StringIO()
    rc = lint_main(["--changed", "--root", str(tmp_path)], stdout=out)
    assert rc == 1
    assert "module-device-array" in out.getvalue()

    untracked = tmp_path / "fresh.py"
    untracked.write_text("import jax.numpy as jnp\nZ = jnp.zeros((1,))\n")
    out = io.StringIO()
    rc = lint_main(["--changed", "--root", str(tmp_path)], stdout=out)
    assert rc == 1
    assert "fresh.py" in out.getvalue()


def test_changed_mode_follows_renames(tmp_path):
    """A `git mv` + edit must lint the file at its NEW path: the old
    ``--name-only`` diff reported only the old (now-nonexistent) path
    for an R entry, silently dropping renamed files from the changed
    set."""
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "ci@local")
    _git(tmp_path, "config", "user.name", "ci")
    old = tmp_path / "module_a.py"
    old.write_text("import jax.numpy as jnp\n\n\ndef f():\n"
                   "    return jnp.zeros((2,))\n")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")

    _git(tmp_path, "mv", "module_a.py", "module_b.py")
    moved = tmp_path / "module_b.py"
    # a small edit keeps git's similarity detection classifying the
    # change as a rename (R9x) while introducing a fresh finding
    moved.write_text(moved.read_text() + "X = jnp.zeros((4,))\n")
    out = io.StringIO()
    rc = lint_main(["--changed", "--root", str(tmp_path)], stdout=out)
    assert rc == 1, out.getvalue()
    assert "module_b.py" in out.getvalue()
    assert "module_a.py" not in out.getvalue(), \
        "the pre-rename path must not be linted (it no longer exists)"
