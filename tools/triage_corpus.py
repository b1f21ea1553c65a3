"""Categorize ref-corpus outcomes: pass / parse-error / compile-error /
engine divergence (with counts). Dev tool for burning down
tests/ref_corpus/known_failures.txt."""
import collections
import json
import os
import pathlib
import sys

# the corpus runs on the CPU, like the tests (set before jax loads)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "tests" / "ref_corpus"))

import conftest as corpus  # noqa: E402
from siddhi_tpu.lang.tokens import SiddhiParserException  # noqa: E402
from siddhi_tpu.ops.expr import CompileError  # noqa: E402
from _pytest.outcomes import XFailed  # noqa: E402


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    out = collections.defaultdict(list)
    cases = [c for f in sorted(corpus.DIR.glob("*.json"))
             for c in corpus.fixture_cases(f)]
    for cid, case in cases:
        if only and only not in cid:
            continue
        print(f"... {cid}", file=sys.stderr, flush=True)
        try:
            corpus.replay(case, cid)
            out["pass"].append(cid)
        except XFailed as e:
            out["compile"].append((cid, str(e)[:90]))
        except SiddhiParserException as e:
            out["parse"].append((cid, str(e)[:90]))
        except CompileError as e:
            out["compile"].append((cid, str(e)[:90]))
        except AssertionError as e:
            out["diverge"].append((cid, str(e).split("\n")[0][:110]))
        except BaseException as e:  # noqa: BLE001 — incl. pytest Failed
            if e.__class__.__name__ in ("KeyboardInterrupt", "SystemExit"):
                raise
            out["crash"].append((cid, f"{type(e).__name__}: {e}"[:110]))
    for k in ("pass", "parse", "compile", "diverge", "crash"):
        print(f"== {k}: {len(out[k])}")
        if k != "pass":
            for item in out[k]:
                print("  ", item[0], "|", item[1])
    json_path = pathlib.Path("/tmp/triage.json")
    json_path.write_text(json.dumps(
        {k: [list(i) if isinstance(i, tuple) else i for i in v]
         for k, v in out.items()}, indent=1))
    print("wrote", json_path)


if __name__ == "__main__":
    main()
