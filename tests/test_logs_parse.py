"""Every ``logs/*.json`` must parse — whole-file JSON or JSONL.

The round artifacts under ``logs/`` feed tooling that ``json.load``s them
(scripts/project_multichip.py reads bench captures; future dashboards read
the autotune journal). Round 5 shipped two ``.json`` files with
``CENSUS``/``TIMES`` line prefixes that broke any such loader (ADVICE r5);
they are ``.log`` now, and this test keeps the extension honest."""

import glob
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parses(path: str) -> bool:
    with open(path) as f:
        text = f.read()
    try:
        json.loads(text)
        return True
    except ValueError:
        pass
    # JSONL: every non-empty line parses alone (the autotune decision
    # journals are line-delimited)
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        return False
    try:
        for ln in lines:
            json.loads(ln)
        return True
    except ValueError:
        return False


def test_every_logs_json_parses():
    paths = glob.glob(os.path.join(REPO, "logs", "**", "*.json"),
                      recursive=True)
    assert paths, "no logs/*.json found — glob root moved?"
    bad = [p for p in paths if not _parses(p)]
    assert not bad, f"unparseable .json artifacts: {bad}"
