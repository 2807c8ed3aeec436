"""Every command a document tells a reader to run names something in the
tree.

One case per document: each `python[3] <path>.py` and each
`python[3] -m <module>` in it must resolve to a file of this checkout
(or, for a module whose top-level package is not ours, to an installed
one). A PR that deletes a tool and leaves its advert fails here.
"""

import importlib.util
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

DOCUMENTS = [
    "README.md",
    *sorted(str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md")),
    "tools/ci_gate.sh",
    ".claude/skills/verify/SKILL.md",
]

_COMMAND = re.compile(
    r"\bpython3?\s+(?:-m\s+(?P<module>[A-Za-z_][\w.]*)|(?P<path>[\w./-]+\.py)\b)"
)


def _module_in_tree(dotted: str) -> bool:
    base = ROOT.joinpath(*dotted.split("."))
    return (
        base.with_suffix(".py").is_file()
        or (base / "__main__.py").is_file()
        or (base / "__init__.py").is_file()
    )


def _missing(text: str):
    out = []
    for m in _COMMAND.finditer(text):
        if m["path"]:
            ok = (ROOT / m["path"]).is_file()
        elif (ROOT / m["module"].split(".")[0]).exists():
            ok = _module_in_tree(m["module"])
        else:  # somebody else's package (pytest): it has to be installed
            ok = importlib.util.find_spec(m["module"].split(".")[0]) is not None
        if not ok:
            out.append(m[0])
    return out


def test_the_scan_sees_both_command_forms():
    text = "run `python3 no/such_tool.py x`, then python -m tools.no_such"
    assert _missing(text) == ["python3 no/such_tool.py", "python -m tools.no_such"]
    assert _missing("python -m emqx_tpu; python chip_smoke.py") == []


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documented_commands_exist(doc):
    text = (ROOT / doc).read_text()
    assert _missing(text) == [], doc
