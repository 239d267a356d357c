"""The closed list of `FLAXDIFF_*` environment names (ROADMAP D2).

Every name the program reads is documented in exactly one of two
tables, with what it selects and whether a chip run has measured it:
the kernel hatches in docs/KERNELS.md "Environment names", the rest in
README.md "Other environment names". One case per name: a hatch cannot
arrive unlisted, and a deleted one has to leave the documents.
"""
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
CODE = ("flaxdiff_tpu", "train.py", "chip_smoke.py")
TABLES = {"docs/KERNELS.md": "## Environment names",
          "README.md": "### Other environment names"}
# what the "chip number" cell may say: a PR's reading, that there is
# none yet, or that the name selects nothing a chip run could time
CHIP_CELL = re.compile(r"PR \d+|not measured|none: ")


def _names_read():
    """Each `FLAXDIFF_*` name that stands in the code as a string
    literal of its own (the argument of an environment read or the
    constant that one uses) -> the files that hold it."""
    out = {}
    for root in CODE:
        path = ROOT / root
        for f in [path] if path.is_file() else sorted(path.rglob("*.py")):
            for name in re.findall(r"""["'](FLAXDIFF_[A-Z0-9_]+)["']""",
                                   f.read_text(encoding="utf-8")):
                out.setdefault(name, []).append(str(f.relative_to(ROOT)))
    return out


def _names_documented():
    """name -> [(file, selects cell, chip cell)], from the rows of the
    two tables whose first cell is the name in backticks."""
    out = {}
    for rel, heading in TABLES.items():
        text = (ROOT / rel).read_text(encoding="utf-8")
        if heading not in text:     # every name of that table then fails
            continue
        section = text.split(heading, 1)[1].split("\n#", 1)[0]
        for line in section.splitlines():
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            m = re.fullmatch(r"`(FLAXDIFF_[A-Z0-9_]+)`", cells[0])
            if m and len(cells) >= 3:
                out.setdefault(m.group(1), []).append(
                    (rel, cells[1], cells[2]))
    return out


READ = _names_read()
DOCUMENTED = _names_documented()


@pytest.mark.parametrize("name", sorted(set(READ) | set(DOCUMENTED)))
def test_environment_name_is_read_and_documented_once(name):
    assert name in READ, \
        f"{name} is documented ({DOCUMENTED[name][0][0]}) and no code reads it"
    assert name in DOCUMENTED, \
        f"{name} is read by {READ[name]} and in neither table of {TABLES}"
    [(rel, selects, chip)] = DOCUMENTED[name]       # exactly one row
    assert len(selects) > 20, f"{rel}: say what {name} selects"
    assert CHIP_CELL.search(chip), \
        f"{rel}: say whether {name} has a chip number ({CHIP_CELL.pattern})"
