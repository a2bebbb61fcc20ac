"""The census of ``PINOT_TPU_*`` names: ``README.md``'s table under
"Environment settings" against what ``pinot_tpu/`` names, both ways.
ROADMAP D5 counts from the table.  A name that ends in ``_`` stands for
a family (``PINOT_TPU_TIER_COST_<NAME>``, a docstring's
``PINOT_TPU_SLO_*``) and is matched by its prefix."""
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"PINOT_TPU_[A-Z0-9_]+")
KINDS = re.compile(r"deployment setting|safety|unjudged fork: ROADMAP D\d+")


def code_names() -> set:
    found = set()
    for folder, _, files in os.walk(os.path.join(ROOT, "pinot_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    found.update(NAME.findall(f.read()))
    return found


def table_rows() -> dict:
    """name -> (default, what, kind), from the one table of the section."""
    with open(os.path.join(ROOT, "README.md")) as f:
        section = f.read().split("### Environment settings", 1)[1]
    rows = {}
    for line in section.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if line.startswith("| `PINOT_TPU_") and len(cells) == 4:
            rows[NAME.match(cells[0].strip("`")).group(0)] = tuple(cells[1:])
    return rows


def covered(name: str, names: set) -> bool:
    """``name`` is among ``names``, or one of the two is the family the other belongs to."""
    return any(n == name or (n.endswith("_") and name.startswith(n)) or (name.endswith("_") and n.startswith(name))
               for n in names)


def test_every_name_the_code_reads_is_in_the_table():
    rows = table_rows()
    assert len(rows) > 80
    missing = sorted(n for n in code_names() if not covered(n, set(rows)))
    assert not missing, f"add to README.md's table of environment settings: {missing}"


def test_every_name_of_the_table_is_read_by_code():
    code, rows = code_names(), table_rows()
    stale = sorted(n for n in rows if not covered(n, code))
    assert not stale, f"in README.md's table and read by nothing under pinot_tpu/: {stale}"
    bad = sorted(n for n, (default, what, kind) in rows.items() if not (default and what and KINDS.fullmatch(kind)))
    assert not bad, f"rows without a default, a line, or one of the three kinds: {bad}"
