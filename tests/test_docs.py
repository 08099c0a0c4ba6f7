"""The documents are held to the code where a test can hold them."""

import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_operations_lists_every_switch():
    """Every ``ARROYO_*`` name the program reads has a row in one of the
    tables of ``docs/operations.md``, and no table keeps a row for a
    switch that is gone."""
    in_code = set()
    for path in (ROOT / "arroyo_tpu").rglob("*"):
        if path.suffix in (".py", ".proto", ".sh"):
            in_code |= set(re.findall(r"ARROYO_[A-Z0-9_]+",
                                      path.read_text(errors="replace")))
    in_tables = set()
    for line in (ROOT / "docs" / "operations.md").read_text().splitlines():
        first = re.match(r"\|\s*`(ARROYO_[A-Z0-9_]+)`\s*\|", line)
        if first:
            in_tables.add(first.group(1))
    assert in_code - in_tables == set(), "switches without a row"
    assert in_tables - in_code == set(), "rows without a switch"
