"""The environment names the program reads are the rows of the table
"Environment" in docs/performance.md.

A new `PADDLE_TPU_*` / `PT_*` name needs a row there (what it selects,
who sets it), which is where a reviewer asks whether two callers need
it. The launcher's rendezvous names (`PADDLE_TRAINER_*`, `PMI_*`,
`OMPI_*`, `POD_IP`) are deployment settings and are not in the set.
"""

import ast
import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NAME = re.compile(r"(PADDLE_TPU|PT)_[A-Z0-9_]+")


def _names_read_by_the_program():
    """String literals that are exactly such a name, in modules that
    touch `os.environ`: direct reads, reads through an alias or a
    constant, and names looped over (`PADDLE_TPU_FLASH_BLOCK_Q/_K`).
    Prose that mentions a name is a longer literal and does not count."""
    names = set()
    for dirpath, _dirs, files in os.walk(os.path.join(_REPO, "paddle_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                src = f.read()
            if "environ" not in src:
                continue
            for node in ast.walk(ast.parse(src)):
                if (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)
                        and _NAME.fullmatch(node.value)):
                    names.add(node.value)
    return names


def _names_in_the_table():
    with open(os.path.join(_REPO, "docs", "performance.md")) as f:
        text = f.read()
    table = text.split("## Environment", 1)[1].split("\n## ", 1)[0]
    return set(re.findall(r"^\| `([A-Z0-9_]+)` \|", table, re.M))


def test_every_environment_name_read_has_a_row_and_every_row_a_reader():
    read, rows = _names_read_by_the_program(), _names_in_the_table()
    assert read == rows, (f"read but not in docs/performance.md: "
                          f"{sorted(read - rows)}; rows nothing reads: "
                          f"{sorted(rows - read)}")
