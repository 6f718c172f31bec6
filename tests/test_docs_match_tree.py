"""The documents name only what the tree holds: every ``python <path>.py``
and ``python -m dsml_tpu...`` / ``-m benchmarks...`` a document tells a
reader to run resolves to a file, and every ``DSML_*`` name it gives is
read by the program: an ``os.environ`` / ``os.getenv`` / ``env_<type>`` read
in code, not a mention in a docstring or a comment. A harness, a CLI or
a knob that is deleted takes its mentions with it, or this fails.
"""

from __future__ import annotations

import ast
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md", "PARITY.md", "PERF.md"]
    + sorted(os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
    + [os.path.join(".claude", "skills", "verify", "SKILL.md")]
)
# ROADMAP.md keeps the removed by name (struck items, "Recent") and the
# driver's own tier-1 line, so only the names of its knobs are held
ENV_ONLY_DOCUMENTS = ["ROADMAP.md"]

# where a DSML_* name has to be read for a document to be allowed to give it
_PROGRAM = ("dsml_tpu", "benchmarks", "examples", "scripts", "chip_smoke.py")

_SCRIPT = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*([\w./-]+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*-m\s+((?:dsml_tpu|benchmarks)[\w.]*)")
_ENV = re.compile(r"\bDSML_[A-Z0-9_]*[A-Z0-9]")
_ENV_HELPER = re.compile(r"_?env_(int|float|flag)")  # utils' typed readers


def _read(relpath: str) -> str:
    with open(os.path.join(REPO, relpath), encoding="utf-8") as f:
        # a command wrapped over two lines of prose is still one command
        return re.sub(r"\s*\n\s*", " ", f.read())


def _env_reads(source: str) -> set:
    """``DSML_*`` names a module reads from the environment: the key of an
    ``os.environ.get`` / ``os.getenv`` / ``env_<type>`` call or of an
    ``os.environ[...]`` load, given as a literal or as a module constant."""
    tree = ast.parse(source)
    constants = {
        t.id: node.value.value
        for node in tree.body if isinstance(node, ast.Assign)
        and isinstance(node.value, ast.Constant) and isinstance(node.value.value, str)
        for t in node.targets if isinstance(t, ast.Name)
    }
    keys = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            fn = ast.unparse(node.func)
            if fn in ("os.environ.get", "os.getenv") or _ENV_HELPER.fullmatch(fn.rsplit(".", 1)[-1]):
                keys.append(node.args[0])
        elif (isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load)
              and ast.unparse(node.value) == "os.environ"):
            keys.append(node.slice)
    names = set()
    for key in keys:
        value = key.value if isinstance(key, ast.Constant) else constants.get(getattr(key, "id", None))
        if isinstance(value, str) and _ENV.fullmatch(value):
            names.add(value)
    return names


@functools.cache
def _program_env_names() -> frozenset:
    names = set()
    for root in _PROGRAM:
        top = os.path.join(REPO, root)
        files = [top] if top.endswith(".py") else [
            os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs if f.endswith(".py")
        ]
        for path in files:
            with open(path, encoding="utf-8") as f:
                names |= _env_reads(f.read())
    return frozenset(names)


def _missing_commands(text: str) -> list[str]:
    missing = [p for p in _SCRIPT.findall(text)
               if not os.path.isfile(os.path.join(REPO, p))]
    for mod in _MODULE.findall(text):
        base = os.path.join(REPO, *mod.rstrip(".").split("."))
        if not (os.path.isfile(base + ".py") or os.path.isfile(os.path.join(base, "__main__.py"))):
            missing.append("-m " + mod)
    return sorted(set(missing))


def _unread_env_names(text: str) -> list[str]:
    return sorted(set(_ENV.findall(text)) - _program_env_names())


@pytest.mark.parametrize("document, check", [
    (d, c) for d in DOCUMENTS for c in ("commands", "env_names")
] + [(d, "env_names") for d in ENV_ONLY_DOCUMENTS])
def test_document_names_only_what_the_tree_holds(document, check):
    text = _read(document)
    if check == "commands":
        assert _missing_commands(text) == [], f"{document} tells a reader to run what is not there"
    else:
        assert _unread_env_names(text) == [], f"{document} gives DSML_* names nothing reads"

