"""The documents describe the tree as it is.

* every token ending in ``.py`` that a document back-ticks, invokes or names
  is a file of the tree, by exact path or by path suffix (the documents write
  ``ops/sampling.py`` for ``ddim_cold_tpu/ops/sampling.py``). Tokens with
  ``<``, ``*`` or ``…`` are patterns, not names. History sections are exempt
  by their heading: they say what a file was when it was there.
* every ``ddim_cold_tpu`` import of the scripts and entry points resolves, at
  any depth: they import lazily inside functions, so importing the script
  itself proves nothing.
"""

import ast
import glob
import importlib
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ("README.md", "PERF.md", "ROADMAP.md", "benchmark/README.md",
             ".github/workflows/ci.yml")
#: headings (lower case, numbering stripped) whose sections are history
HISTORY = ("recent", "findings")

ENTRY_POINTS = sorted(
    os.path.relpath(f, ROOT)
    for f in glob.glob(os.path.join(ROOT, "scripts", "*.py"))) + [
    "chip_smoke.py", "ViT.py", "ViT_draft2drawing.py", "multi_gpu_trainer.py",
    "diffusion_loader.py"]


def _tree_files() -> set:
    """Relative paths of the tree's files, without what ``.gitignore`` lists
    as a directory (scratch checkouts hold copies of deleted files)."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {ln.strip().rstrip("/") for ln in f if ln.strip().endswith("/")}
    ignored.add(".git")
    out = set()
    for dirpath, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in ignored]
        rel = os.path.relpath(dirpath, ROOT)
        out.update(os.path.normpath(os.path.join(rel, n)) for n in names)
    return out


def _without_history(text: str) -> str:
    """``text`` less every section whose heading is one of ``HISTORY``, down
    to the next heading of the same or a higher level."""
    kept, skip_level = [], None
    for line in text.splitlines():
        m = re.match(r"(#+)\s+(?:\d+\.\s*)?(.*)", line)
        if m:
            level, title = len(m.group(1)), m.group(2).strip().lower()
            if skip_level is not None and level <= skip_level:
                skip_level = None
            if skip_level is None and title in HISTORY:
                skip_level = level
        if skip_level is None:
            kept.append(line)
    return "\n".join(kept)


def _py_tokens(text: str) -> set:
    tokens = set()
    for piece in re.split(r"[^\w./<>*…:-]+", text):
        m = re.match(r"(.+?\.py)(?:::?.*)?[.,:;]*$", piece)
        if m and not re.search(r"[<>*…]", piece):
            tokens.add(m.group(1).lstrip("./"))
    return tokens


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_files_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    if doc.endswith(".md"):
        text = _without_history(text)
    files = _tree_files()
    missing = sorted(
        t for t in _py_tokens(text)
        if t not in files and not any(f.endswith("/" + t) for f in files))
    assert not missing, f"{doc} names files the tree does not have: {missing}"


@pytest.mark.parametrize("path", ENTRY_POINTS)
def test_entry_imports_resolve(path):
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read(), path)
    wanted = []  # (module, name or None, line)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            wanted += [(a.name, None, node.lineno) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            wanted += [(node.module, a.name, node.lineno) for a in node.names]
    wanted = [w for w in wanted if w[0].split(".")[0] == "ddim_cold_tpu"]
    for module, name, line in wanted:
        mod = importlib.import_module(module)
        if name is not None and not hasattr(mod, name):
            try:  # ``from package import submodule``
                importlib.import_module(f"{module}.{name}")
            except ImportError:
                pytest.fail(f"{path}:{line}: {module} has no {name!r}")
