"""The tree's entry points are the ones its documents name (ISSUE 30).

One measurement plane (`benchmarks/run.py`), one proof on the chip
(`chip_smoke.py`), one X.509 block generator (`benchlib.generator`):
these pins keep a deleted script from coming back through a document, a
second generator from coming back through the smoke or the dryrun, and
the device hash route from coming back through `TPUCSP.hash_batch`."""

from __future__ import annotations

import ast
import os
import re
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMMAND = re.compile(r"(?<![\w./-])python3?[ \t]+([^\n`]*)")
_PLACEHOLDER = re.compile(r"[<*{]")


def _named_files(text: str) -> list:
    """The file each `python` / `python3` command of a document runs:
    a script's path, or the path of a `-m` module; None where the
    module's top-level package is not the tree's (pytest and the like)."""
    found = []
    for match in _COMMAND.finditer(text):
        words = match.group(1).split()
        while words and words[0].startswith("-") and words[0] not in ("-m", "-c", "-"):
            words.pop(0)
        if not words or words[0] in ("-c", "-"):
            continue
        if words[0] == "-m":
            if len(words) < 2 or _PLACEHOLDER.search(words[1]):
                continue
            parts = words[1].rstrip(".,;:)").split(".")
            in_tree = os.path.exists(os.path.join(ROOT, parts[0]))
            found.append(os.path.join(*parts) if in_tree else None)
            continue
        target = words[0].rstrip(".,;:)")
        if target.endswith(".py") and not _PLACEHOLDER.search(target):
            found.append(target)
    return found


@pytest.mark.parametrize("document", ["README.md", "PERF.md", "ROADMAP.md"])
def test_every_python_command_of_a_document_names_a_file_of_the_tree(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        named = _named_files(f.read())
    assert named, f"{document}: the scan found no command at all"
    missing = sorted({
        n for n in named
        if n is not None and not any(
            os.path.exists(os.path.join(ROOT, n + tail))
            for tail in ("", ".py", os.sep + "__init__.py")
        )
    })
    assert not missing, (
        f"{document} gives commands that run files the tree does not "
        f"hold: {missing} (a deleted file is named as `git show <sha>:<path>`)"
    )


@pytest.mark.parametrize("entry_point", ["chip_smoke.py", "__graft_entry__.py"])
def test_entry_point_builds_its_blocks_with_the_benchmarks_generator(entry_point):
    with open(os.path.join(ROOT, entry_point), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    scripts = {
        name[:-3] for name in os.listdir(os.path.join(ROOT, "scripts"))
        if name.endswith(".py")
    }
    imported, reaches_generator = set(), False
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name.split(".")[0] for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
            names = {alias.name for alias in node.names}
            reaches_generator |= (
                node.module == "benchlib.generator"
                or (node.module == "benchlib" and "generator" in names)
            )
    # `profile` is also a module of the standard library: neither entry
    # point imports either, so the plain intersection is the rule
    assert not imported & scripts, sorted(imported & scripts)
    assert not any(
        isinstance(node, ast.Constant) and node.value == "scripts"
        for node in ast.walk(tree)
    ), f"{entry_point} still puts scripts/ on its path"
    assert reaches_generator, f"{entry_point} does not import benchlib.generator"


def test_hash_batch_is_hashlib_and_never_loads_the_device_kernel():
    """The route is gone, not dormant: a batch well over the old
    threshold (`min_device_batch`, 16) comes back from hashlib with
    the SHA-256 kernel's module never imported."""
    code = textwrap.dedent("""
        import hashlib, sys
        from fabric_tpu.csp.tpu.provider import TPUCSP

        msgs = [bytes([i]) * 1024 for i in range(64)]
        csp = TPUCSP()
        try:
            assert csp.hash_batch(msgs) == [hashlib.sha256(m).digest() for m in msgs]
        finally:
            csp.close()
        assert "fabric_tpu.csp.tpu.sha256" not in sys.modules
        print("HASHLIB-ONLY")
    """)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "HASHLIB-ONLY" in proc.stdout
