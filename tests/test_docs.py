"""Doc-drift guard: everything README/docs name must actually exist.

Docs rot silently — a renamed flag, a dropped env var or a moved
public symbol leaves the guide describing a repo that no longer
exists.  This suite walks ``README.md`` + ``docs/*.md`` and checks,
against the real code:

* every ``BLASX_*`` environment variable is consumed somewhere in
  ``src/`` or ``benchmarks/``;
* every ``--flag`` shown next to one of the repo's own runnables is
  registered by that runnable's argparse (introspected via
  ``main(["--help"])``);
* every dotted ``repro.*`` path resolves by import + getattr;
* every ``cblas_*`` name is exported by ``repro.api``;
* every ``ctx.<method>`` / ``srv.<method>`` reference is an attribute
  of ``BlasxContext`` / ``BlasxServer``;
* the markdown link checker (``tools/check_links.py``, the CI lint
  step) passes — and still fails on actually-broken links.
"""
import contextlib
import importlib
import io
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
DOC_FILES = [REPO_ROOT / "README.md"] + sorted(
    (REPO_ROOT / "docs").glob("*.md"))


def _doc_text():
    return {p: p.read_text(encoding="utf-8") for p in DOC_FILES}


def _source_text():
    chunks = []
    for root in ("src", "benchmarks"):
        for p in sorted((REPO_ROOT / root).rglob("*.py")):
            chunks.append(p.read_text(encoding="utf-8"))
    return "\n".join(chunks)


def test_required_docs_exist():
    for name in ("ARCHITECTURE.md", "TUNING.md", "BENCHMARKS.md"):
        assert (REPO_ROOT / "docs" / name).exists(), f"docs/{name} missing"


def test_env_vars_in_docs_exist_in_code():
    # BLASX_Malloc (the allocator's name) must not read as an env var,
    # hence the no-lowercase-following lookahead
    pat = re.compile(r"BLASX_[A-Z_]{2,}(?![a-z])")
    source = _source_text()
    seen = set()
    for path, text in _doc_text().items():
        for var in pat.findall(text):
            seen.add(var)
            assert var in source, (
                f"{path.name} documents env var {var} but nothing under "
                f"src/ or benchmarks/ mentions it")
    assert "BLASX_TUNING_CACHE" in seen  # the guide must cover it


# the repo's own runnables, as they appear on doc command lines
_RUNNABLES = {
    "benchmarks.run": "benchmarks.run",
    "benchmarks/run.py": "benchmarks.run",
    "compare.py": "benchmarks.compare",
    "benchmarks.overlap": "benchmarks.overlap",
    "repro.serve": "repro.serve.__main__",
    "repro.analysis": "repro.analysis",
    "chip_smoke.py": "chip_smoke",
}


def _argparse_flags(module_name):
    """The --flags a module's main() registers, via --help output."""
    mod = importlib.import_module(module_name)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), pytest.raises(SystemExit):
        mod.main(["--help"])
    return set(re.findall(r"--[A-Za-z][A-Za-z0-9-]*", buf.getvalue()))


def test_cli_flags_in_docs_exist():
    flag_re = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*")
    flags_cache = {}
    checked = 0
    for path, text in _doc_text().items():
        # join backslash continuations so a wrapped command line keeps
        # its runnable token next to its flags
        joined = re.sub(r"\\\n\s*", " ", text)
        for lineno, line in enumerate(joined.splitlines(), 1):
            mods = [m for tok, m in _RUNNABLES.items() if tok in line]
            if not mods:
                continue
            for flag in flag_re.findall(line):
                ok = False
                for module_name in mods:
                    if module_name not in flags_cache:
                        flags_cache[module_name] = _argparse_flags(module_name)
                    ok = ok or flag in flags_cache[module_name]
                assert ok, (
                    f"{path.name}:{lineno} shows flag {flag} for "
                    f"{mods}, but no such argparse option exists")
                checked += 1
    assert checked >= 5  # the docs do show flags; silence = regex rot


def _resolve(dotted):
    parts = dotted.split(".")
    for i in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:i]))
        except ImportError:
            continue
        for attr in parts[i:]:
            obj = getattr(obj, attr)
        return obj
    raise ImportError(dotted)


def test_dotted_repro_paths_resolve():
    pat = re.compile(r"\brepro\.[a-z_][A-Za-z0-9_.]*")
    seen = set()
    for path, text in _doc_text().items():
        for dotted in pat.findall(text):
            dotted = dotted.rstrip(".")
            if dotted in seen:
                continue
            seen.add(dotted)
            try:
                _resolve(dotted)
            except (ImportError, AttributeError) as e:
                pytest.fail(f"{path.name} references {dotted}, which does "
                            f"not resolve: {e}")
    assert len(seen) >= 10


def test_cblas_names_exported():
    api = importlib.import_module("repro.api")
    seen = 0
    for path, text in _doc_text().items():
        for name in set(re.findall(r"\bcblas_[a-z0-9]+\b", text)):
            assert hasattr(api, name), (
                f"{path.name} documents {name}; repro.api does not export it")
            seen += 1
    assert seen >= 12  # both precision families are documented


def test_context_and_server_methods_exist():
    from repro.api import BlasxContext
    from repro.serve import BlasxServer

    for var, cls in (("ctx", BlasxContext), ("srv", BlasxServer)):
        pat = re.compile(rf"\b{var}\.([A-Za-z_][A-Za-z0-9_]*)")
        for path, text in _doc_text().items():
            for attr in set(pat.findall(text)):
                assert hasattr(cls, attr), (
                    f"{path.name} references {var}.{attr}; "
                    f"{cls.__name__} has no such attribute")


def _run_checker(*args):
    return subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "check_links.py"),
         *args],
        capture_output=True, text=True, cwd=str(REPO_ROOT))


def test_markdown_links_are_green():
    proc = _run_checker()
    assert proc.returncode == 0, (
        f"tools/check_links.py failed:\n{proc.stdout}{proc.stderr}")
    assert "0 hard failures" in proc.stdout


def test_link_checker_catches_breakage(tmp_path):
    bad = tmp_path / "bad.md"
    bad.write_text("# Title\n\n[a](#title)\n[b](#no-such)\n[c](gone.md)\n"
                   "```\n[fenced links are ignored](also-gone.md)\n```\n",
                   encoding="utf-8")
    proc = _run_checker(str(bad))
    assert proc.returncode == 1
    assert "broken anchor" in proc.stdout
    assert "broken link" in proc.stdout
    assert "also-gone.md" not in proc.stdout
