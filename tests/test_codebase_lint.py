"""Self-lint gate (ISSUE 6 satellite): run ruff (pyflakes + bugbear
rules, configured in pyproject.toml) over the codebase as a tier-1 test
so real-defect regressions — undefined names, unused imports/vars,
mutable default args — fail CI. Skips when ruff is not installed (the
container does not ship it); the config still drives editor/CI runs.

A dependency-free fallback check (AST walk for unused module-level
imports, the highest-volume pyflakes class) runs either way, so the
self-lint invariant survives environments without ruff."""

import ast
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKIP_DIRS = {"__pycache__", "proto", ".git", ".claude", "csrc"}
# files whose unused imports are intentional re-export surfaces —
# mirrors pyproject's [tool.ruff.lint.per-file-ignores]
REEXPORT_FILES = {"__init__.py", "lowering.py"}


def _py_files():
    for dirpath, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


@pytest.mark.skipif(shutil.which("ruff") is None,
                    reason="ruff not installed in this environment")
def test_ruff_pyflakes_bugbear_clean():
    out = subprocess.run(
        ["ruff", "check", "--no-cache", "."],
        cwd=ROOT, capture_output=True, text=True)
    assert out.returncode == 0, (
        f"ruff found issues:\n{out.stdout}\n{out.stderr}")


def _unused_imports(path):
    src = open(path).read()
    tree = ast.parse(src)
    noqa = {i + 1 for i, line in enumerate(src.splitlines())
            if "noqa" in line}
    imported = {}
    for node in tree.body:  # module level only (function-local imports
        # are often for side effects / lazy cycles)
        if isinstance(node, ast.Import):
            for a in node.names:
                imported[(a.asname or a.name).split(".")[0]] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name in ("*", "annotations"):
                    continue
                imported[a.asname or a.name] = node.lineno
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            used.add(node.value)  # __all__ strings / doc references
    return [(ln, name) for name, ln in imported.items()
            if name not in used and ln not in noqa]


def test_no_unused_module_level_imports():
    problems = []
    for path in _py_files():
        if os.path.basename(path) in REEXPORT_FILES:
            continue
        try:
            for ln, name in _unused_imports(path):
                problems.append(
                    f"{os.path.relpath(path, ROOT)}:{ln}: "
                    f"unused import '{name}'")
        except SyntaxError as e:
            problems.append(f"{path}: syntax error: {e}")
    assert not problems, "\n".join(problems)


def test_all_sources_compile():
    """Syntax gate: every source file byte-compiles (catches stray
    merge markers / py-version slips before any import-time cost)."""
    for path in _py_files():
        with open(path, "rb") as f:
            compile(f.read(), path, "exec")
    assert True


def test_ruff_config_present():
    """The ruff config (pyflakes F + bugbear B) must stay in
    pyproject.toml so editor/CI runs agree with this gate."""
    cfg = open(os.path.join(ROOT, "pyproject.toml")).read()
    assert "[tool.ruff.lint]" in cfg
    assert '"F"' in cfg and '"B"' in cfg


def _a_pytest_of_its_own(tmp_path, tests, patch=""):
    """``tests`` as a file run by a pytest of its own under
    tests/conftest.py (``patch``: statements on ``conftest`` first)."""
    (tmp_path / "test_two.py").write_text(tests)
    run = (f"import sys, pytest, conftest\n{patch}"
           "sys.exit(pytest.main(['-q', '-p', 'conftest', '-p', "
           f"'no:cacheprovider', '--rootdir', {str(tmp_path)!r}, "
           f"{str(tmp_path / 'test_two.py')!r}]))\n")
    out = subprocess.run(
        [sys.executable, "-c", run], capture_output=True, text=True,
        timeout=120, cwd=ROOT, env={
            **os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join(
                [os.path.join(ROOT, "tests"), ROOT])})
    return out.returncode, out.stdout + out.stderr


def test_a_test_that_hangs_fails_by_its_name_and_the_run_goes_on(tmp_path):
    """tests/conftest.py's limit a test, with the constant patched to
    1 s in a pytest of its own over two tests: the sleeper is ``F`` by
    its name with the threads' stacks, the next test runs and passes."""
    rc, said = _a_pytest_of_its_own(
        tmp_path,
        "import time\n\n"
        "def test_sleeps():\n    time.sleep(60)\n\n"
        "def test_after():\n    pass\n",
        patch="conftest.TEST_LIMIT_S = 1.0\n")
    assert rc == 1, said[-2000:]
    assert "1 failed, 1 passed" in said, said[-2000:]
    assert "test_two.py::test_sleeps took more than the 1 s" in said
    # the stack of the thread that slept, down to the test's own line
    assert "in test_sleeps" in said and "Current thread" in said


def test_a_test_that_leaves_telemetry_on_leaves_no_hook_behind(tmp_path):
    """tests/conftest.py's ``_zeroed_metrics`` puts the flag back, in a
    pytest of its own over two tests: the first turns telemetry on and
    ends, the second finds it off and nothing of the monitor's in
    ``gc.callbacks``."""
    rc, said = _a_pytest_of_its_own(
        tmp_path,
        "import gc\n\nfrom paddle_tpu import monitor\n\n"
        "def test_leaves_it_on():\n    monitor.enable()\n"
        "    assert monitor._on_gc in gc.callbacks\n\n"
        "def test_after():\n    assert not monitor.enabled()\n"
        "    assert monitor._on_gc not in gc.callbacks\n")
    assert rc == 0 and "2 passed" in said, said[-2000:]
