"""The style gate's own tests (reference codestyle/test_docstring_checker.py)."""

import ast
import os
import subprocess
import sys

import pytest

REPO = __file__.rsplit("/tests/", 1)[0]


def _run_checker(tmp_path, source, *args):
    f = tmp_path / "mod.py"
    f.write_text(source)
    return subprocess.run(
        [sys.executable, f"{REPO}/codestyle/docstring_checker.py", str(f), *args],
        capture_output=True, text=True, timeout=60,
    )


def test_flags_missing_docstrings(tmp_path):
    r = _run_checker(
        tmp_path,
        "class Thing:\n    pass\n\ndef func():\n    pass\n",
    )
    assert r.returncode == 1
    assert "module docstring missing" in r.stdout
    assert "class Thing" in r.stdout
    assert "def func" in r.stdout


def test_passes_documented_module(tmp_path):
    r = _run_checker(
        tmp_path,
        '"""Module."""\n\nclass Thing:\n    """Doc."""\n\n'
        'def func():\n    """Doc."""\n',
    )
    assert r.returncode == 0, r.stdout


def test_private_and_methods_exempt_unless_strict(tmp_path):
    src = (
        '"""Module."""\n\nclass Thing:\n    """Doc."""\n'
        "    def method(self):\n        pass\n\n"
        "def _private():\n    pass\n"
    )
    assert _run_checker(tmp_path, src).returncode == 0
    r = _run_checker(tmp_path, src, "--strict")
    assert r.returncode == 1
    assert "def method" in r.stdout


def test_repo_tree_is_clean():
    r = subprocess.run(
        [sys.executable, f"{REPO}/codestyle/docstring_checker.py",
         f"{REPO}/fleetx_tpu"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout[-1500:]


def _sources(*patterns) -> str:
    """The text of every file under the repo that matches a glob pattern."""
    import glob

    text = []
    for pat in patterns:
        for path in glob.glob(os.path.join(REPO, pat), recursive=True):
            with open(path) as f:
                text.append(f.read())
    return "\n".join(text)


def test_env_vars_documented():
    """Drift gate (ISSUE 5): every ``FLEETX_*`` env var mentioned under
    fleetx_tpu/ and tools/ must appear in docs/ENV_VARS.md — this issue
    found FLEETX_FLASH_BLOCK_K read in ops/pallas/flash_attention.py but
    absent from the doc, and this test keeps that class of drift out."""
    import re

    with open(os.path.join(REPO, "docs", "ENV_VARS.md")) as f:
        doc = f.read()
    # trailing [A-Z0-9]: an f-string prefix like "FLEETX_FLASH_" (dynamic
    # name) reduces to its stem, which the doc's real entries cover as a
    # substring
    reads = set(re.findall(
        r"FLEETX_[A-Z0-9_]*[A-Z0-9]",
        _sources("fleetx_tpu/**/*.py", "tools/**/*.py")))
    missing = sorted(v for v in reads if v not in doc)
    assert not missing, (
        f"env vars read in code but undocumented in docs/ENV_VARS.md: "
        f"{missing}")


def test_documented_env_vars_are_read():
    """The table read the other way: every ``FLEETX_*`` / ``BENCH_*`` row
    of docs/ENV_VARS.md names a variable some file of the program still
    reads, so a deleted switch cannot keep its row."""
    import re

    with open(os.path.join(REPO, "docs", "ENV_VARS.md")) as f:
        rows = [line for line in f if line.startswith("| `")]
    documented = set()
    for row in rows:
        documented |= set(re.findall(
            r"`((?:FLEETX|BENCH)_[A-Z0-9_]*[A-Z0-9])`", row.split("|")[1]))
    assert len(documented) > 50, "env-var table not found (format changed?)"
    src = _sources("fleetx_tpu/**/*.py", "tools/**/*.py", "perfbench/**/*.py",
                   "bench.py", "chip_smoke.py", "tests/conftest.py")
    unread = sorted(v for v in documented if v not in src)
    assert not unread, (
        f"rows of docs/ENV_VARS.md that no file reads any more: {unread}")


def test_metric_names_linted_and_documented():
    """Metric-name drift gate (ISSUE 9): every registry metric registered
    under fleetx_tpu/ with a literal name must be snake_case with a
    ``fleetx_`` prefix AND appear in the docs/OBSERVABILITY.md metric
    table — the Prometheus exposition surface cannot drift undocumented.
    (Names built from variables would evade a static lint, so literal
    first-arg registration is the house style; the regex below is that
    contract.)"""
    import glob
    import re

    reg_call = re.compile(
        r"\b(?:counter|gauge|histogram|hist)\(\s*[\"']([A-Za-z0-9_.-]+)[\"']")
    names = set()
    for path in glob.glob(os.path.join(REPO, "fleetx_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            names |= set(reg_call.findall(f.read()))
    assert names, "metric-name lint found no registrations (regex rotted?)"
    bad = sorted(n for n in names
                 if not re.match(r"^fleetx_[a-z0-9_]*[a-z0-9]$", n))
    assert not bad, (
        f"registry metrics under fleetx_tpu/ must be snake_case with a "
        f"fleetx_ prefix: {bad}")
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    undocumented = sorted(n for n in names if f"`{n}`" not in doc)
    assert not undocumented, (
        f"metrics registered in code but missing from the "
        f"docs/OBSERVABILITY.md metric table: {undocumented}")


def test_shell_scripts_parse():
    """bash -n over every launch/benchmark script (the reference gates its
    shell surface through CI runs; we gate syntax statically)."""
    import glob

    scripts = [
        p for pat in ("projects/**/*.sh", "tools/*.sh")
        for p in glob.glob(os.path.join(REPO, pat), recursive=True)
    ]
    assert len(scripts) >= 40, scripts  # the launch-script zoo is present
    bad = []
    for s in scripts:
        r = subprocess.run(["bash", "-n", s], capture_output=True, text=True,
                           timeout=30)
        if r.returncode != 0:
            bad.append((s, r.stderr[:200]))
    assert not bad, bad


def _unbounded_waits(source):
    """Line numbers of ``source``'s calls that wait without a bound of 180 s
    or less: ``subprocess.run(``, ``urlopen(``, and a ``.wait(`` or ``.join(``
    whose bound is neither a ``timeout=`` nor its one number
    (``sep.join(parts)`` is handed something else and is no wait)."""
    lines = []
    for node in ast.walk(ast.parse(source)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        name, on = node.func.attr, getattr(node.func.value, "id", None)
        bound = next((k.value for k in node.keywords if k.arg == "timeout"),
                     None)
        if name in ("wait", "join"):
            if node.args and bound is None:
                bound = node.args[0]
                if not (isinstance(bound, ast.Constant)
                        and isinstance(bound.value, (int, float))):
                    continue
        elif not ((name == "run" and on == "subprocess")
                  or name == "urlopen"):
            continue
        if bound is None or (isinstance(bound, ast.Constant)
                             and not 0 < bound.value <= 180):
            lines.append(node.lineno)
    return lines


def test_every_wait_in_the_tests_has_a_bound():
    """No ``subprocess.run(``, ``.wait(``, thread ``.join(`` or ``urlopen(``
    under ``tests/*.py`` without a ``timeout=``, and none above 180 s:
    ``pytest-timeout`` is not installed, so a wait that never returns would
    cost the whole run its clock and name no test."""
    import glob

    unbounded = []
    for path in sorted(glob.glob(os.path.join(REPO, "tests", "*.py"))):
        with open(path) as f:
            unbounded += [f"{os.path.relpath(path, REPO)}:{line}"
                          for line in _unbounded_waits(f.read())]
    assert not unbounded, unbounded


@pytest.mark.parametrize("call,waits_unbounded", [
    ("subprocess.run(cmd, capture_output=True)", True),
    ("subprocess.run(cmd, timeout=500)", True),
    ("subprocess.run(cmd, timeout=120)", False),
    ("proc.wait()", True),
    ("proc.wait(300)", True),
    ("proc.wait(timeout=30)", False),
    ("thread.join()", True),
    ("thread.join(60)", False),
    ("', '.join(parts)", False),
    ("urllib.request.urlopen(url)", True),
    ("urllib.request.urlopen(url, timeout=10)", False),
])
def test_the_bound_on_waits_bites(call, waits_unbounded):
    assert bool(_unbounded_waits(f"x = {call}\n")) == waits_unbounded
