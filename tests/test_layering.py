"""The package's box diagram, held by its imports.

`RUNGS` orders the units of `actor_critic_tpu/` bottom to top; a unit
may import the units on the rungs below it and nothing else. Every
`import` is read with `ast` (function-local ones and
`importlib.import_module` of a literal too), nothing is imported or
run. `DEBTS` lists the edges that point upward today, with their files:
a new one fails, and so does a listed one that is gone, so the list can
only shrink (ROADMAP D13 is its repair). `README.md` `## Layout` shows
the same table.
"""

import ast
import re
from pathlib import Path

import pytest

REPO = Path(__file__).parent.parent
PACKAGE = REPO / "actor_critic_tpu"

RUNGS = (
    ("utils",),                              # checkpoint, compile cache, guards
    ("telemetry",),                          # spans, sampler, profiler, exporter
    ("native", "ops"),                       # kernels
    ("models", "replay"),                    # networks, buffers
    ("envs",),                               # on-device envs and host pools
    ("data_plane", "parallel"),              # device ring; dp, seqpar, multihost
    ("algos",),                              # the trainers and their drivers
    ("serving", "config"),                   # gateway; presets (config.py)
    ("analysis",),                           # jaxlint and the sanitizers
)
UNITS = sorted(u for rung in RUNGS for u in rung)
ALLOWED = {
    unit: {below for lower in RUNGS[:i] for below in lower}
    for i, rung in enumerate(RUNGS)
    for unit in rung
}

# unit -> {the unit it reaches up to: the files that do}
DEBTS = {
    "utils": {
        "telemetry": {
            "utils/checkpoint.py", "utils/compile_cache.py",
            "utils/watchdog.py",
        },
        "models": {"utils/compile_cache.py"},
        "algos": {"utils/compile_cache.py"},
    },
    "models": {"algos": {"models/seq_policy.py"}},
    "replay": {"algos": {"replay/quantize.py"}},
    "envs": {"algos": {"envs/mixture.py"}},
    "data_plane": {
        "algos": {"data_plane/device_replay.py", "data_plane/ring.py"},
    },
    "parallel": {"algos": {"parallel/dp.py", "parallel/multihost.py"}},
}

OUTSIDE = {"benchmark", "scripts", "train", "bench"}


def _unit_files(unit: str) -> list[Path]:
    d = PACKAGE / unit
    return sorted(d.rglob("*.py")) if d.is_dir() else [PACKAGE / f"{unit}.py"]


def _imported(path: Path) -> set[str]:
    """Dotted names `path` imports, relative ones made absolute."""
    here = list(path.relative_to(REPO).parts[:-1])
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = here[: len(here) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            names |= {f"{mod}.{a.name}" for a in node.names}
        elif isinstance(node, ast.Call) and node.args and (
            getattr(node.func, "attr", None) == "import_module"
        ):
            # importlib.import_module("a.b") or (f"a.b.{name}"): the literal head
            arg = node.args[0]
            if isinstance(arg, ast.JoinedStr) and arg.values:
                arg = arg.values[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                names.add(arg.value.rstrip("."))
    return names


def _edges(unit: str) -> dict[str, set[str]]:
    """Other units of the package that `unit` imports -> its files that do."""
    out: dict[str, set[str]] = {}
    for path in _unit_files(unit):
        for name in _imported(path):
            parts = name.split(".")
            if parts[0] != "actor_critic_tpu" or len(parts) < 2:
                continue
            if parts[1] in ALLOWED and parts[1] != unit:
                out.setdefault(parts[1], set()).add(
                    str(path.relative_to(PACKAGE))
                )
    return out


def test_rungs_name_every_unit_of_the_package():
    on_disk = {
        p.name for p in PACKAGE.iterdir()
        if p.is_dir() and (p / "__init__.py").exists()
    } | {p.stem for p in PACKAGE.glob("*.py") if p.stem != "__init__"}
    assert on_disk == set(UNITS)


@pytest.mark.parametrize("unit", UNITS)
def test_unit_imports_only_the_rungs_below_it(unit):
    upward = {
        target: files for target, files in _edges(unit).items()
        if target not in ALLOWED[unit]
    }
    assert upward == DEBTS.get(unit, {}), (
        f"{unit} may import {sorted(ALLOWED[unit]) or 'no other unit'}; "
        "an edge missing from DEBTS is new (move the code down instead), "
        "one DEBTS has and the tree lacks is repaid (delete it from DEBTS)"
    )


def test_package_imports_nothing_that_drives_it():
    offenders = [
        (str(path.relative_to(REPO)), name)
        for path in sorted(PACKAGE.rglob("*.py"))
        for name in _imported(path)
        if name.split(".")[0] in OUTSIDE
    ]
    assert offenders == []


_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_PY_PATH = re.compile(r"[\w./*-]*\w\.py\b")


def _exists(named: str) -> bool:
    """From the repo's root or the package's; a bare file name, anywhere
    under the directories that hold the repo's Python."""
    if any(next(root.glob(named), None) for root in (REPO, PACKAGE)):
        return True
    return "/" not in named and any(
        next((REPO / d).rglob(named), None)
        for d in ("actor_critic_tpu", "scripts", "benchmark", "tests")
    )


def test_every_python_path_the_readme_names_exists():
    text = (REPO / "README.md").read_text()
    named = {
        p for code in _CODE.findall(text) for p in _PY_PATH.findall(code)
    }
    assert named, "README.md names no Python file: the pattern is broken"
    assert sorted(p for p in named if not _exists(p)) == []
