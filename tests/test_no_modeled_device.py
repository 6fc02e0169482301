"""Product code does not sleep to imitate a device.

The host-path modules once carried a transfer pad and a dispatch pad:
a `time.sleep` in the host-to-device span that stood in for a remote
device's round trip, driven only by a CPU bench. Both left in PR 31;
speed is measured on the chip by `benchmark/run.py`. This holds the six
modules that carried the pads to it, by source (`ast`; nothing is
imported or run).
"""

import ast
from pathlib import Path

import pytest

PACKAGE = Path(__file__).parent.parent / "actor_critic_tpu"

PAD_SUFFIX = "_pad_s"

MODULES = [
    "algos/ppo.py",
    "algos/host_loop.py",
    "algos/ddpg.py",
    "algos/sac.py",
    "data_plane/ring.py",
    "serving/engine.py",
]


def _sleep_calls(tree: ast.AST) -> list[int]:
    """Lines that call `sleep` of the `time` module, under whatever name
    the file imported either (function-local imports too)."""
    time_names, sleep_names = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            time_names |= {
                a.asname or a.name for a in node.names if a.name == "time"
            }
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            sleep_names |= {
                a.asname or a.name for a in node.names if a.name == "sleep"
            }
    lines = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr == "sleep"
            and isinstance(f.value, ast.Name)
            and f.value.id in time_names
        ) or (isinstance(f, ast.Name) and f.id in sleep_names):
            lines.append(node.lineno)
    return lines


def _pad_names(tree: ast.AST) -> list[tuple[int, str]]:
    """Every parameter, keyword, attribute or variable named as a pad."""
    found = []
    for node in ast.walk(tree):
        name = (
            node.arg if isinstance(node, (ast.arg, ast.keyword))
            else node.attr if isinstance(node, ast.Attribute)
            else node.id if isinstance(node, ast.Name)
            else None
        )
        if name is not None and name.endswith(PAD_SUFFIX):
            found.append((node.lineno, name))
    return found


@pytest.mark.parametrize("module", MODULES)
def test_module_neither_sleeps_nor_takes_a_pad(module):
    tree = ast.parse((PACKAGE / module).read_text())
    assert _sleep_calls(tree) == [], (
        f"actor_critic_tpu/{module} calls time.sleep: product code does "
        "not imitate a device; measure on the chip (benchmark/run.py)"
    )
    assert _pad_names(tree) == [], (
        f"actor_critic_tpu/{module} has a *{PAD_SUFFIX} name: the pads "
        "left in PR 31 and do not come back"
    )
