"""Static check: every phase-span name in the codebase is canonical.

`scripts/run_report.py`'s phase breakdown groups spans by NAME — a
typo'd `telemetry.span("updaet")` raises nowhere and simply grows a
one-off row that silently vanishes from every aggregate people actually
read. This test greps the source for every literal name passed to
`telemetry.span(...)` / `complete_span(...)` / `instant(...)` (and the
tracer-level `complete_foreign(...)` the shard-pool relay uses) and
asserts membership in `telemetry.CANONICAL_PHASES`. Add new phases to
that set (telemetry/spans.py) BEFORE instrumenting with them.
"""

import re
from pathlib import Path

import pytest

from actor_critic_tpu import telemetry
from actor_critic_tpu.telemetry import session as session_mod

REPO = Path(__file__).parent.parent

# Source that emits phase spans; tests are excluded on purpose — they
# exercise the tracer with synthetic names.
SCAN = ["actor_critic_tpu", "scripts", "train.py"]

_CALL = re.compile(
    r"""(?:telemetry|_session)\s*\.\s*
        (?:span|complete_span|instant)\s*\(\s*
        (['"])(?P<name>[^'"]+)\1
    """,
    re.VERBOSE,
)
_FOREIGN = re.compile(
    r"""\.\s*complete_foreign\s*\(\s*(['"])(?P<name>[^'"]+)\1""",
    re.VERBOSE,
)
# The serving hops are timed first and emitted afterwards, straight on the
# session's tracer (`tracer.complete("serve_dispatch", ...)`).
_TRACER = re.compile(
    r"""\.\s*complete\s*\(\s*(['"])(?P<name>[^'"]+)\1""",
    re.VERBOSE,
)
# Phase names bound to a constant before use (e.g. the shard-pool
# relay's batched emission) declare themselves with a *_PHASE suffix.
_CONST = re.compile(
    r"""^\s*\w+_PHASE\s*=\s*(['"])(?P<name>[^'"]+)\1""",
    re.MULTILINE,
)


def _span_names() -> dict[str, set[str]]:
    """{span name: {files using it}} across the scanned source."""
    uses: dict[str, set[str]] = {}
    for root in SCAN:
        path = REPO / root
        files = [path] if path.is_file() else sorted(path.rglob("*.py"))
        for f in files:
            text = f.read_text()
            for pat in (_CALL, _FOREIGN, _TRACER, _CONST):
                for m in pat.finditer(text):
                    uses.setdefault(m.group("name"), set()).add(
                        str(f.relative_to(REPO))
                    )
    return uses


def test_every_span_name_is_canonical():
    uses = _span_names()
    assert uses, "scanner found no span call sites — regex rotted?"
    rogue = {
        name: sorted(files)
        for name, files in uses.items()
        if name not in telemetry.CANONICAL_PHASES
    }
    assert not rogue, (
        f"non-canonical span name(s) {rogue} — add to "
        "telemetry/spans.py CANONICAL_PHASES or fix the typo"
    )


def test_core_phases_are_instrumented():
    """The phases the run report's breakdown documents must actually be
    emitted somewhere (guards against an instrumentation refactor
    silently dropping one)."""
    uses = _span_names()
    for phase in ("iteration", "env_step", "update", "log", "checkpoint",
                  "eval", "host_to_device", "env_step_worker"):
        assert phase in uses, f"phase {phase!r} no longer instrumented"


def test_every_canonical_phase_has_a_call_site():
    """The other direction: a name in the vocabulary that nothing emits is
    dead weight in every report's legend (and in `PERF.md`'s table of who
    reads which span). Retire the name with its last call site."""
    unused = telemetry.CANONICAL_PHASES - set(_span_names())
    assert not unused, f"canonical phase(s) nothing emits: {sorted(unused)}"


@pytest.mark.parametrize("name", sorted(telemetry.CANONICAL_PHASES))
def test_a_span_is_mirrored_into_the_profiler_as_ac_name(name, monkeypatch):
    """While a session is installed a span opens a profiler annotation
    named exactly `"ac:" + name`: the contract the benchmark's
    `trace_reduce.label_gap` attributes idle gaps by."""
    opened = []

    class Annotation:
        def __init__(self, label):
            self.label = label

        def __enter__(self):
            opened.append(self.label)

        def __exit__(self, *exc):
            opened.append("/" + self.label)

    monkeypatch.setattr(session_mod, "_ANNOTATION", Annotation)
    with telemetry.span(name):
        assert opened == ["ac:" + name]
    assert opened == ["ac:" + name, "/ac:" + name]
