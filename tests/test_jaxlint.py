"""Tier-1 wiring for the jaxlint analyzer (ISSUE 5).

Three layers of guarantees:

1. **Fixture pairs** — per registered check, a `*_flag.py` fixture that
   MUST produce findings of exactly that check and a `*_ok.py` near
   miss that MUST stay completely clean, so a pass going blind (or
   over-flagging the sanctioned idiom) fails CI.
2. **Mechanics** — inline suppression comments (same line and
   standalone line), baseline round-trip (save → load → zero new,
   stale detection when the flagged line changes).
3. **The gate** — the real tree (`actor_critic_tpu train.py`)
   analyzes clean against the repo baseline, and the CLI's exit codes
   stay distinct: 0 clean / 1 findings / 2 crash-or-parse-error.

Everything runs AST-only (the analyzer never imports the files it
scans), so this module is JAX_PLATFORMS=cpu-safe and fast; only the
final gate test touches the live warmup registry (already imported by
the rest of tier-1).
"""

import importlib.util
import json
from pathlib import Path

import pytest

from actor_critic_tpu import analysis
from actor_critic_tpu.analysis import warmup

REPO = Path(__file__).parent.parent
FIXTURES = Path(__file__).parent / "jaxlint_fixtures"

# Every AST check rides the same fixture contract; warmup-registry is
# repo-scoped and has its own pair test below.
PAIRS = [
    ("donation-aliasing", "donation_aliasing"),
    ("tracer-leak", "tracer_leak"),
    ("prng-reuse", "prng_reuse"),
    ("recompile-hazard", "recompile_hazard"),
    ("transfer-discipline", "transfer_discipline"),
    ("donation-discipline", "donation_discipline"),
    ("dispatch-granularity", "dispatch_granularity"),
    ("lock-discipline", "lock_discipline"),
    ("publish-aliasing", "publish_aliasing"),
    ("check-then-act", "check_then_act"),
    ("collective-discipline", "collective_discipline"),
    ("mailbox-protocol", "mailbox_protocol"),
    ("rank-affinity", "rank_affinity"),
    ("precision-discipline", "precision_discipline"),
    ("nonfinite-hazard", "nonfinite_hazard"),
    ("sink-guard", "sink_guard"),
    ("pad-mask-discipline", "pad_mask_discipline"),
    ("mask-propagation", "mask_propagation"),
    ("slice-before-commit", "slice_before_commit"),
]


def _analyze(*names: str, checks=None):
    return analysis.analyze_paths(
        [str(FIXTURES / n) for n in names],
        str(REPO),
        checks=checks,
        skip=("warmup-registry",),
    )


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "jaxlint_cli", REPO / "scripts" / "jaxlint.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# fixture pairs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("check,stem", PAIRS)
def test_flag_fixture_flags(check, stem):
    findings = _analyze(f"{stem}_flag.py")
    assert findings, f"{stem}_flag.py produced no findings"
    assert all(f.check == check for f in findings), (
        f"{stem}_flag.py leaked findings of other checks: "
        f"{[f.render() for f in findings if f.check != check]}"
    )


@pytest.mark.parametrize("check,stem", PAIRS)
def test_ok_fixture_stays_clean(check, stem):
    findings = _analyze(f"{stem}_ok.py")
    assert findings == [], (
        f"{stem}_ok.py must be clean, got: "
        f"{[f.render() for f in findings]}"
    )


def test_warmup_registry_fixture_pair():
    mods = analysis.load_modules(
        [
            str(FIXTURES / "warmup_registry_flag.py"),
            str(FIXTURES / "warmup_registry_ok.py"),
        ],
        str(REPO),
    )
    sites = warmup.sites_from_modules(
        mods, scan_dirs=("tests/jaxlint_fixtures",)
    )
    assert set(sites) == {
        "warmup_registry_flag.make_step",
        "warmup_registry_ok.make_step",
    }
    findings = warmup.site_findings(
        sites, registered={"warmup_registry_ok.make_step"}, exempt={}
    )
    assert [f.check for f in findings] == ["warmup-registry"]
    assert "warmup_registry_flag.make_step" in findings[0].message
    # near miss: fully covered registry -> clean
    assert (
        warmup.site_findings(
            sites,
            registered={
                "warmup_registry_flag.make_step",
                "warmup_registry_ok.make_step",
            },
            exempt={},
        )
        == []
    )
    # stale exemptions are findings too (refactors can't leave dead keys)
    stale = warmup.site_findings(
        sites,
        registered={
            "warmup_registry_flag.make_step",
            "warmup_registry_ok.make_step",
        },
        exempt={"gone.make_step": "reason"},
    )
    assert len(stale) == 1 and "stale exemption" in stale[0].message


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

_SNIPPET = (
    "import jax\n"
    "def f(seed):\n"
    "    key = jax.random.key(seed)\n"
    "    a = jax.random.normal(key, (2,))\n"
    "    b = jax.random.uniform(key, (2,)){pragma}\n"
    "    return a + b\n"
)


def _run_snippet(tmp_path, src):
    p = tmp_path / "snippet.py"
    p.write_text(src)
    return analysis.analyze_paths(
        [str(p)], str(REPO), skip=("warmup-registry",)
    )


def test_suppression_same_line(tmp_path):
    assert _run_snippet(tmp_path, _SNIPPET.format(pragma=""))
    suppressed = _run_snippet(
        tmp_path,
        _SNIPPET.format(
            pragma="  # jaxlint: disable=prng-reuse (fixture reason)"
        ),
    )
    assert suppressed == []


def test_suppression_standalone_line_covers_next_code_line(tmp_path):
    src = _SNIPPET.format(pragma="").replace(
        "    b = jax.random.uniform",
        "    # jaxlint: disable=prng-reuse (fixture reason)\n"
        "    b = jax.random.uniform",
    )
    assert _run_snippet(tmp_path, src) == []


def test_suppression_is_per_check(tmp_path):
    # Disabling a DIFFERENT check must not hide the finding.
    still = _run_snippet(
        tmp_path,
        _SNIPPET.format(pragma="  # jaxlint: disable=transfer-discipline"),
    )
    assert len(still) == 1 and still[0].check == "prng-reuse"
    assert (
        _run_snippet(
            tmp_path, _SNIPPET.format(pragma="  # jaxlint: disable=all")
        )
        == []
    )


# ---------------------------------------------------------------------------
# false-positive guards (reviewed hazards that must stay clean)
# ---------------------------------------------------------------------------


def test_fold_in_loop_idiom_is_clean(tmp_path):
    src = (
        "import jax\n"
        "def rollout(key, steps):\n"
        "    out = []\n"
        "    for i in range(steps):\n"
        "        sub = jax.random.fold_in(key, i)\n"
        "        out.append(jax.random.normal(sub, ()))\n"
        "    return out\n"
    )
    assert _run_snippet(tmp_path, src) == []


def test_exclusive_if_arms_are_not_reuse(tmp_path):
    src = (
        "import jax\n"
        "def sample(key, flag):\n"
        "    if flag:\n"
        "        a = jax.random.normal(key, (2,))\n"
        "    else:\n"
        "        a = jax.random.uniform(key, (2,))\n"
        "    return a\n"
    )
    assert _run_snippet(tmp_path, src) == []


def test_donation_read_in_sibling_branch_is_not_use_after_free(tmp_path):
    src = (
        "import jax\n"
        "def dispatch(state, fast, slow_fn):\n"
        "    step = jax.jit(lambda s: s, donate_argnums=0)\n"
        "    if fast:\n"
        "        metrics = step(state)\n"
        "    else:\n"
        "        metrics = slow_fn(state)\n"
        "    return metrics\n"
    )
    assert _run_snippet(tmp_path, src) == []


def test_hot_module_pragma_in_docstring_does_not_opt_in(tmp_path):
    body = (
        "import numpy as np\n"
        "def collect(act, obs, steps):\n"
        "    for _ in range(steps):\n"
        "        obs = np.asarray(act(obs))\n"
        "    return obs\n"
    )
    doc = '"""Docs may MENTION `# jaxlint: hot-module` safely."""\n'
    assert _run_snippet(tmp_path, doc + body) == []
    # ... while a real comment pragma does opt in
    flagged = _run_snippet(tmp_path, "# jaxlint: hot-module\n" + body)
    assert [f.check for f in flagged] == ["transfer-discipline"]


def test_partial_scan_reports_no_stale_exemptions(capsys):
    """Scanning ONE algos file (against the repo baseline) must stay
    clean: neither the other modules' compile_cache.EXEMPT entries nor
    the unscanned files' baseline entries may read as stale."""
    cli = _load_cli()
    rc = cli.main(["actor_critic_tpu/algos/host_loop.py"])
    out = capsys.readouterr()
    assert rc == 0, f"{out.out}\n{out.err}"
    assert "stale" not in out.err


def test_write_baseline_scoped_run_keeps_out_of_scope_entries(
    tmp_path, capsys
):
    cli = _load_cli()
    bl = tmp_path / "bl.json"
    foreign = {
        "check": "host-sync",
        "path": "some/other/file.py",
        "context": "f",
        "line_text": "x = np.asarray(y)",
        "reason": "audited elsewhere",
    }
    analysis.save_baseline(str(bl), [foreign])
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--baseline", str(bl), "--write-baseline",
        ]
    )
    capsys.readouterr()
    assert rc == 0
    entries = analysis.load_baseline(str(bl))
    assert any(e.get("reason") == "audited elsewhere" for e in entries)
    assert any(e.get("check") == "prng-reuse" for e in entries)


def test_multiline_donating_call_is_not_self_reuse(tmp_path):
    src = (
        "import jax\n"
        "def run(state):\n"
        "    step = jax.jit(lambda s: s, donate_argnums=0)\n"
        "    out = step(\n"
        "        state,\n"
        "    )\n"
        "    return out\n"
    )
    assert _run_snippet(tmp_path, src) == []


def test_loop_carried_donation_without_rebind_flags(tmp_path):
    src = (
        "import jax\n"
        "def run(state, n):\n"
        "    step = jax.jit(lambda s: s, donate_argnums=0)\n"
        "    for _ in range(n):\n"
        "        metrics = step(state)\n"  # state freed on iteration 1
        "    return metrics\n"
    )
    flagged = _run_snippet(tmp_path, src)
    assert [f.check for f in flagged] == ["donation-aliasing"]
    assert "never rebound" in flagged[0].message


def test_standalone_pragma_covers_multiline_statement(tmp_path):
    src = (
        "# jaxlint: hot-module\n"
        "import numpy as np\n"
        "def collect(act, obs, steps):\n"
        "    for _ in range(steps):\n"
        "        # jaxlint: disable=transfer-discipline (fixture reason)\n"
        "        obs = (\n"
        "            np.asarray(act(obs))\n"  # finding anchors HERE
        "        )\n"
        "    return obs\n"
    )
    assert _run_snippet(tmp_path, src) == []


def test_standalone_pragma_does_not_disable_a_whole_block(tmp_path):
    src = (
        "# jaxlint: hot-module\n"
        "import numpy as np\n"
        "def collect(act, obs, steps, flag):\n"
        "    # jaxlint: disable=transfer-discipline (header only)\n"
        "    for _ in range(steps):\n"
        "        obs = np.asarray(act(obs))\n"
        "    return obs\n"
    )
    flagged = _run_snippet(tmp_path, src)
    assert [f.check for f in flagged] == ["transfer-discipline"]


def test_quoted_pragma_in_comment_does_not_suppress(tmp_path):
    src = (
        "# jaxlint: hot-module\n"
        "import numpy as np\n"
        "def collect(act, obs, steps):\n"
        "    for _ in range(steps):\n"
        "        # TODO: revisit the `# jaxlint: disable=host-sync` idea\n"
        "        obs = np.asarray(act(obs))\n"
        "    return obs\n"
    )
    flagged = _run_snippet(tmp_path, src)
    assert [f.check for f in flagged] == ["transfer-discipline"]


def test_legacy_host_sync_pragma_still_suppresses(tmp_path):
    """The deprecation alias (ISSUE 15): annotations written against
    the absorbed host-sync name keep suppressing transfer-discipline
    at their sites."""
    src = (
        "# jaxlint: hot-module\n"
        "import numpy as np\n"
        "def collect(act, obs, steps):\n"
        "    for _ in range(steps):\n"
        "        # jaxlint: disable=host-sync (legacy annotation)\n"
        "        obs = np.asarray(act(obs))\n"
        "    return obs\n"
    )
    assert _run_snippet(tmp_path, src) == []


def test_stale_warnings_are_check_scoped(capsys):
    """A --checks subset run must not call the deselected checks'
    baseline entries stale."""
    cli = _load_cli()
    rc = cli.main(["actor_critic_tpu", "--checks", "prng-reuse"])
    out = capsys.readouterr()
    assert rc == 0, f"{out.out}\n{out.err}"
    assert "stale" not in out.err


def test_write_baseline_refuses_no_baseline(tmp_path, capsys):
    cli = _load_cli()
    bl = tmp_path / "bl.json"
    analysis.save_baseline(
        str(bl),
        [{"check": "host-sync", "path": "p.py", "context": "f",
          "line_text": "x", "reason": "audited"}],
    )
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--baseline", str(bl), "--no-baseline", "--write-baseline",
        ]
    )
    capsys.readouterr()
    assert rc == 2
    assert analysis.load_baseline(str(bl))[0]["reason"] == "audited"


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    findings = _analyze("prng_reuse_flag.py")
    assert findings
    path = tmp_path / "baseline.json"
    analysis.save_baseline(
        str(path), analysis.regenerate(findings, [])
    )
    entries = analysis.load_baseline(str(path))
    new, matched, stale = analysis.apply_baseline(findings, entries)
    assert new == []
    assert len(matched) == len(findings)
    assert stale == []
    # regenerating preserves hand-written reasons by fingerprint
    entries[0]["reason"] = "audited: deliberate"
    regen = analysis.regenerate(findings, entries)
    assert any(e["reason"] == "audited: deliberate" for e in regen)


def test_baseline_goes_stale_when_the_line_changes(tmp_path):
    findings = _analyze("prng_reuse_flag.py")
    entries = analysis.regenerate(findings, [])
    entries[0]["line_text"] = "edited since the entry was written"
    new, _matched, stale = analysis.apply_baseline(findings, entries)
    # the finding resurfaces as new AND the dead entry is reported
    assert new and stale


def test_malformed_baseline_is_a_crash_not_a_clean_run(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text("{not json")
    with pytest.raises(analysis.AnalysisError):
        analysis.load_baseline(str(path))


# ---------------------------------------------------------------------------
# CLI: exit codes, --list-checks, --json
# ---------------------------------------------------------------------------


def test_cli_list_checks_names_all_twenty_one(capsys):
    cli = _load_cli()
    assert cli.main(["--list-checks"]) == 0
    out = capsys.readouterr().out
    for name in (
        "donation-aliasing", "tracer-leak", "prng-reuse",
        "recompile-hazard", "transfer-discipline", "warmup-registry",
        "lock-discipline", "publish-aliasing", "check-then-act",
        "collective-discipline", "mailbox-protocol", "rank-affinity",
        "precision-discipline", "nonfinite-hazard", "sink-guard",
        "donation-discipline", "dispatch-granularity",
        "pad-mask-discipline", "mask-propagation", "slice-before-commit",
    ):
        assert name in out
    # absorbed: no registered check is NAMED host-sync any more (the
    # docs column may still mention it as the absorbed predecessor)
    assert not any(
        line.startswith("host-sync") for line in out.splitlines()
    )


def test_select_host_sync_alias_resolves(capsys):
    """`--select host-sync` must run transfer-discipline (the
    deprecation alias), not crash as an unknown check."""
    cli = _load_cli()
    rc = cli.main(
        [
            str(FIXTURES / "transfer_discipline_flag.py"),
            "--no-baseline", "--select", "host-sync",
        ]
    )
    capsys.readouterr()
    assert rc == 1  # the flag fixture's findings surface through the alias
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--no-baseline", "--select", "host-sync",
        ]
    )
    capsys.readouterr()
    assert rc == 0  # alias selects ONLY the successor check


def test_cli_exit_codes_distinguish_findings_from_crashes(
    tmp_path, capsys
):
    cli = _load_cli()
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli.main([str(clean), "--no-baseline"]) == 0

    flag = str(FIXTURES / "prng_reuse_flag.py")
    assert cli.main([flag, "--no-baseline", "--error-on-new"]) == 1

    broken = tmp_path / "broken.py"
    broken.write_text("def (:\n")
    assert cli.main([str(broken), "--no-baseline"]) == 2
    assert cli.main([str(tmp_path / "missing.py"), "--no-baseline"]) == 2
    assert cli.main([flag, "--no-baseline", "--checks", "no-such"]) == 2
    capsys.readouterr()


def test_cli_json_mode(capsys):
    cli = _load_cli()
    rc = cli.main(
        [str(FIXTURES / "prng_reuse_flag.py"), "--no-baseline", "--json"]
    )
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"]["new"] >= 1
    assert all(f["check"] == "prng-reuse" for f in payload["new"])
    assert payload["counts"]["stale"] == 0


# ---------------------------------------------------------------------------
# the tier-1 gate: the real tree is clean against the repo baseline
# ---------------------------------------------------------------------------


def test_repo_tree_is_clean(capsys):
    """`python scripts/jaxlint.py actor_critic_tpu train.py` must
    exit 0: zero un-baselined findings (the ISSUE 5 acceptance
    criterion, enforced in-process so tier-1 fails with the report)."""
    cli = _load_cli()
    rc = cli.main(["actor_critic_tpu", "train.py", "--error-on-new"])
    out = capsys.readouterr()
    assert rc == 0, f"jaxlint found new findings:\n{out.out}\n{out.err}"


# ---------------------------------------------------------------------------
# --select / --prune-stale (ISSUE 7 satellites)
# ---------------------------------------------------------------------------


def test_select_runs_only_the_named_checks(capsys):
    cli = _load_cli()
    # prng_reuse_flag.py HAS prng findings, but a selection that
    # excludes the check must come back clean.
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--no-baseline", "--select", "host-sync,lock-discipline",
        ]
    )
    capsys.readouterr()
    assert rc == 0
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--no-baseline", "--select", "prng-reuse",
        ]
    )
    capsys.readouterr()
    assert rc == 1
    # a typo'd selection is a crash, not a clean run
    assert (
        cli.main(
            [str(FIXTURES / "prng_reuse_flag.py"), "--select", "no-such"]
        )
        == 2
    )
    capsys.readouterr()


def test_prune_stale_drops_only_in_scope_dead_entries(tmp_path, capsys):
    cli = _load_cli()
    bl = tmp_path / "bl.json"
    dead_in_scope = {
        "check": "prng-reuse",
        "path": "tests/jaxlint_fixtures/prng_reuse_flag.py",
        "context": "f",
        "line_text": "this line no longer exists",
        "reason": "went stale",
    }
    out_of_scope = {
        "check": "host-sync",
        "path": "some/other/file.py",
        "context": "g",
        "line_text": "x = np.asarray(y)",
        "reason": "audited elsewhere",
    }
    live = analysis.regenerate(_analyze("prng_reuse_flag.py"), [])
    for e in live:
        e["reason"] = "kept"
    analysis.save_baseline(
        str(bl), [dead_in_scope, out_of_scope, *live]
    )
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--baseline", str(bl), "--prune-stale",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0 and "pruned 1" in out
    after = analysis.load_baseline(str(bl))
    reasons = {e["reason"] for e in after}
    # dead-in-scope gone; matched entries and out-of-scope retained
    assert "went stale" not in reasons
    assert "audited elsewhere" in reasons
    assert "kept" in reasons


def test_prune_stale_refuses_no_baseline(tmp_path, capsys):
    cli = _load_cli()
    bl = tmp_path / "bl.json"
    analysis.save_baseline(
        str(bl),
        [{"check": "host-sync", "path": "p.py", "context": "f",
          "line_text": "x", "reason": "audited"}],
    )
    rc = cli.main(
        [
            str(FIXTURES / "prng_reuse_flag.py"),
            "--baseline", str(bl), "--no-baseline", "--prune-stale",
        ]
    )
    capsys.readouterr()
    assert rc == 2
    assert analysis.load_baseline(str(bl))[0]["reason"] == "audited"


# ---------------------------------------------------------------------------
# --diff mode (ISSUE 15 satellite): lint only files changed vs a ref
# ---------------------------------------------------------------------------


def _scratch_repo(tmp_path):
    """A throwaway git repo the CLI's REPO global is redirected into —
    the only way to make --diff deterministic regardless of the real
    working tree's state."""
    import subprocess

    root = tmp_path / "scratch"
    root.mkdir()
    git = ["git", "-C", str(root), "-c", "user.email=t@t",
           "-c", "user.name=t"]
    subprocess.run([*git[:3], "init", "-q"], check=True)
    (root / "clean.py").write_text("x = 1\n")
    (root / "hot.py").write_text("y = 2\n")
    subprocess.run([*git, "add", "-A"], check=True)
    subprocess.run([*git, "commit", "-qm", "seed"], check=True)
    return root


def test_diff_mode_lints_only_changed_files(tmp_path, capsys):
    cli = _load_cli()
    root = _scratch_repo(tmp_path)
    old_repo = cli.REPO
    cli.REPO = str(root)
    try:
        # nothing changed -> clean exit 0 without scanning anything
        rc = cli.main(["clean.py", "hot.py", "--no-baseline",
                       "--diff", "HEAD"])
        out = capsys.readouterr().out
        assert rc == 0 and "nothing to lint" in out
        # introduce a finding in ONE file: only it is linted
        (root / "hot.py").write_text(
            "import jax\n"
            "def f(seed):\n"
            "    key = jax.random.key(seed)\n"
            "    a = jax.random.normal(key, (2,))\n"
            "    b = jax.random.uniform(key, (2,))\n"
            "    return a + b\n"
        )
        rc = cli.main(["clean.py", "hot.py", "--no-baseline",
                       "--diff", "HEAD", "--json",
                       "--skip", "warmup-registry"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert {f["path"] for f in payload["new"]} == {"hot.py"}
        # a changed file OUTSIDE the scanned paths stays out
        rc = cli.main(["clean.py", "--no-baseline", "--diff", "HEAD"])
        out = capsys.readouterr().out
        assert rc == 0 and "nothing to lint" in out
        # exit codes unchanged: a bad ref is a crash, not a clean run
        rc = cli.main(["clean.py", "--no-baseline",
                       "--diff", "no-such-ref"])
        capsys.readouterr()
        assert rc == 2
    finally:
        cli.REPO = old_repo


# ---------------------------------------------------------------------------
# --since mode (ISSUE 20 satellite): --diff + rev-parse + untracked +
# fixture-pair re-lint
# ---------------------------------------------------------------------------


def test_since_mode_includes_untracked_files(tmp_path, capsys):
    cli = _load_cli()
    root = _scratch_repo(tmp_path)
    old_repo = cli.REPO
    cli.REPO = str(root)
    try:
        # a brand-new (never-committed) module: invisible to --diff,
        # linted by --since
        (root / "fresh.py").write_text(
            "import jax\n"
            "def f(seed):\n"
            "    key = jax.random.key(seed)\n"
            "    a = jax.random.normal(key, (2,))\n"
            "    b = jax.random.uniform(key, (2,))\n"
            "    return a + b\n"
        )
        rc = cli.main(["fresh.py", "--no-baseline", "--diff", "HEAD"])
        out = capsys.readouterr().out
        assert rc == 0 and "nothing to lint" in out
        rc = cli.main(["fresh.py", "--no-baseline", "--since", "HEAD",
                       "--json", "--skip", "warmup-registry"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert {f["path"] for f in payload["new"]} == {"fresh.py"}
    finally:
        cli.REPO = old_repo


def test_since_mode_resolves_revs_and_rejects_typos(tmp_path, capsys):
    cli = _load_cli()
    root = _scratch_repo(tmp_path)
    old_repo = cli.REPO
    cli.REPO = str(root)
    try:
        # a symbolic rev a plain `git diff` would also take — --since
        # resolves it through rev-parse first, same answer
        rc = cli.main(["clean.py", "--no-baseline", "--since", "HEAD"])
        out = capsys.readouterr().out
        assert rc == 0 and "nothing to lint" in out
        rc = cli.main(["clean.py", "--no-baseline",
                       "--since", "no-such-rev"])
        err = capsys.readouterr().err
        assert rc == 2 and "not a resolvable rev" in err
        # --diff and --since together is a usage error, not a merge
        rc = cli.main(["clean.py", "--no-baseline",
                       "--since", "HEAD", "--diff", "HEAD"])
        capsys.readouterr()
        assert rc == 2
    finally:
        cli.REPO = old_repo


def test_since_mode_fixture_pair_relints_the_pass_module(
    tmp_path, capsys
):
    """A change touching ONLY a check's fixture pair re-lints the
    module implementing that check: the fixture pins the pass's
    flag/ok contract, so editing one without re-examining the other is
    the drift --since exists to catch."""
    import sys as _sys
    import types

    cli = _load_cli()
    root = _scratch_repo(tmp_path)
    (root / "passmod.py").write_text("z = 3\n")
    import subprocess

    git = ["git", "-C", str(root), "-c", "user.email=t@t",
           "-c", "user.name=t"]
    subprocess.run([*git, "add", "-A"], check=True)
    subprocess.run([*git, "commit", "-qm", "pass module"], check=True)
    # a registered check whose implementing module file lives in the
    # scratch repo (the real registry's modules live outside it)
    modname = "jaxlint_scratch_pass"
    mod = types.ModuleType(modname)
    mod.__file__ = str(root / "passmod.py")
    _sys.modules[modname] = mod

    def scratch_check(mod_info):
        return []

    scratch_check.__module__ = modname
    analysis.core.register_check("scratch-pair", "test-only")(
        scratch_check
    )
    old_repo = cli.REPO
    cli.REPO = str(root)
    try:
        fixdir = root / "tests" / "jaxlint_fixtures"
        fixdir.mkdir(parents=True)
        (fixdir / "scratch_pair_flag.py").write_text("w = 4\n")
        rc = cli.main(["passmod.py", "--no-baseline",
                       "--since", "HEAD",
                       "--skip", "warmup-registry"])
        out = capsys.readouterr().out
        # the fixture itself is outside the scanned paths, but its
        # pass module was pulled in and linted (clean)
        assert rc == 0
        assert "nothing to lint" not in out
        assert "0 new finding(s)" in out
    finally:
        cli.REPO = old_repo
        analysis.core._CHECKS.pop("scratch-pair", None)
        _sys.modules.pop(modname, None)


# ---------------------------------------------------------------------------
# thread-owned annotation mechanics (ISSUE 7)
# ---------------------------------------------------------------------------

_COUNTER_SNIPPET = (
    "import threading\n"
    "class Svc:\n"
    "    def __init__(self):\n"
    "{anno}"
    "        self.blocks = 0\n"
    "        self._t = threading.Thread(target=self._run)\n"
    "    def _run(self):\n"
    "        while True:\n"
    "            self.blocks += 1\n"
)


def test_thread_owned_annotation_clears_the_attribute(tmp_path):
    flagged = _run_snippet(tmp_path, _COUNTER_SNIPPET.format(anno=""))
    assert [f.check for f in flagged] == ["lock-discipline"]
    clean = _run_snippet(
        tmp_path,
        _COUNTER_SNIPPET.format(
            anno="        # jaxlint: thread-owned=svc (fixture reason)\n"
        ),
    )
    assert clean == []


def test_cta_window_with_two_writes_flags_once(tmp_path):
    """Every unlocked write in a check-then-act window belongs to that
    finding: lock-discipline must not ALSO flag the second compound
    write (one defect, one finding)."""
    src = (
        "import threading\n"
        "class Reg:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._subs = []\n"
        "    def add(self, x):\n"
        "        if x in self._subs:\n"
        "            return\n"
        "        self._subs.append(x)\n"
        "        self._subs.sort()\n"
    )
    flagged = _run_snippet(tmp_path, src)
    assert [f.check for f in flagged] == ["check-then-act"]


def test_thread_owned_in_docstring_does_not_annotate(tmp_path):
    # The pragma is anchored to comment starts; prose QUOTING it (as
    # this repo's docs do) must not silence the finding.
    doc = (
        '        """Docs may MENTION `# jaxlint: thread-owned=x`."""\n'
    )
    src = _COUNTER_SNIPPET.format(anno="").replace(
        "    def __init__(self):\n",
        "    def __init__(self):\n" + doc,
    )
    flagged = _run_snippet(tmp_path, src)
    assert [f.check for f in flagged] == ["lock-discipline"]


# ---------------------------------------------------------------------------
# the two PR 6 bugs reproduce as findings (ISSUE 7 acceptance)
# ---------------------------------------------------------------------------

# telemetry/session.py as it was BEFORE the PR 6 per-thread span-stack
# fix: one module-global open-span list, pushed/popped from every
# thread that opens a span (actor services do). Reverting the fix must
# trip lock-discipline.
_PRE_FIX_SESSION = (
    "import threading\n"
    "import time\n"
    "_OPEN_SPANS = []\n"
    "class _Span:\n"
    "    def __init__(self, name):\n"
    "        self._name = name\n"
    "    def __enter__(self):\n"
    "        _OPEN_SPANS.append((self._name, time.perf_counter()))\n"
    "        return self\n"
    "    def __exit__(self, *exc):\n"
    "        _OPEN_SPANS.pop()\n"
    "def last_open_span():\n"
    "    return _OPEN_SPANS[-1] if _OPEN_SPANS else None\n"
)


def test_pr6_span_stack_revert_trips_lock_discipline(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_SESSION)
    assert {f.check for f in flagged} == {"lock-discipline"}
    lines = {f.line for f in flagged}
    assert len(lines) == 2  # the push AND the pop
    # ...and the FIXED session.py (per-thread stacks, registry lock)
    # sweeps clean: the finding is the revert, not the fix.
    assert (
        analysis.analyze_paths(
            ["actor_critic_tpu/telemetry/session.py"],
            str(REPO),
            checks=["lock-discipline"],
        )
        == []
    )


# ppo.train_host_async's transfer as it was BEFORE the PR 6
# copy-on-transfer fix: jnp.asarray may alias the slot's numpy buffer
# zero-copy, and the release below hands the slot back to the pool
# while the dispatched update still reads it.
_PRE_FIX_TRANSFER = (
    "import jax.numpy as jnp\n"
    "def learner(queue, update, params, opt_state, key):\n"
    "    while True:\n"
    "        block = queue.get()\n"
    "        arrays = {k: jnp.asarray(v) for k, v in "
    "block.arrays.items()}\n"
    "        queue.release(block)\n"
    "        params, opt_state = update(params, opt_state, arrays)\n"
)


def test_pr6_copy_on_transfer_revert_trips_publish_aliasing(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_TRANSFER)
    assert [f.check for f in flagged] == ["publish-aliasing"]
    assert "jnp.asarray" in flagged[0].message
    # the fixed consumer (jnp.array snapshots) stays clean
    fixed = _PRE_FIX_TRANSFER.replace("jnp.asarray", "jnp.array")
    assert _run_snippet(tmp_path, fixed) == []
    # ...and so does the real ppo.py this fixture mirrors
    assert (
        analysis.analyze_paths(
            ["actor_critic_tpu/algos/ppo.py"],
            str(REPO),
            checks=["publish-aliasing"],
        )
        == []
    )


# ---------------------------------------------------------------------------
# the PR 12 protocol bugs reproduce as findings (ISSUE 12 acceptance)
# ---------------------------------------------------------------------------

# multihost.read_params as it was BEFORE the PR 12 torn-read fix: the
# handler tuple misses zipfile.BadZipFile/EOFError, so the first torn
# snapshot (SIGKILL mid-publish on a non-atomic writer, fs hiccup)
# kills the mailbox writer thread. Reverting the fix must trip
# mailbox-protocol.
_PRE_FIX_READER = (
    "import os\n"
    "import numpy as np\n"
    "def params_file(mailbox_dir, rank):\n"
    "    return os.path.join(mailbox_dir, f'host{rank}', 'params.npz')\n"
    "def read_params(mailbox_dir, rank):\n"
    "    path = params_file(mailbox_dir, rank)\n"
    "    try:\n"
    "        with np.load(path) as z:\n"
    "            return {k: z[k] for k in z.files}\n"
    "    except (OSError, KeyError, ValueError):\n"
    "        return None\n"
)


def test_pr12_torn_reader_revert_trips_mailbox_protocol(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_READER)
    assert [f.check for f in flagged] == ["mailbox-protocol"]
    assert "BadZipFile" in flagged[0].message
    # the fixed multihost.py sweeps clean
    assert (
        analysis.analyze_paths(
            ["actor_critic_tpu/parallel/multihost.py"],
            str(REPO),
            checks=["mailbox-protocol"],
        )
        == []
    )


# train.py's --distributed telemetry wiring as it was BEFORE the PR 12
# rank-affinity fix: every host hands the SAME --telemetry-dir and
# metrics path to its session/logger — N hosts interleave one jsonl.
_PRE_FIX_TELEMETRY = (
    "class TelemetrySession:\n"
    "    def __init__(self, directory, **kw):\n"
    "        self.directory = directory\n"
    "class JsonlLogger:\n"
    "    def __init__(self, path, **kw):\n"
    "        self.path = path\n"
    "def main(args):\n"
    "    if args.distributed:\n"
    "        pass  # ranks join the fleet here\n"
    "    session = TelemetrySession(args.telemetry_dir)\n"
    "    logger = JsonlLogger(args.metrics)\n"
    "    return session, logger\n"
)


def test_pr12_telemetry_clobber_revert_trips_rank_affinity(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_TELEMETRY)
    assert {f.check for f in flagged} == {"rank-affinity"}
    assert len(flagged) == 2  # the session AND the logger
    # the fixed train.py (host<rank>-suffixed paths) sweeps clean
    assert (
        analysis.analyze_paths(
            ["train.py"], str(REPO), checks=["rank-affinity"]
        )
        == []
    )


# The PR 9 review bug as a snippet: a GLOBAL newest-seen version clock
# across peers permanently mutes every host slower than the fastest.
_GLOBAL_CLOCK_POLL = (
    "def poll(mailbox, schedule):\n"
    "    newest = -1\n"
    "    for peer in schedule:\n"
    "        out = mailbox.read(peer)\n"
    "        if out is None:\n"
    "            continue\n"
    "        version, params = out\n"
    "        if version > newest:\n"
    "            newest = version\n"
    "            mailbox.deposit(params, version, peer)\n"
)


def test_global_version_clock_trips_mailbox_protocol(tmp_path):
    flagged = _run_snippet(tmp_path, _GLOBAL_CLOCK_POLL)
    assert [f.check for f in flagged] == ["mailbox-protocol"]
    assert "per-peer" in flagged[0].message.lower() or (
        "PER RANK" in flagged[0].message
    )


# ---------------------------------------------------------------------------
# the ISSUE 14 bug classes reproduce as findings (numerics acceptance)
# ---------------------------------------------------------------------------

# replay/quantize.init_stats as it would read with the PR 8 bug
# re-introduced: the scale stats slot seeded at 1.0 instead of the
# _EPS floor (the running max only grows, so the 1.0 seed permanently
# floors the quantization step at 1/127). Reverting the fix must trip
# nonfinite-hazard.
_PRE_FIX_SCALE_SEED = (
    "import jax.numpy as jnp\n"
    "def init_stats(kind, example_leaf):\n"
    "    shape = jnp.shape(example_leaf)\n"
    "    mean = jnp.zeros(shape, jnp.float32)\n"
    "    scale = jnp.full(shape, 1.0, jnp.float32)\n"
    "    return {'mean': mean, 'scale': scale}\n"
)


def test_pr8_scale_seed_revert_trips_nonfinite_hazard(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_SCALE_SEED)
    assert [f.check for f in flagged] == ["nonfinite-hazard"]
    assert "PR 8" in flagged[0].message
    # the fixed quantize.py (the _EPS-floor seed) sweeps clean
    assert (
        analysis.analyze_paths(
            ["actor_critic_tpu/replay/quantize.py"],
            str(REPO),
            checks=["nonfinite-hazard"],
        )
        == []
    )


# A bf16 compute path whose loss reduction lost its fp32 accumulator —
# the revert the precision pass exists to catch before the ROADMAP's
# bf16/Pallas work lands.
_PRE_FIX_BF16_ACCUMULATOR = (
    "import jax.numpy as jnp\n"
    "def loss_terms(preds_f32, targets_f32):\n"
    "    preds = preds_f32.astype(jnp.bfloat16)\n"
    "    targets = targets_f32.astype(jnp.bfloat16)\n"
    "    err = preds - targets\n"
    "    return jnp.mean(err * err)\n"
)


def test_bf16_accumulator_revert_trips_precision_discipline(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_BF16_ACCUMULATOR)
    assert [f.check for f in flagged] == ["precision-discipline"]
    assert "accumulate" in flagged[0].message.lower()
    # the fp32-accumulator spelling is the near miss
    fixed = _PRE_FIX_BF16_ACCUMULATOR.replace(
        "jnp.mean(err * err)", "jnp.mean(err * err, dtype=jnp.float32)"
    )
    assert _run_snippet(tmp_path, fixed) == []


# The per-algo loss reductions exactly as they would read with
# ISSUE 19's fp32 accumulators dropped: under --update-dtype bf16 the
# activations reach every jnp.mean bare and the entropy/pg terms
# accumulate in bf16.
_PRE_FIX_UPDATE_LOSS = (
    "import jax.numpy as jnp\n"
    "def update_loss(log_probs_f32, ratio_f32, adv_f32):\n"
    "    log_probs = log_probs_f32.astype(jnp.bfloat16)\n"
    "    ratio = ratio_f32.astype(jnp.bfloat16)\n"
    "    adv = adv_f32.astype(jnp.bfloat16)\n"
    "    entropy = -jnp.mean(log_probs)\n"
    "    pg_loss = -jnp.mean(ratio * adv)\n"
    "    return pg_loss + entropy\n"
)


def test_update_loss_accumulator_revert_trips_precision_discipline(
    tmp_path,
):
    """ISSUE 19: dropping the explicit fp32 accumulators from the
    update-shaped loss reductions is caught per-site, and the LANDED
    per-algo loss modules (which spell every reduction with
    dtype=jnp.float32) sweep clean."""
    flagged = _run_snippet(tmp_path, _PRE_FIX_UPDATE_LOSS)
    assert flagged and all(
        f.check == "precision-discipline" for f in flagged
    )
    assert sum(
        "accumulate" in f.message.lower() for f in flagged
    ) == 2  # one finding per bare reduction: entropy AND pg_loss
    assert (
        analysis.analyze_paths(
            [
                "actor_critic_tpu/algos/ppo.py",
                "actor_critic_tpu/algos/a2c.py",
                "actor_critic_tpu/algos/impala.py",
            ],
            str(REPO),
            checks=["precision-discipline"],
        )
        == []
    )


# telemetry/sampler._emit as it was BEFORE the ISSUE 14 fix: the strict
# allow_nan=False dumps — one NaN gauge raises ValueError on every tick
# and resource sampling silently ends for the rest of the run.
_PRE_FIX_SAMPLER = (
    "import json\n"
    "def emit(fh, sample_row):\n"
    "    try:\n"
    "        fh.write(json.dumps(sample_row(), allow_nan=False) + '\\n')\n"
    "    except (OSError, ValueError):\n"
    "        pass\n"
)


def test_sampler_nan_crash_revert_trips_sink_guard(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_FIX_SAMPLER)
    assert [f.check for f in flagged] == ["sink-guard"]
    assert "safe_json_row" in flagged[0].message
    # the fixed telemetry writers sweep clean
    assert (
        analysis.analyze_paths(
            [
                "actor_critic_tpu/telemetry/sampler.py",
                "actor_critic_tpu/telemetry/spans.py",
                "actor_critic_tpu/telemetry/session.py",
                "actor_critic_tpu/utils/logging.py",
            ],
            str(REPO),
            checks=["sink-guard"],
        )
        == []
    )


# ---------------------------------------------------------------------------
# the ISSUE 15 regression classes reproduce as findings (perf acceptance)
# ---------------------------------------------------------------------------

# The async PPO learner's consume path as it was BEFORE PR 13's device
# data plane: every consumed block is gathered to host numpy and
# re-uploaded inside the steady-state loop — the per-block transfer the
# device ring removed. Re-introducing it must trip transfer-discipline.
_PRE_PR13_HOST_GATHER = (
    "# jaxlint: hot-module\n"
    "import jax\n"
    "import jax.numpy as jnp\n"
    "def learner(queue, update, params, opt_state, key, n):\n"
    "    for _ in range(n):\n"
    "        block = queue.get()\n"
    "        host = jax.device_get(block.arrays)\n"
    "        arrays = {k: jnp.array(v) for k, v in host.items()}\n"
    "        queue.release(block)\n"
    "        params, opt_state, _ = update(params, opt_state, arrays, key)\n"
    "    return params, opt_state\n"
)


def test_pre_pr13_host_gather_trips_transfer_discipline(tmp_path):
    flagged = _run_snippet(tmp_path, _PRE_PR13_HOST_GATHER)
    assert {f.check for f in flagged} == {"transfer-discipline"}
    lines = {f.line for f in flagged}
    assert len(lines) == 2  # the gather AND the re-upload
    # the fixed device-plane consume (ppo.train_host_async's device
    # branch) sweeps clean — audited annotations only
    assert (
        analysis.analyze_paths(
            ["actor_critic_tpu/algos/ppo.py"],
            str(REPO),
            checks=["transfer-discipline"],
        )
        == []
    )


# An undonated recycled device ring ingest — the donation gap the
# donation-discipline pass exists to price (the real ring's enqueue
# donates; a NEW consumer forgetting to would re-pay a full-state copy
# per block).
_UNDONATED_RING_INGEST = (
    "import jax\n"
    "def make_ingest_update(cfg):\n"
    "    def ingest(ring_state, block):\n"
    "        return ring_state\n"
    "    return jax.jit(ingest)\n"
    "def learner(cfg, ring_state, blocks):\n"
    "    ingest = make_ingest_update(cfg)\n"
    "    for block in blocks:\n"
    "        ring_state = ingest(ring_state, block)\n"
    "    return ring_state\n"
)


def test_undonated_ring_ingest_trips_donation_discipline(tmp_path):
    flagged = _run_snippet(tmp_path, _UNDONATED_RING_INGEST)
    assert [f.check for f in flagged] == ["donation-discipline"]
    assert "donate_argnums" in flagged[0].message
    # the donated spelling is the near miss
    fixed = _UNDONATED_RING_INGEST.replace(
        "jax.jit(ingest)", "jax.jit(ingest, donate_argnums=0)"
    )
    assert _run_snippet(tmp_path, fixed) == []
    # ...and the real device plane (donating enqueue/ingest) stays clean
    assert (
        analysis.analyze_paths(
            [
                "actor_critic_tpu/data_plane/ring.py",
                "actor_critic_tpu/data_plane/device_replay.py",
            ],
            str(REPO),
            checks=["donation-discipline"],
        )
        == []
    )


# A Python-level reduction over per-actor device metrics inside the
# step loop — one tiny dispatch per element plus a sync, every
# iteration; the dispatch-granularity class.
_PY_REDUCTION_IN_LOOP = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "step = jax.jit(lambda s, b: s, donate_argnums=0)\n"
    "def drive(state, blocks, shards):\n"
    "    for b in blocks:\n"
    "        total = sum(jnp.sum(s) for s in shards)\n"
    "        state = step(state, total)\n"
    "    return state\n"
)


def test_python_reduction_trips_dispatch_granularity(tmp_path):
    flagged = _run_snippet(tmp_path, _PY_REDUCTION_IN_LOOP)
    assert {f.check for f in flagged} == {"dispatch-granularity"}
    assert any("sum()" in f.message for f in flagged)
    # folding the reduction into the program is the near miss
    fixed = _PY_REDUCTION_IN_LOOP.replace(
        "        total = sum(jnp.sum(s) for s in shards)\n"
        "        state = step(state, total)\n",
        "        state = step(state, b)\n",
    )
    assert _run_snippet(tmp_path, fixed) == []
    # the real fused driver stays clean
    assert (
        analysis.analyze_paths(
            ["actor_critic_tpu/algos/host_loop.py"],
            str(REPO),
            checks=["dispatch-granularity"],
        )
        == []
    )


def test_ungated_commit_points_trip_sink_guard(tmp_path):
    """Stripping the check_finite gate from a commit-point def (the
    numsan reverted-guard mode, in source form) must resurface as a
    sink-guard finding — and the real gated modules stay clean."""
    src = (
        "STORE = {}\n"
        "def write_params(mailbox_dir, rank, version, params):\n"
        "    STORE[(mailbox_dir, rank)] = (version, params)\n"
    )
    flagged = _run_snippet(tmp_path, src)
    assert [f.check for f in flagged] == ["sink-guard"]
    assert (
        analysis.analyze_paths(
            [
                "actor_critic_tpu/parallel/multihost.py",
                "actor_critic_tpu/serving/policy_store.py",
                "actor_critic_tpu/algos/traj_queue.py",
                "actor_critic_tpu/utils/checkpoint.py",
            ],
            str(REPO),
            checks=["sink-guard"],
        )
        == []
    )
