"""Tier-1 wiring for perfsan (ISSUE 15 runtime half).

Mirrors test_racesan/test_fleetsan/test_numsan's layers: (1) the quick
profile sweeps green against the COMMITTED perf_budgets.json, (2) the
counters are structural — two runs of the same program measure
identical actuals, (3) a tightened budget is caught (the meter is not
vacuous), (4) both reverted-regression modes are caught
deterministically on every run, (5) the CLI's exit codes stay distinct
(0 green / 1 violation-or-detection / 2 crash).

The exercisers compile tiny REAL programs (the fixture idiom numsan
uses), so this module is JAX_PLATFORMS=cpu-safe; the heavyweight
mixture-fleet program is exercised once and reused.
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from actor_critic_tpu.analysis import perfsan

REPO = Path(__file__).parent.parent
MANIFEST = REPO / "perf_budgets.json"


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "perfsan_cli", REPO / "scripts" / "perfsan.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _budgets():
    return perfsan.load_manifest(str(MANIFEST))


# ---------------------------------------------------------------------------
# the committed manifest is green for every steady-state program
# ---------------------------------------------------------------------------


def test_manifest_is_committed_and_well_formed():
    budgets = _budgets()
    for name in perfsan.PROGRAMS:
        assert name in budgets, f"{name} missing a committed budget"
        for key in perfsan.BUDGET_KEYS:
            assert key in budgets[name], f"{name} missing {key}"
    # the device plane's actor-side enqueue budget rides along
    assert "ppo_update_device.enqueue" in budgets


def test_ppo_update_host_within_budget():
    report = perfsan.run_program("ppo_update_host", _budgets())
    c = report["counters"]
    # the host plane PAYS a per-block upload — budgeted, nonzero
    assert c.transferred_bytes > 0
    assert c.recompiles == 0


def test_ppo_update_device_within_budget_and_zero_transfer():
    report = perfsan.run_program("ppo_update_device", _budgets())
    c = report["counters"]
    # the PR 13 contract, metered: ONE program, ONE explicit transfer
    # (the staged slot index scalar), 4 bytes, zero recompiles
    assert c.dispatches == 1
    assert c.transfers == 1
    assert c.transferred_bytes == 4
    assert c.recompiles == 0
    # the actor-side enqueue moves the encoded bytes instead
    assert report["enqueue"].transferred_bytes >= report[
        "enqueue_bytes_per_block"
    ]
    assert report["enqueue_bytes_per_block"] < report[
        "host_bytes_per_block"
    ]


def test_ppo_update_fused_within_budget():
    """ISSUE 19: the fused consume (gather + decode + advantages via the
    common.gae_targets seam + update, correction='none') meters the SAME
    one-program contract as the device plane — the advantage scan costs
    no extra dispatch, crossing, or recompile."""
    report = perfsan.run_program("ppo_update_fused", _budgets())
    c = report["counters"]
    assert c.dispatches == 1
    assert c.transfers == 1
    assert c.transferred_bytes == 4
    assert c.recompiles == 0


def test_offpolicy_ingest_within_budget():
    report = perfsan.run_program("offpolicy_ingest", _budgets())
    assert report["counters"].dispatches == 1
    assert report["counters"].recompiles == 0


def test_serving_dispatch_swap_never_recompiles():
    report = perfsan.run_program("serving_dispatch", _budgets())
    c = report["counters"]
    assert c.dispatches == 1  # one program per act, every bucket
    assert c.recompiles == 0  # including the act AFTER the hot-swap
    assert c.transfers == 2  # device_put obs in, device_get actions out


def test_serving_overlap_within_budget():
    """ISSUE 17: the overlapped act path (max_inflight flight workers
    dispatching off the 1-deep handoff) keeps the per-act serving
    budget — one dispatch, the two explicit crossings, zero recompiles
    — with flight-thread work metered under the global transfer
    guard."""
    report = perfsan.run_program("serving_overlap", _budgets())
    c = report["counters"]
    assert c.dispatches == 1
    assert c.transfers == 2
    assert c.recompiles == 0


def test_serving_proxy_hop_is_all_zero():
    """ISSUE 17 leg b: the fleet-proxy relay carries NO device state —
    the whole proxied request meters zero dispatches, zero crossings,
    zero bytes, zero recompiles."""
    report = perfsan.run_program("serving_proxy_hop", _budgets())
    c = report["counters"]
    assert c.dispatches == 0
    assert c.transfers == 0 and c.transferred_bytes == 0
    assert c.recompiles == 0


def test_mixture_fleet_step_is_one_fused_program():
    report = perfsan.run_program("mixture_fleet_step", _budgets())
    c = report["counters"]
    assert c.dispatches == 1
    assert c.transfers == 0 and c.transferred_bytes == 0
    assert c.recompiles == 0


# ---------------------------------------------------------------------------
# determinism: the counters are structural
# ---------------------------------------------------------------------------


def test_counters_are_identical_run_to_run():
    a = perfsan.exercise_ppo_update_device(blocks=2)
    b = perfsan.exercise_ppo_update_device(blocks=2)
    assert [c.as_dict() for c in a["per_block"]] == [
        c.as_dict() for c in b["per_block"]
    ]
    # and across seeds: the budgets gate structure, not data
    c = perfsan.exercise_ppo_update_device(blocks=2, seed=7)
    assert a["counters"].as_dict() == c["counters"].as_dict()


# ---------------------------------------------------------------------------
# the meter is not vacuous: a tightened budget trips
# ---------------------------------------------------------------------------


def test_tightened_budget_is_a_violation():
    budgets = {
        "ppo_update_host": {
            "max_dispatches_per_block": 0,
            "max_transfers_per_block": 0,
            "max_transferred_bytes_per_block": 0,
            "max_recompiles": 0,
        }
    }
    with pytest.raises(perfsan.PerfSanError, match="BUDGET VIOLATION"):
        perfsan.run_program("ppo_update_host", budgets)


def test_missing_program_budget_is_a_violation():
    with pytest.raises(perfsan.PerfSanError, match="no budget entry"):
        perfsan.check_budget("brand_new_program", perfsan.Counters(), {})


def test_malformed_manifest_is_a_crash_not_a_detection(tmp_path):
    p = tmp_path / "perf_budgets.json"
    p.write_text("{not json")
    with pytest.raises(perfsan.ManifestError):
        perfsan.load_manifest(str(p))
    with pytest.raises(perfsan.ManifestError):
        perfsan.load_manifest(str(tmp_path / "missing.json"))


def test_manifest_key_typos_are_refused(tmp_path):
    """A misspelled or dropped max_* key would silently UN-GATE that
    counter forever — load_manifest must refuse both loudly."""
    base = {
        "max_dispatches_per_block": 1,
        "max_transfers_per_block": 1,
        "max_transferred_bytes_per_block": 4,
        "max_recompiles": 0,
    }
    p = tmp_path / "perf_budgets.json"
    typo = dict(base)
    typo["max_transfer_per_block"] = typo.pop("max_transfers_per_block")
    p.write_text(json.dumps({"version": 1, "programs": {"x": typo}}))
    with pytest.raises(perfsan.ManifestError, match="unknown key"):
        perfsan.load_manifest(str(p))
    dropped = dict(base)
    del dropped["max_recompiles"]
    p.write_text(json.dumps({"version": 1, "programs": {"x": dropped}}))
    with pytest.raises(perfsan.ManifestError, match="missing budget"):
        perfsan.load_manifest(str(p))


# ---------------------------------------------------------------------------
# reverted modes: caught deterministically on EVERY run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("run", [0, 1])
def test_reverted_host_gather_detected(run):
    with pytest.raises(perfsan.PerfSanError):
        perfsan.run_reverted("host-gather", str(MANIFEST))


@pytest.mark.parametrize("run", [0, 1])
def test_reverted_unfused_detected(run):
    """Splitting the advantage program back out of the fused consume
    (the pre-ISSUE-19 two-dispatch shape) trips the dispatch budget on
    every run."""
    with pytest.raises(
        perfsan.PerfSanError, match="max_dispatches_per_block"
    ):
        perfsan.run_reverted("unfused", str(MANIFEST))


def test_reverted_uncommit_detected():
    with pytest.raises(
        perfsan.PerfSanError, match="max_recompiles"
    ):
        perfsan.run_reverted("uncommit", str(MANIFEST))


def test_measure_restores_all_seams():
    """The measure() context must restore the dispatch hook and the
    four transfer seams even when the block raises — a leaked patch
    would meter (and slow) every later dispatch in the process."""
    import jax
    import jax.numpy as jnp
    from jax._src.api import _post_hook_state

    orig = (
        jax.device_put, jax.device_get, jnp.array, jnp.asarray,
        _post_hook_state.get_global(),
    )
    with pytest.raises(RuntimeError):
        with perfsan.measure():
            raise RuntimeError("boom")
    now = (
        jax.device_put, jax.device_get, jnp.array, jnp.asarray,
        _post_hook_state.get_global(),
    )
    assert now == orig


# ---------------------------------------------------------------------------
# CLI exit codes
# ---------------------------------------------------------------------------


def test_cli_exit_codes(capsys, tmp_path):
    cli = _load_cli()
    # one cheap program green against the committed manifest
    assert cli.main(["--program", "serving_dispatch"]) == 0
    # tightened manifest -> violation (exit 1)
    tight = {
        "version": 1,
        "programs": {
            "serving_dispatch": {
                "max_dispatches_per_block": 0,
                "max_transfers_per_block": 0,
                "max_transferred_bytes_per_block": 0,
                "max_recompiles": 0,
            }
        },
    }
    p = tmp_path / "tight.json"
    p.write_text(json.dumps(tight))
    assert cli.main(
        ["--program", "serving_dispatch", "--manifest", str(p)]
    ) == 1
    # missing manifest -> crash (exit 2), never a detection
    assert cli.main(
        ["--program", "serving_dispatch", "--manifest",
         str(tmp_path / "missing.json")]
    ) == 2
    # unknown program -> crash
    assert cli.main(["--program", "no-such"]) == 2
    # --revert and --program are exclusive
    assert cli.main(
        ["--revert", "uncommit", "--program", "serving_dispatch"]
    ) == 2
    capsys.readouterr()


def test_cli_revert_modes_exit_one(capsys):
    cli = _load_cli()
    assert cli.main(["--revert", "uncommit"]) == 1
    out = capsys.readouterr()
    assert "VIOLATION DETECTED" in out.err
    assert cli.main(["--revert", "unfused"]) == 1
    out = capsys.readouterr()
    assert "VIOLATION DETECTED" in out.err


def test_cli_json_and_out(capsys, tmp_path):
    cli = _load_cli()
    out_path = tmp_path / "actuals.json"
    rc = cli.main(
        ["--program", "serving_dispatch", "--json", "--out",
         str(out_path)]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["programs"]["serving_dispatch"]["actuals"][
        "recompiles"
    ] == 0
    on_disk = json.loads(out_path.read_text())
    assert on_disk == payload
