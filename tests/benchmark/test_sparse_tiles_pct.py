"""benchmark/layers/sparse_tiles_pct.py (ISSUE 37): the share of the sparse
causal pass's tiles that hold a selected pair, from the program's
`sparse_tiles_frac` in the window's rows: on hand-made rows, on the rows of
the cell's rehearsal, and nothing on the parent's rows. A file and NOT a
manifest entry, like the token cells' other readers (PERF.md section 7), so
the test also holds that it would read the cell once listed."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CELL = "impala_keye_vl2.ctx8192"


def _read(run):
    return harness.load_module("layers", "sparse_tiles_pct").read(run, None)


@pytest.mark.parametrize("fracs, want", [
    ([0.53125, 0.53125], 53.125),    # 136 of 256 tiles at rows of 8,192
    ([0.25, None, 0.75], 50.0),      # a row without the key is skipped
])
def test_rows_with_the_key_give_the_mean_times_100(fracs, want):
    rows = [{"iter": i, **({} if f is None else {"sparse_tiles_frac": f})}
            for i, f in enumerate(fracs)]
    assert _read({"rows": rows}) == pytest.approx(want)


@pytest.mark.parametrize("run", [{"rows": [{"iter": 1}, {"iter": 2}]}, {"rows": []}, {}],
                         ids=["the_parents_rows", "no_rows", "no_rows_key"])
def test_a_program_without_the_counter_reads_nothing(run):
    assert _read(run) is None


def test_the_rehearsals_rows_carry_the_counter(tmp_path):
    """The rehearsal's own command line for the cell (train.py at toy
    widths), its rows written under `tmp_path` and not the cell's scratch
    directory, which `test_impala_keye_vl2.py`'s rehearsals use: every row
    has `sparse_tiles_frac`. At 16 positions the shipped chunks of 512 make
    the square one tile, which holds pairs: 100."""
    workload = harness.load_json("workloads", f"{CELL}.json")
    ctx = harness.Ctx(workload, harness.load_json("configs", f"{workload['config']}.json"),
                      harness.load_json("traffic", f"{workload['traffic']}.json"),
                      3037000001, 1.0, False, True, str(tmp_path))
    metrics = str(tmp_path / "metrics.jsonl")
    argv = harness.load_module("drivers", workload["driver"]).train_argv(ctx, 2, metrics)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    r = subprocess.run([sys.executable, "train.py", *argv], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = harness.read_rows(metrics)
    assert len(rows) == 2 and all("sparse_tiles_frac" in row for row in rows)
    assert _read({"rows": rows}) == pytest.approx(100.0)


def test_the_cell_reads_it_once_listed(monkeypatch):
    mod = harness.load_module("layers", "sparse_tiles_pct")
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        "sequence policy", "%", "program_counter", "fused_steps_per_s")
    cell = harness.load_json("workloads", f"{CELL}.json")
    accepted = harness.per_layer_names(cell)
    assert "sparse_tiles_pct" not in accepted
    later = json.loads(json.dumps(harness.load_manifest()))
    later["per_layer"].append({
        "name": "sparse_tiles_pct", "unit": mod.UNIT, "better": "higher",
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL]})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(cell) == accepted + ["sparse_tiles_pct"]
