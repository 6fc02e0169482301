"""benchmark/layers/truncated_rows_pct.py (ISSUE 27): the share of the
window's decisions at which the time limit cut an episode, from the program's
`truncated_frac` in the window's rows. No chip, no JAX device.

The manifest does not list it yet: `test_phases.py` pins the tail of
`BENCHMARK.json`'s `per_layer` to PR 26's seven readers, so the entry takes a
`benchmark` PR that may edit that test (PERF.md section 7). A cell that is
only a file can name it in its own `per_layer` list today."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def _read(run):
    ctx = harness.Ctx({"rate_metric": "fused_steps_per_s", "name": "t"}, {}, {},
                      0, 1.0, False, False, "")
    return harness.load_module("layers", "truncated_rows_pct").read(run, ctx)


def _rows(fracs):
    return [{"iter": 10 * i, "loss": 0.1, **({} if f is None else
                                             {"truncated_frac": f})}
            for i, f in enumerate(fracs)]


@pytest.mark.parametrize("fracs, want", [
    ([0.0, 0.0, 0.0], 0.0),                 # the shipped cells: no row cut
    ([0.05, 0.05], 5.0),                    # max_steps = T: E rows an iteration
    ([0.0, 0.0, 0.0, 0.0, 0.05], 1.0),      # a burst every fifth row
    ([0.001], 0.1),
    ([None, 0.02, None], 2.0),              # a row without the key is skipped
], ids=["zero", "every_iteration", "burst", "one_row", "mixed"])
def test_rows_with_the_key_give_the_mean_times_100(fracs, want):
    assert _read({"rows": _rows(fracs)}) == pytest.approx(want)


@pytest.mark.parametrize("run", [
    {"rows": _rows([None, None])},  # the parent: rows, no counter
    {"rows": []},
    {},
], ids=["rows_without_the_key", "no_rows", "no_rows_key"])
def test_a_program_without_the_counter_reads_nothing(run):
    assert _read(run) is None
