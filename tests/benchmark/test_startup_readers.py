"""The five start-up readers (`benchmark/startup.py`) on canned spans: what
each reads, and nothing from a run without spans or from one made before
the program had a `startup` span."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MAIN, WARM = 11, 22


def _x(span, ts_ms, dur_ms, tid=MAIN, **args):
    e = {"name": span, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
         "pid": 1, "tid": tid, "cat": "phase"}
    if args:
        e["args"] = args
    return e


# Start-up from -500 ms to the end of the first update at 9,000 ms. On the
# training thread: state 1,000 ms; in the first update a 700 ms trace with
# a lowering of 300 ms after it and a nested trace inside it (counted once),
# a 600 ms cache load and a 50 ms collection; before it a 100 ms collection.
# On the warm-up thread, spans of the same names that no reader counts.
CANNED = [
    {"name": "clock_sync", "ph": "M", "pid": 1, "tid": 0,
     "args": {"unix_epoch_at_ts0": 1.0}},
    _x("init_env", 0, 200),
    _x("warmup", 300, 4000, tid=WARM, entry="impala.make_train_step"),
    _x("jit_trace", 310, 900, tid=WARM, fun="train_step"),
    _x("compile", 1300, 2900, tid=WARM, name="jit_train_step"),
    _x("gc", 400, 100, generation=2, collected=3),
    _x("init_state", 2000, 1000),
    _x("startup", -500, 7400),
    _x("jit_trace", 7000, 700, fun="train_step"),
    _x("jit_trace", 7100, 200, fun="inner"),
    _x("jit_lower", 7700, 300, fun="jit(train_step)"),
    _x("gc", 7750, 50, generation=2, collected=0),
    _x("compile", 8100, 600, name="jit_train_step", cache_hit=True),
    _x("update", 6900, 2100, it=1, dispatch="async"),
    _x("jit_trace", 9500, 40, fun="later"),
    _x("gc", 9600, 80, generation=2, collected=1),
    _x("update", 9100, 2, it=2, dispatch="async"),
]
WANT_ORDER = ("startup_ms", "init_state_ms", "retrace_ms", "cache_load_ms",
              "gc_pause_ms")
WANT = {
    "startup_ms": 9500.0,
    "init_state_ms": 1000.0,
    "retrace_ms": 1000.0,
    "cache_load_ms": 600.0,
    "gc_pause_ms": 150.0,
}


@pytest.mark.parametrize("reader", sorted(WANT))
def test_reader_reads_canned_startup(reader):
    mod = harness.load_module("layers", reader)
    assert (mod.UNIT, mod.SOURCE, mod.MOVES) == ("ms", "program_span", "setup_s")
    assert mod.read({"spans": CANNED}, None) == pytest.approx(WANT[reader])


@pytest.mark.parametrize("reader", sorted(WANT))
def test_reader_reads_nothing_without_startup(reader):
    mod = harness.load_module("layers", reader)
    assert mod.read({}, None) is None
    assert mod.read({"spans": []}, None) is None
    older = [e for e in CANNED if e["name"] not in (
        "startup", "init_env", "init_state", "jit_trace", "jit_lower",
        "compile", "warmup", "gc")]
    assert mod.read({"spans": older}, None) is None


def test_the_three_parts_fit_inside_startup():
    run = {"spans": CANNED}
    part = {name: harness.load_module("layers", name).read(run, None)
            for name in WANT}
    assert part["init_state_ms"] + part["retrace_ms"] + part["cache_load_ms"] \
        <= part["startup_ms"]
    assert harness.load_module("layers", "gc_pause_ms").read(
        {"spans": [e for e in CANNED if e["name"] != "gc"]}, None) == 0.0


def test_the_token_cells_read_the_startup_readers_once_listed(monkeypatch):
    """The five entries list the two pixel cells: a token cell's own test
    holds its count of accepted entries at 14. Appending a token cell to
    their `workloads` is all it takes for the cell to read them."""
    import json

    manifest = harness.load_manifest()
    entries = [e for e in manifest["per_layer"] if e["name"] in WANT]
    assert [e["name"] for e in entries] == list(WANT_ORDER)
    assert all(e["workloads"] == ["impala_pong.fleet", "impala_pong.fleet2048"]
               for e in entries)
    later = json.loads(json.dumps(manifest))
    token_cells = [w["name"] for w in manifest["workloads"]
                   if not w["name"].startswith("impala_pong.")]
    for e in later["per_layer"]:
        if e["name"] in WANT:
            e["workloads"] += token_cells
    before = {c: harness.per_layer_names(harness.load_json("workloads", f"{c}.json"))
              for c in token_cells}
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    for c in token_cells:
        after = harness.per_layer_names(harness.load_json("workloads", f"{c}.json"))
        assert after == before[c] + list(WANT_ORDER)
