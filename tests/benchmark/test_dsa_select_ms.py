"""benchmark/layers/dsa_select_ms.py: the sparse layers' selection, scope
`dsa_select` inside `dsa_index`, read off a planted trace wherever the scope
sits (the decode loop, the prefill, the update's forward and its
rematerialized forward), and nothing on a program without the scope (the
parent's). A file and NOT a manifest entry, like the token cells' other
readers (PERF.md section 7), so the test also holds that the cell would read
it once listed."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, phases, trace_reduce  # noqa: E402

CELL = "impala_keye_vl2.ctx8192"
STEP = "jit(train_step)"
OPS = [
    # The indexer's scores, in `dsa_index` and not in `dsa_select`.
    ["%fusion.1", 100.0, 10.0,
     {"tf_op": f"{STEP}/rollout/while/body/attn_sparse/dsa_index/dot_general"}],
    # The kernel's event in a decode step.
    ["%topk_select.2", 110.0, 3.0, {"tf_op": f"{STEP}/rollout/while/body/attn_sparse/"
                                             "dsa_index/dsa_select/pallas_call",
                                    "hlo_category": "custom-call"}],
    # A fusion of the kernel's event (no stack of its own): its encloser's.
    ["%fusion.3", 111.0, 1.0, {}],
    ["%topk_select.4", 120.0, 3.0, {"tf_op": f"{STEP}/rollout/prefill/checkpoint/"
                                             "attn_sparse/while/body/dsa_index/dsa_select/"
                                             "pallas_call"}],
    ["%topk_select.5", 130.0, 4.0, {"tf_op": f"{STEP}/jvp(forward)/attn_sparse/while/"
                                             "body/dsa_index/dsa_select/pallas_call"}],
    ["%topk_select.6", 140.0, 5.0, {"tf_op": f"{STEP}/transpose(jvp(forward))/"
                                             "attn_sparse/while/body/dsa_index/dsa_select/"
                                             "pallas_call"}],
    ["%fusion.7", 150.0, 9.0, {"tf_op": f"{STEP}/jvp(forward)/attn_sparse/mul"}],
]


def _trace(ops):
    modules = [["jit_train_step(7)", 0.0, 90.0], ["jit_train_step(7)", 100.0, 100.0],
               ["jit_train_step(7)", 210.0, 90.0]]
    edge = [["%add.1", 0.0, 5.0, {"tf_op": f"{STEP}/rollout/add"}],
            ["%add.2", 290.0, 5.0, {"tf_op": f"{STEP}/rollout/add"}]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.MODULES_LINE, "events": modules},
        {"name": trace_reduce.OPS_LINE, "events": edge + ops}]}]}


def _clear():
    for cached in (phases.load, phases._step_events_of, phases._steps_of):
        cached.cache_clear()


CTX = harness.Ctx({"rate_metric": "fused_steps_per_s", "name": "t"}, {},
                  {"step_module": "jit_train_step"}, 0, 1.0, True, False, "")


@pytest.fixture
def read(monkeypatch, tmp_path):
    """The reader over a plain trace (the file is a stand-in; `phases.load`
    is what reads it)."""

    def run(trace, reader="dsa_select_ms"):
        path = tmp_path / f"t{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(b"")
        _clear()
        monkeypatch.setattr(trace_reduce, "load_xplane", lambda p, **kw: trace)
        return harness.load_module("layers", reader).read({"trace_path": str(path)}, CTX)
    yield run
    _clear()


def test_the_reader_finds_the_selection_in_every_phase(read):
    """Decode (the kernel's event and its child), prefill, the update's
    forward and its rematerialized forward; the scores beside it are not
    the selection's."""
    assert read(_trace(OPS)) == pytest.approx((3.0 + 3.0 + 4.0 + 5.0) / 1e6)
    assert read(_trace(OPS), "dsa_index_ms") == pytest.approx(
        (10.0 + 3.0 + 3.0 + 4.0 + 5.0) / 1e6)


@pytest.mark.parametrize("ops", [OPS[:1] + OPS[-1:], OPS[-1:]],
                         ids=["the_parents_program", "no_sparse_layer"])
def test_a_program_without_the_scope_reads_nothing(read, ops):
    assert read(_trace(ops)) is None


def test_no_trace_reads_nothing():
    mod = harness.load_module("layers", "dsa_select_ms")
    assert mod.read({}, CTX) is None


def test_the_cell_reads_it_once_listed(monkeypatch):
    mod = harness.load_module("layers", "dsa_select_ms")
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        "sequence policy", "ms", "device_trace", "fused_steps_per_s")
    cell = harness.load_json("workloads", f"{CELL}.json")
    accepted = harness.per_layer_names(cell)
    assert "dsa_select_ms" not in accepted
    later = json.loads(json.dumps(harness.load_manifest()))
    later["per_layer"].append({
        "name": "dsa_select_ms", "unit": mod.UNIT, "better": "lower",
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL]})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(cell) == accepted + ["dsa_select_ms"]
