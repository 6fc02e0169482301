"""benchmark/layers/attn_causal_roofline_pct.py and the count it divides by
(benchmark/kernels/causal_attention.py): the visited tiles and the work of
both cells against a hand count, the reader on a hand-made trace (the
kernel's events under `attn_causal`, nothing else; nothing on the parent's
program), a kernel at the roofline reading 100, and that both cells read it
once a manifest lists it (it is a file, not an entry: PERF.md section 7)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, phases, trace_reduce  # noqa: E402

CELLS = {"impala_mellum2.ctx4096": "impala_mellum2",
         "impala_qwen3_next.ctx16384": "impala_qwen3_next"}
READER = "attn_causal_roofline_pct"
PEAK, BANDWIDTH = 197e12, 819e9
STEP = "jit(train_step)"


def _count():
    return harness.load_module("kernels", "causal_attention")


class _Ctx:
    def __init__(self, cell):
        self.config = harness.load_json("configs", f"{CELLS[cell]}.json")
        self.traffic = harness.load_json(
            "traffic", harness.load_json("workloads", f"{cell}.json")["traffic"] + ".json")

    def param(self, key, default=None):
        return {"step_module": "jit_train_step", **self.traffic}.get(key, default)


@pytest.mark.parametrize("length, window, tiles", [
    (4096, 4096, 36), (4096, 1024, 21), (3072, 1024, 15), (3072, 3072, 21),
    (16384, 16384, 528), (1024, 100, 3), (512, 1, 1)])
def test_the_tiles_a_band_visits(length, window, tiles):
    """Causal: the lower triangle of tiles with its diagonal. A band of
    1,024 at tiles of 512: the diagonal and the two tiles before it. A band
    inside one tile: the diagonal alone, and the tile before it where the
    band reaches back past the tile's first query."""
    assert _count().tiles_visited(length, window) == tiles


@pytest.mark.parametrize("cell, E, T, P, layers", [
    ("impala_mellum2.ctx4096", 8, 4096, 3072, [(1024, 21, 15)] * 3 + [(None, 36, 21)]),
    ("impala_qwen3_next.ctx16384", 2, 16384, 14336, [(None, 528, 406)]),
])
def test_the_work_of_both_cells_against_a_hand_count(cell, E, T, P, layers):
    ctx = _Ctx(cell)
    n = ctx.config["network"]
    settings = harness.cell_settings(ctx)
    assert (int(settings["num_envs"]), int(settings["rollout_steps"])) == (E, T)
    assert ctx.param("env_set")["prefill_len"] == P
    nh, nkv, d = n["num_attention_heads"], n["num_key_value_heads"], n["head_dim"]
    assert [w or T for w, *_ in layers] == _count().windows(n, T)
    tile_ops = 4.0 * 512 * 512 * d * nh
    a_row = 2 * nh * d * 2 + 2 * nkv * d * 2 + nh * 4
    got = _count().phases(n, settings, P)
    assert got["prefill"] == (E * sum(p for *_, p in layers) * tile_ops,
                              E * len(layers) * P * a_row)
    forward = (E * sum(t for _, t, _ in layers) * tile_ops, E * len(layers) * T * a_row)
    assert got["update_forward"] == got["update_recompute"] == forward
    assert got["update_backward"] == (
        2.5 * forward[0], E * len(layers) * T * (a_row + 2 * nh * d * 2 + 2 * nkv * d * 2))
    # Every phase is bound by the operations, the bytes under a quarter of
    # the time.
    assert all(ops / PEAK > 4 * moved / BANDWIDTH for ops, moved in got.values())


def test_the_qwen_cells_full_layer_is_2_27_tflop_a_row_a_forward():
    """528 tiles of 512 x 512 at 16 heads of 256: 2.27 TFLOP a row a
    forward, 11.5 ms at 197 TFLOP/s."""
    ctx = _Ctx("impala_qwen3_next.ctx16384")
    ops, _ = _count().phases(ctx.config["network"], harness.cell_settings(ctx), 0)[
        "update_forward"]
    assert 2.26e12 < ops / 2 < 2.28e12


def _trace(ops):
    modules = [["jit_train_step(7)", 0.0, 90.0], ["jit_train_step(7)", 100.0, 100.0],
               ["jit_train_step(7)", 210.0, 90.0]]
    edge = [["%add.1", 0.0, 5.0, {"tf_op": f"{STEP}/rollout/add"}],
            ["%add.2", 290.0, 5.0, {"tf_op": f"{STEP}/rollout/add"}]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.MODULES_LINE, "events": modules},
        {"name": trace_reduce.OPS_LINE, "events": edge + ops}]}]}


def _kernel(start, dur, stack):
    return [f"%custom-call.{int(start)}", start, dur,
            {"tf_op": f"{STEP}/{stack}", "hlo_category": "custom-call"}]


# One whole step [100, 200): the prefill's kernel, the update's forward and
# its rematerialization, dq and dkv, 5 + 6 + 7 + 8 + 9 = 35 ns; beside them a
# transpose under the same scope (no kernel), the decode's experts kernel
# (another scope), and a dense einsum of the attention half.
OPS = [
    _kernel(110.0, 5.0, "rollout/prefill/attn_full/attn_causal/pallas_call"),
    _kernel(120.0, 6.0, "jvp(forward)/attn_full/attn_causal/pallas_call"),
    _kernel(130.0, 7.0, "transpose(jvp(forward))/attn_full/attn_causal/pallas_call"),
    _kernel(140.0, 8.0, "transpose(jvp(forward))/attn_full/transpose(jvp(attn_causal))/dq"),
    _kernel(150.0, 9.0, "transpose(jvp(forward))/attn_full/transpose(jvp(attn_causal))/dkv"),
    ["%transpose.3", 160.0, 4.0, {"tf_op": f"{STEP}/jvp(forward)/attn_full/attn_causal/transpose"}],
    _kernel(170.0, 11.0, "rollout/while/body/moe_experts/moe_decode/pallas_call"),
    ["%fusion.7", 185.0, 9.0, {"tf_op": f"{STEP}/jvp(forward)/attn_full/einsum"}],
]
PARENT = [OPS[-2], OPS[-1]]


def _clear():
    for cached in (phases.load, phases._step_events_of, phases._steps_of):
        cached.cache_clear()


@pytest.fixture
def read(monkeypatch, tmp_path):
    """The reader over a plain trace (the file is a stand-in; `phases.load`
    is what reads it)."""

    def run(trace, cell, what="read"):
        path = tmp_path / f"t{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(b"")
        _clear()
        monkeypatch.setattr(trace_reduce, "load_xplane", lambda p, **kw: trace)
        mod = harness.load_module("layers", READER)
        return getattr(mod, what)({"trace_path": str(path),
                                   "device": {"kind": "TPU v5 lite", "count": 1}}, _Ctx(cell))
    yield run
    _clear()


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_reader_takes_the_kernels_events_and_nothing_else(read, cell):
    assert read(_trace(OPS), cell, "kernel_ms") == pytest.approx(35.0 / 1e6)
    ctx = _Ctx(cell)
    least_s = _count().roofline_s(ctx.config["network"], harness.cell_settings(ctx),
                                  ctx.param("env_set")["prefill_len"], PEAK, BANDWIDTH)
    assert read(_trace(OPS), cell) == pytest.approx(100.0 * least_s * 1e3 / (35.0 / 1e6))


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_parents_program_reads_nothing(read, cell):
    assert read(_trace(PARENT), cell, "kernel_ms") is None
    assert read(_trace(PARENT), cell) is None
    assert harness.load_module("layers", READER).read({}, _Ctx(cell)) is None


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_kernel_at_the_roofline_reads_100(read, cell):
    """Its events take exactly the least time: 100, no more."""
    ctx = _Ctx(cell)
    least_ns = 1e9 * _count().roofline_s(
        ctx.config["network"], harness.cell_settings(ctx),
        ctx.param("env_set")["prefill_len"], PEAK, BANDWIDTH)
    modules = [["jit_train_step(7)", 0.0, 10.0], ["jit_train_step(7)", 20.0, least_ns + 20.0],
               ["jit_train_step(7)", least_ns + 60.0, 10.0]]
    ops = [["%add.1", 0.0, 5.0, {"tf_op": f"{STEP}/rollout/add"}],
           _kernel(30.0, least_ns, "jvp(forward)/attn_window/attn_causal/pallas_call"),
           ["%add.2", least_ns + 65.0, 5.0, {"tf_op": f"{STEP}/rollout/add"}]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.MODULES_LINE, "events": modules},
        {"name": trace_reduce.OPS_LINE, "events": ops}]}]}
    assert read(trace, cell) == pytest.approx(100.0)


def test_both_cells_read_it_once_a_manifest_lists_it(monkeypatch):
    mod = harness.load_module("layers", READER)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        "sequence policy", "%", "device_trace", "fused_steps_per_s")
    later = json.loads(json.dumps(harness.load_manifest()))
    before = {cell: harness.per_layer_names(harness.load_json("workloads", f"{cell}.json"))
              for cell in CELLS}
    assert not any(READER in names for names in before.values())
    later["per_layer"].append({
        "name": READER, "unit": mod.UNIT, "better": "higher", "source": mod.SOURCE,
        "layer": mod.LAYER, "moves": mod.MOVES, "workloads": list(CELLS)})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    for cell in CELLS:
        workload = harness.load_json("workloads", f"{cell}.json")
        assert harness.per_layer_names(workload) == before[cell] + [READER]
    fleet = harness.load_json("workloads", "impala_pong.fleet.json")
    assert READER not in harness.per_layer_names(fleet)
