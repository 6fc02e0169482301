"""The decode's experts kernel's three readers (`benchmark/layers/
decode_experts_read_pct.py`, `moe_decode_ms.py`, `moe_decode_roofline_pct.py`)
and the count the share divides by (`benchmark/kernels/moe_decode.py`): the
needed work against a hand count, the readers on hand-made rows and a
hand-made trace, and that both token cells read them once a manifest lists
them (they are files, not entries: PERF.md section 7)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, phases  # noqa: E402

CELLS = {"impala_mellum2.ctx4096": "impala_mellum2",
         "impala_joyai_flash.seq512": "impala_joyai_flash"}
READERS = {"decode_experts_read_pct": ("%", "program_counter"),
           "moe_decode_ms": ("ms", "device_trace"),
           "moe_decode_roofline_pct": ("%", "device_trace")}
PEAK, BANDWIDTH = 197e12, 819e9


def _count():
    return harness.load_module("kernels", "moe_decode")


class _Ctx:
    def __init__(self, cell):
        self.config = harness.load_json("configs", f"{CELLS[cell]}.json")
        self.traffic = harness.load_json(
            "traffic", harness.load_json("workloads", f"{cell}.json")["traffic"] + ".json")

    def param(self, key, default=None):
        return {"step_module": "jit_train_step", **self.traffic}.get(key, default)


@pytest.mark.parametrize("cell, layers, steps, E, H, W, frac", [
    ("impala_mellum2.ctx4096", 4, 1024, 8, 2304, 896, 0.65625),
    ("impala_joyai_flash.seq512", 4, 512, 64, 2048, 768, 0.875),
])
def test_needed_work_of_the_decodes_experts_against_a_hand_count(
        cell, layers, steps, E, H, W, frac):
    ctx = _Ctx(cell)
    settings = harness.cell_settings(ctx)
    prefill = ctx.param("env_set", {}).get("prefill_len", 0)
    assert int(settings["rollout_steps"]) - prefill == steps
    ops, moved = _count().needed(ctx.config["network"], settings, prefill, frac)
    chosen = frac * 16                         # of the 16 held
    a_call = chosen * 3 * H * W * 2 + E * H * 2 + E * 16 * 4 + E * H * 4
    assert moved == layers * steps * a_call
    assert ops == layers * steps * chosen * E * 3 * 2 * H * W
    least = _count().roofline_s(ctx.config["network"], settings, prefill, frac,
                                PEAK, BANDWIDTH)
    # Bound by the bandwidth at a decode step's few rows, and by the chosen
    # experts' weights: the rows and the result are under a hundredth.
    assert least == moved / BANDWIDTH > 3 * ops / PEAK
    assert 1.0 < a_call / (chosen * 3 * H * W * 2) < 1.01
    # Every held expert read: what the batched matmuls stream a step.
    _, whole = _count().needed(ctx.config["network"], settings, prefill, 1.0)
    assert whole > layers * steps * 16 * 3 * H * W * 2


def test_the_mellum_cells_least_time_is_what_the_issue_sized():
    """8 tokens x 8 of 64: 65.6% of 16 experts of 12.4 MB a layer and step,
    4 layers, 1,024 steps: 0.52 GB a step, 0.636 ms at 819 GB/s."""
    ctx = _Ctx("impala_mellum2.ctx4096")
    least = _count().roofline_s(ctx.config["network"], harness.cell_settings(ctx),
                                3072, 1 - (7 / 8) ** 8, PEAK, BANDWIDTH)
    assert 0.63e-3 < least / 1024 < 0.64e-3


def _rows(frac):
    return [{"decode_experts_read_frac": f} for f in frac]


BODY = "jit(train_step)/rollout/while/body/closed_call/moe_experts/"


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("with_kernel", [True, False])
def test_the_readers_take_the_kernels_events_and_nothing_else(
        cell, with_kernel, monkeypatch):
    """The events named `moe_decode`; not the attention kernel, not the other
    operations under `moe_experts` (the update's grouped matmuls, the casts).
    A program without the kernel (the batched matmuls, the parent) reads
    nothing for the kernel's two, and no error; one without the counter
    nothing at all."""
    ctx = _Ctx(cell)
    events = [(10.0, BODY + "dot_general", False),
              (5.0, "jit(train_step)/rollout/while/body/mla/mla_decode", True),
              (3.0, "jit(train_step)/jvp(advantage)/vtrace", True)]
    if with_kernel:
        events += [(20.0, BODY + "moe_decode/pallas_call", True),
                   (15.0, BODY + "moe_decode/pallas_call", True)]
    monkeypatch.setattr(phases, "steps_of", lambda run, ctx: [{}])
    monkeypatch.setattr(phases, "_step_events_of",
                        lambda path, module: ((100.0, events),))
    run = {"device": {"kind": "TPU v5 lite", "count": 1}, "trace_path": "none",
           "rows": _rows([0.5, 0.75])}
    read = {name: harness.load_module("layers", name).read(run, ctx) for name in READERS}
    assert read["decode_experts_read_pct"] == pytest.approx(62.5)
    if not with_kernel:
        assert read["moe_decode_ms"] is None and read["moe_decode_roofline_pct"] is None
    else:
        assert read["moe_decode_ms"] == pytest.approx(35.0 / 1e6)
        least_s = _count().roofline_s(
            ctx.config["network"], harness.cell_settings(ctx),
            ctx.param("env_set", {}).get("prefill_len", 0), 0.625, PEAK, BANDWIDTH)
        assert read["moe_decode_roofline_pct"] == pytest.approx(
            100.0 * least_s * 1e3 / read["moe_decode_ms"])
    bare = {**run, "rows": [{"loss": 0.1}]}
    assert all(harness.load_module("layers", name).read(bare, ctx) is None
               for name in ("decode_experts_read_pct", "moe_decode_roofline_pct"))


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("frac", [0.25, 0.65625, 1.0])
def test_the_share_cannot_pass_100_where_the_kernel_streams_at_the_bandwidth(
        cell, frac, monkeypatch):
    """A kernel that moves exactly the needed bytes at the chip's whole
    bandwidth reads 100, whatever share of the experts was chosen: the share
    counts the bytes the counter says were read, not every held expert's. One
    that takes the time of all sixteen at the bandwidth reads the share
    chosen."""
    ctx = _Ctx(cell)
    settings = harness.cell_settings(ctx)
    prefill = ctx.param("env_set", {}).get("prefill_len", 0)
    run = {"device": {"kind": "TPU v5 lite", "count": 1}, "trace_path": "none",
           "rows": _rows([frac])}
    monkeypatch.setattr(phases, "steps_of", lambda run, ctx: [{}])
    for read_all, want in [(False, 100.0), (True, None)]:
        _, moved = _count().needed(ctx.config["network"], settings, prefill,
                                   1.0 if read_all else frac)
        events = [(moved / BANDWIDTH * 1e9, BODY + "moe_decode/pallas_call", True)]
        monkeypatch.setattr(phases, "_step_events_of",
                            lambda path, module, events=events: ((1.0, events),))
        share = harness.load_module("layers", "moe_decode_roofline_pct").read(run, ctx)
        assert share <= 100.0 + 1e-9
        if want:
            assert share == pytest.approx(want)
        else:
            assert 100.0 * frac - 1e-9 <= share <= 100.0 * frac + 1.0   # + the rows


@pytest.mark.parametrize("cell", list(CELLS))
def test_both_token_cells_read_the_three_readers_once_a_manifest_lists_them(
        cell, monkeypatch):
    workload = harness.load_json("workloads", f"{cell}.json")
    accepted = harness.per_layer_names(workload)
    assert not set(accepted) & set(READERS)
    later = json.loads(json.dumps(harness.load_manifest()))
    for name, (unit, source) in READERS.items():
        mod = harness.load_module("layers", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            "sequence policy", unit, source, "fused_steps_per_s")
        later["per_layer"].append({
            "name": name, "unit": unit, "source": mod.SOURCE, "layer": mod.LAYER,
            "better": "lower" if unit == "ms" else "higher",
            "moves": mod.MOVES, "workloads": list(CELLS)})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(workload) == accepted + list(READERS)
    fleet = harness.load_json("workloads", "impala_pong.fleet.json")
    assert not set(harness.per_layer_names(fleet)) & set(READERS)
