"""The decode attention kernel's two readers (`benchmark/layers/
mla_decode_ms.py`, `mla_decode_roofline_pct.py`) and the count they divide by
(`benchmark/kernels/mla_decode.py`): the needed work against a hand count, the
readers on a hand-made trace, and that the token cell reads them once a
manifest lists them (they are files, not entries: PERF.md section 7)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, phases  # noqa: E402

CELL = "impala_joyai_flash.seq512"
READERS = {"mla_decode_ms": "ms", "mla_decode_roofline_pct": "%"}
PEAK, BANDWIDTH = 197e12, 819e9


def _network():
    return harness.load_json("configs", "impala_joyai_flash.json")["network"]


def _count():
    return harness.load_module("kernels", "mla_decode")


def test_needed_work_of_the_decode_attention_against_a_hand_count():
    n = _network()
    ops, moved = _count().needed(n, {"rollout_steps": 512, "num_envs": 64})
    # Positions read over a row's 512 steps at a grain of 128: steps 0-127
    # read one block, 128-255 two, ...: 128 x (128 + 256 + 384 + 512).
    positions = 128 * (128 + 256 + 384 + 512)
    assert positions == 163_840                 # 62.5% of 512 x 512
    latents = positions * (512 + 64) * 2        # both caches, bf16
    queries = 512 * 32 * (512 + 64) * 2
    output = 512 * 32 * 512 * 4
    assert moved == 5 * 64 * (latents + queries + output) == 77_175_193_600
    # Scores over rank + rope, values over rank, two a multiply-accumulate.
    assert ops == 5 * 2 * 64 * 32 * positions * (512 + 64 + 512)
    least = _count().roofline_s(n, {"rollout_steps": 512, "num_envs": 64},
                                PEAK, BANDWIDTH)
    # Bound by the bandwidth, five times over: 32 queries a row reuse a block.
    assert least == moved / BANDWIDTH and moved / BANDWIDTH > 5 * ops / PEAK
    assert 0.094 < least < 0.095


@pytest.mark.parametrize("T, positions", [
    (1, 128), (128, 128 * 128), (130, 128 * 128 + 2 * 256)])
def test_the_prefix_is_counted_in_whole_blocks_of_positions(T, positions):
    n = {**_network(), "num_hidden_layers": 1}
    ops, moved = _count().needed(n, {"rollout_steps": T, "num_envs": 1})
    assert ops == 2 * 32 * positions * 1088
    assert moved == positions * 576 * 2 + T * (32 * 576 * 2 + 32 * 512 * 4)
    # float32 caches move twice the latents and the same output.
    _, wide = _count().needed({**n, "compute_dtype": "float32"},
                              {"rollout_steps": T, "num_envs": 1})
    assert wide == positions * 576 * 4 + T * (32 * 576 * 4 + 32 * 512 * 4)


class _Ctx:
    def __init__(self):
        self.config = {"network": _network(),
                       "algorithm": {"rollout_steps": 512, "num_envs": 64}}

    def param(self, key, default=None):
        return {"step_module": "jit_train_step"}.get(key, default)


BODY = "jit(train_step)/rollout/while/body/"


@pytest.mark.parametrize("with_kernel", [True, False])
def test_the_readers_take_the_kernels_events_and_nothing_else(with_kernel, monkeypatch):
    """The events named `mla_decode`, one a layer; not the advantage kernel,
    not the other operations under `mla`. A program without the kernel (the
    einsum path, the parent) reads nothing, and no error."""
    # One whole step: (self time ns, name stack, is a kernel).
    events = [(10.0, BODY + "mla/dot_general", False),
              (3.0, "jit(train_step)/jvp(advantage)/vtrace", True)]
    if with_kernel:
        events += [(20.0, BODY + "mla/mla_decode", True),
                   (15.0, BODY + "mla/mla_decode", True)]
    monkeypatch.setattr(phases, "steps_of", lambda run, ctx: [{}])
    monkeypatch.setattr(phases, "_step_events_of",
                        lambda path, module: ((100.0, events),))
    run = {"device": {"kind": "TPU v5 lite", "count": 1}, "trace_path": "none"}
    ms = harness.load_module("layers", "mla_decode_ms").read(run, _Ctx())
    share = harness.load_module("layers", "mla_decode_roofline_pct").read(run, _Ctx())
    if not with_kernel:
        assert ms is None and share is None
        return
    assert ms == pytest.approx(35.0 / 1e6)
    least_ms = 77_175_193_600 / BANDWIDTH * 1e3
    assert share == pytest.approx(100.0 * least_ms / ms)


def test_the_token_cell_reads_the_two_readers_once_a_manifest_lists_them(monkeypatch):
    cell = harness.load_json("workloads", f"{CELL}.json")
    accepted = harness.per_layer_names(cell)
    assert not set(accepted) & set(READERS)
    later = json.loads(json.dumps(harness.load_manifest()))
    for name, unit in READERS.items():
        mod = harness.load_module("layers", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            "sequence policy", unit, "device_trace", "fused_steps_per_s")
        later["per_layer"].append({
            "name": name, "unit": unit, "source": mod.SOURCE, "layer": mod.LAYER,
            "better": "lower" if unit == "ms" else "higher",
            "moves": mod.MOVES, "workloads": [CELL]})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(cell) == accepted + list(READERS)
    fleet = harness.load_json("workloads", "impala_pong.fleet.json")
    assert not set(harness.per_layer_names(fleet)) & set(READERS)
