"""The `impala_joyai_flash` configuration's own benchmark files: its cell
rehearsed on the CPU, its operation counts against a hand count, its readers
on hand-made rows."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness, phases, trace_reduce  # noqa: E402

CELL = "impala_joyai_flash.seq512"
# The configuration's own readers. They are files and NOT manifest entries:
# two of the harness's tests (test_benchmark_harness.py, `accepted = [e["name"]
# for e in manifest["per_layer"]]`) hold every entry of `per_layer` to list
# both pixel fleets, which an entry for this cell alone cannot (PERF.md
# section 7). What a `benchmark` PR lists once those lines are repaired:
OWN_READERS = {
    "moe_experts_ms": ("ms", "device_trace"),
    "moe_route_ms": ("ms", "device_trace"),
    "mla_ms": ("ms", "device_trace"),
    "lm_head_ms": ("ms", "device_trace"),
    "moe_experts_roofline_pct": ("%", "device_trace"),
    "routed_here_pct": ("%", "program_counter"),
    "expert_load_max_over_mean": ("ratio", "program_counter"),
    "response_tokens_pct": ("%", "program_counter"),
}


def _config():
    return harness.load_json("configs", "impala_joyai_flash.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_token_cell(trace):
    """`impala_joyai_flash.seq512` through train.main at toy widths (every
    count of the block as published: 32 heads, 256 experts, 16 held, top-8):
    the check compares targets, loss and logits (of the whole model and of
    its dense layers alone) with the plain reference, and a traced run reads
    what the manifest lists for the cell."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    try:
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
             "2147483659", "--seconds", "1", "--trace", str(trace), "--rehearsal"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    finally:
        shutil.rmtree(os.path.join(ROOT, ".bench_scratch", CELL), ignore_errors=True)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REHEARSAL")]
    would = json.loads(lines[-1].split("would print: ", 1)[1])
    assert would["correct"] is True and would["failed"] == 0 and would["attempted"] > 0
    compared = would["compared"]
    assert list(compared)[:4] == [
        "adv_err", "loss_err", "logits_err", "logits_dense_err"]
    assert all(0 <= number <= limit for number, limit in compared.values())
    m = would["metrics"]
    if not trace:
        assert set(m) == {"fused_steps_per_s", "setup_s"}
        return
    # What a CPU trace can give of the accepted entries the cell is appended
    # to (the device-time readers find no TPU plane and stay silent); the
    # configuration's own readers are not listed, so not read.
    assert m["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    assert {"cache_miss_count", "adv_kernel_calls", "enqueue_ms"} <= set(m)
    assert "mfu_pct" not in m and not set(m) & set(OWN_READERS)


def test_the_cell_reads_the_accepted_entries_and_its_own_readers_once_listed(
        monkeypatch):
    manifest = harness.load_manifest()
    cell = harness.load_json("workloads", f"{CELL}.json")
    accepted = harness.per_layer_names(cell)
    assert len(accepted) == 14 and "mfu_pct" in accepted
    assert not {"final_obs_ms", "truncated_rows_pct"} & set(accepted)
    assert not set(accepted) & set(OWN_READERS)
    later = json.loads(json.dumps(manifest))
    for name, (unit, source) in OWN_READERS.items():
        mod = harness.load_module("layers", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            "sequence policy", unit, source, "fused_steps_per_s")
        later["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL]})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(cell) == accepted + list(OWN_READERS)
    fleet = harness.load_json("workloads", "impala_pong.fleet.json")
    assert not set(harness.per_layer_names(fleet)) & set(OWN_READERS)


def _trace_of_one_step(ops):
    """A device plane with three executions of the step, the middle one
    whole, holding `ops`: [name, start, duration, stats]."""
    modules = [["jit_train_step(7)", 0.0, 90.0], ["jit_train_step(7)", 100.0, 100.0],
               ["jit_train_step(7)", 210.0, 90.0]]
    edge = [["%add.1", 0.0, 5.0, {"tf_op": "jit(train_step)/rollout/add"}],
            ["%add.2", 290.0, 5.0, {"tf_op": "jit(train_step)/rollout/add"}]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.MODULES_LINE, "events": modules},
        {"name": trace_reduce.OPS_LINE, "events": edge + ops}]}]}


def test_the_experts_reader_finds_the_grouped_kernels_by_name(monkeypatch):
    """Scope `moe_experts` at any depth, and the kernels XLA:TPU writes for a
    `ragged_dot` under its own name; no other kernel (a TopK, say)."""
    kernel = {"hlo_category": "custom-call"}
    ops = [
        ["%fusion.1", 100.0, 10.0,
         {"tf_op": "jit(train_step)/rollout/while/body/moe_experts/dot_general"}],
        ["%ragged-dot-none.3", 110.0, 20.0, {**kernel, "tf_op": "ragged-dot-none"}],
        ["%ragged-dot-metadata", 130.0, 1.0, {**kernel, "tf_op": "ragged-dot-metadata"}],
        ["%topk.1", 131.0, 7.0,
         {**kernel, "tf_op": "jit(train_step)/jvp(forward)/moe_route/top_k"}],
        ["%vtrace.1", 140.0, 3.0,
         {**kernel, "tf_op": "jit(train_step)/jvp(advantage)/vtrace"}],
        ["%convert.1", 150.0, 4.0, {"tf_op": "jit(train_step)/transpose(jvp(forward))"
                                             "/jvp(moe_experts)/convert_element_type"}],
    ]
    trace = _trace_of_one_step(ops)
    monkeypatch.setattr(phases, "steps_of", lambda run, ctx: [{}])
    monkeypatch.setattr(phases, "trace_of", lambda run: trace)

    class Ctx:
        def param(self, key, default=None):
            return {"step_module": "jit_train_step"}.get(key, default)

    got = harness.load_module("layers", "moe_experts_ms").read({}, Ctx())
    assert got == pytest.approx((10.0 + 20.0 + 1.0 + 4.0) / 1e6)


def test_flops_of_the_decoder_against_a_hand_count():
    n = _config()["network"]
    # MLA, multiply-accumulates a token: 2048x1536 + 1536x(32x192) + 2048x576
    # + 512x(32x256) + (32x128)x2048.
    mla = 3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608
    assert mla == 26_345_472
    attention = 32 * 256.5 * (192 + 128)          # mean causal context of 512
    dense = 3 * 2048 * 7168                       # 44,040,192
    expert = 3 * 2048 * 768                       # 4,718,592
    sparse = 2048 * 256 + expert + 8 * (16 / 256) * expert
    head = 2048 * 16160 + 2048
    macs = 5 * (mla + attention) + dense + 4 * sparse + head
    assert flops.forward_flops(n) == pytest.approx(2 * macs, rel=1e-12)
    assert 0.50e9 < flops.forward_flops(n) < 0.51e9
    settings = {"rollout_steps": 512, "num_envs": 64}
    # Rollout 1 forward, update 3: no bootstrap pass, nothing rematerialized.
    assert flops.per_decision(n, settings) == pytest.approx(8 * macs, rel=1e-12)


def test_roofline_of_the_held_experts_against_a_hand_count():
    n = _config()["network"]
    kind = harness.load_module("networks", "mla_moe")
    settings = {"rollout_steps": 512, "num_envs": 64}
    peak, bandwidth = 197e12, 819e9
    expert = 3 * 2048 * 768
    weights = 2 * 16 * expert                                  # bf16 bytes a layer
    row = 2 * (2 * 2048 + 3 * 768)
    # A decode step: 64 x 8 / 16 = 32 assignments, bound by the weights' bytes.
    step = max(2 * 32 * expert / peak, (weights + 32 * row) / bandwidth)
    assert step == (weights + 32 * row) / bandwidth
    # The update: 16,384 assignments, three passes, bound by compute.
    update = max(3 * 2 * 16384 * expert / peak,
                 3 * (weights + 16384 * row) / bandwidth)
    assert update == 3 * 2 * 16384 * expert / peak
    want = 4 * (512 * step + update)
    got = kind.held_experts_roofline_s(n, settings, 16 / 256, peak, bandwidth)
    assert got == pytest.approx(want, rel=1e-12)
    assert 0.38 < got < 0.40
    # Twice the share landing here: the update doubles, the rollout hardly moves.
    more = kind.held_experts_roofline_s(n, settings, 32 / 256, peak, bandwidth)
    assert 4 * update < more - got < 4 * update * 1.2


@pytest.mark.parametrize("reader, key, scale", [
    ("routed_here_pct", "routed_here_frac", 100.0),
    ("expert_load_max_over_mean", "expert_load_max_over_mean", 1.0),
    ("response_tokens_pct", "response_frac", 100.0),
])
def test_counter_readers_take_the_mean_over_the_windows_rows(reader, key, scale):
    mod = harness.load_module("layers", reader)
    rows = [{key: 0.25, "iter": 1}, {key: 0.75, "iter": 2}, {"iter": 3}]
    assert mod.read({"rows": rows}, None) == pytest.approx(0.5 * scale)
    # The parent's rows lack the counter: nothing, and no error.
    assert mod.read({"rows": [{"iter": 1}]}, None) is None


def test_the_configuration_file_keeps_the_catalogs_keys():
    """Every number of the source's config.json is in the file under the same
    key, unchanged unless `reduced` lists it; no width is listed."""
    cfg = _config()
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 32000000,
        "routed_scaling_factor": 2.5, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 129280, "ep_size": 1,
    }
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert all(cfg["published"][k] == published[k] for k in cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (5, 16, 16160)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
