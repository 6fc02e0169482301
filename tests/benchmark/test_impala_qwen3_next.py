"""The `impala_qwen3_next` configuration's own benchmark files: its cell
rehearsed on the CPU, its operation counts and its delta rule's roofline
against a hand count, its readers on hand-made rows and traces and once a
manifest lists them, its file against the catalog's keys, its reference apart
from the program, and the preset against the file."""

import dataclasses
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

CELL = "impala_qwen3_next.ctx16384"
# The configuration's own readers: files and NOT manifest entries, for the
# reason `test_impala_joyai_flash.py` gives (PERF.md section 7).
OWN_READERS = {
    "attn_linear_ms": ("ms", "device_trace"),
    "gdn_scan_ms": ("ms", "device_trace"),
    "gdn_scan_roofline_pct": ("%", "device_trace"),
    "gdn_chunk_logdecay_min": ("ln", "program_counter"),
}
# The token cells' shared file readers, which read this cell too once listed.
SHARED_READERS = ("attn_full_ms", "prefill_ms", "prefill_pct", "moe_route_ms",
                  "moe_experts_ms", "moe_decode_ms", "decode_experts_read_pct",
                  "lm_head_ms", "routed_here_pct", "expert_load_max_over_mean")
PEAK, BANDWIDTH = 197e12, 819e9


def _config():
    return harness.load_json("configs", "impala_qwen3_next.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_hybrid_cell(trace):
    """`impala_qwen3_next.ctx16384` through train.main at toy widths (16 of
    512 experts held, top-10, a prompt prefix prefilled in one pass): the
    check compares targets, loss and logits with the plain reference, and on
    the tree no router decides the causal pass's logits and the rollout's own
    behaviour log-probabilities."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    try:
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
             "2147483671", "--seconds", "1", "--trace", str(trace), "--rehearsal"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
            preexec_fn=lambda: os.nice(10))
    finally:
        shutil.rmtree(os.path.join(ROOT, ".bench_scratch", CELL), ignore_errors=True)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REHEARSAL")]
    would = json.loads(lines[-1].split("would print: ", 1)[1])
    assert would["correct"] is True and would["failed"] == 0 and would["attempted"] > 0
    compared = would["compared"]
    assert list(compared)[:5] == [
        "adv_err", "loss_err", "logits_err", "logits_attn_err", "decode_logp_err"]
    assert all(0 <= number <= limit for number, limit in compared.values())
    m = would["metrics"]
    if not trace:
        assert set(m) == {"fused_steps_per_s", "setup_s"}
        return
    assert m["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    assert {"cache_miss_count", "adv_kernel_calls", "enqueue_ms"} <= set(m)
    assert "mfu_pct" not in m and not set(m) & set(OWN_READERS)


def test_the_rehearsal_starts_its_window_at_the_first_row():
    """A one-second window at toy widths on a loaded machine may hold rows
    that all fall within a lead of the first: the rehearsal has none, so
    `harness.window_start` always finds its row."""
    rehearsal = harness.load_json("traffic", "ctx16384.json")["rehearsal"]
    assert rehearsal["lead_s"] == 0.0 and rehearsal["calibration_iterations"] >= 4
    assert harness.window_start([5.0, 5.001], rehearsal["lead_s"]) == 0


def test_the_cell_reads_the_accepted_entries_and_its_own_readers_once_listed(
        monkeypatch):
    manifest = harness.load_manifest()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == "impala_qwen3_next")
    assert len(config["why"]) <= 200
    assert config["file"] == "benchmark/configs/impala_qwen3_next.json"
    accepted = harness.per_layer_names(cell)
    token = harness.load_json("workloads", "impala_keye_vl2.ctx8192.json")
    assert accepted == harness.per_layer_names(token)
    later = json.loads(json.dumps(manifest))
    for name, (unit, source) in OWN_READERS.items():
        mod = harness.load_module("layers", name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            "sequence policy", unit, source, "fused_steps_per_s")
        later["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [CELL]})
    for name in SHARED_READERS:
        mod = harness.load_module("layers", name)
        later["per_layer"].append({
            "name": name, "unit": mod.UNIT, "better": "lower", "source": mod.SOURCE,
            "layer": mod.LAYER, "moves": mod.MOVES, "workloads": [token["name"], CELL]})
    monkeypatch.setattr(harness, "load_manifest", lambda: later)
    assert harness.per_layer_names(cell) == accepted + list(OWN_READERS) + list(
        SHARED_READERS)
    assert not set(harness.per_layer_names(token)) & set(OWN_READERS)


def _trace_of_one_step(ops):
    modules = [["jit_train_step(7)", 0.0, 90.0], ["jit_train_step(7)", 100.0, 100.0],
               ["jit_train_step(7)", 210.0, 90.0]]
    edge = [["%add.1", 0.0, 5.0, {"tf_op": "jit(train_step)/rollout/add"}],
            ["%add.2", 290.0, 5.0, {"tf_op": "jit(train_step)/rollout/add"}]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.MODULES_LINE, "events": modules},
        {"name": trace_reduce.OPS_LINE, "events": edge + ops}]}]}


def _clear():
    for cached in (phases.load, phases._step_events_of, phases._steps_of):
        cached.cache_clear()


@pytest.fixture
def as_run(monkeypatch, tmp_path):
    """A `run` dict whose `trace_path` gives a plain trace (the file is a
    stand-in; `phases.load` is what reads it), as `test_phases.py` has it."""
    def make(trace):
        path = tmp_path / f"t{len(os.listdir(tmp_path))}.xplane.pb"
        path.write_bytes(b"")
        _clear()
        monkeypatch.setattr(trace_reduce, "load_xplane", lambda p, **kw: trace)
        return {"trace_path": str(path), "device": {"kind": "TPU v5 lite"}}
    yield make
    _clear()


OPS = [
    ["%fusion.1", 100.0, 10.0,
     {"tf_op": "jit(train_step)/rollout/while/body/attn_linear/dot_general"}],
    ["%fusion.2", 110.0, 2.0,
     {"tf_op": "jit(train_step)/rollout/while/body/attn_linear/gdn_scan/mul"}],
    ["%fusion.3", 120.0, 3.0, {"tf_op": "jit(train_step)/rollout/prefill/checkpoint/"
                                        "attn_linear/while/body/gdn_scan/while/body/dot"}],
    ["%fusion.4", 130.0, 4.0, {"tf_op": "jit(train_step)/jvp(forward)/attn_linear/mul"}],
    ["%fusion.5", 140.0, 5.0, {"tf_op": "jit(train_step)/transpose(jvp(forward))"
                                        "/jvp(attn_linear)/jvp(gdn_scan)/dot_general"}],
    ["%fusion.6", 150.0, 9.0, {"tf_op": "jit(train_step)/jvp(forward)/attn_full/dot"}],
]


def _ctx(trace=True):
    config = _config()
    traffic = harness.load_json("traffic", "ctx16384.json")
    return harness.Ctx({"rate_metric": "fused_steps_per_s", "name": CELL}, config,
                       {**traffic, "step_module": "jit_train_step"}, 0, 1.0, trace,
                       False, "")


@pytest.mark.parametrize("reader, want", [
    ("attn_linear_ms", 10.0 + 2.0 + 3.0 + 4.0 + 5.0), ("gdn_scan_ms", 2.0 + 3.0 + 5.0)])
def test_scope_readers_find_their_scope_at_any_depth(reader, want, as_run):
    """The delta rule's scope sits inside the linear half's, in the decode
    loop, inside the prefill and in the update's backward. A program without
    the scopes reads nothing."""
    mod = harness.load_module("layers", reader)
    assert mod.read(as_run(_trace_of_one_step(OPS)), _ctx()) == pytest.approx(want / 1e6)
    assert mod.read(as_run(_trace_of_one_step(OPS[-1:])), _ctx()) is None


def test_the_roofline_share_is_the_least_time_over_the_scopes(as_run):
    mod = harness.load_module("layers", "gdn_scan_roofline_pct")
    ctx = _ctx()
    least = harness.load_module("kernels", "gdn_scan").roofline_s(
        ctx.config["network"], harness.cell_settings(ctx), 14336, PEAK, BANDWIDTH)
    want = 100.0 * least * 1e3 / (10.0 / 1e6)
    assert mod.read(as_run(_trace_of_one_step(OPS)), ctx) == pytest.approx(want)
    assert mod.read(as_run(_trace_of_one_step(OPS[-1:])), ctx) is None


def test_the_decay_counter_reads_the_windows_least():
    mod = harness.load_module("layers", "gdn_chunk_logdecay_min")
    rows = [{"gdn_chunk_logdecay_min": -310.5, "iter": 1},
            {"gdn_chunk_logdecay_min": -420.25, "iter": 2}, {"iter": 3}]
    assert mod.read({"rows": rows}, None) == -420.25
    # The parent's rows lack the counter: nothing, and no error.
    assert mod.read({"rows": [{"iter": 1}]}, None) is None


def test_flops_of_the_decoder_against_a_hand_count():
    n = _config()["network"]
    kind = harness.load_module("networks", "gdn_gated_moe")
    # A linear layer: in_proj_qkvz 2,048 x 12,288, in_proj_ba 2,048 x 64,
    # out_proj 4,096 x 2,048; the conv 4 x 8,192.
    linear = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert kind.linear_projection_macs(n) == linear == 33_685_504
    assert kind.conv_macs(n) == 32_768
    # The chunked delta rule at C = 64, a position and value head: q k^T and
    # k k^T 64 x 128 each, the solve 64 x 256 / 2, W S, q S, k^T v' 128 x 128
    # each, attn v' 64 x 128; 32 value heads.
    rule = 32 * (2 * 64 * 128 + 64 * 256 / 2 + 3 * 128 * 128 + 64 * 128)
    assert kind.delta_rule_macs(n) == rule == 2_621_440
    # The full layer: q and its gate 2,048 x 4,096 each, k and v 2,048 x 512
    # each, o 4,096 x 2,048; scores and values over (T + 1) / 2 keys on
    # average, 16 heads of 256.
    full = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert kind.full_projection_macs(n) == full
    attention = 16 * (16384 + 1) / 2 * 2 * 256
    assert kind.attention_macs(n, 16384) == attention
    # Every layer: the router 2,048 x 512, 10 x 16 / 512 routed experts of
    # 3 x 2,048 x 512, the shared expert of 512 and its gate.
    expert = 3 * 2048 * 512
    ffn = 2048 * 512 + 10 * 16 / 512 * expert + expert + 2048
    head = 2048 * 18992 + 2048
    want = 2 * (3 * (linear + 32_768 + rule) + (full + attention) + 4 * ffn + head)
    assert flops.forward_flops(n) == pytest.approx(want, rel=1e-12)
    assert 0.52e9 < flops.forward_flops(n) < 0.53e9
    settings = {"rollout_steps": 16384, "num_envs": 2}
    assert flops.per_decision(n, settings) == pytest.approx(4 * want, rel=1e-12)


def test_the_delta_rules_roofline_against_a_hand_count():
    """Per phase the larger of operations over the peak and bytes over the
    bandwidth: the decode is bound by the float32 state read and written
    every step, and the causal passes by their bytes too (159 operations a
    byte of q, k, v, g, beta and o, under the chip's 240)."""
    count = harness.load_module("kernels", "gdn_scan")
    n = _config()["network"]
    settings = {"rollout_steps": 16384, "num_envs": 2}
    got = count.phases(n, settings, 14336)
    # A position of a linear layer: q and k 16 x 128 bf16, v 32 x 128 bf16,
    # g and beta 32 float32, o 32 x 128 float32.
    per = 2 * 16 * 128 * 2 + 32 * 128 * 2 + 2 * 32 * 4 + 32 * 128 * 4
    assert count.position_bytes(n) == per == 33_024
    steps = 2048 * 2 * 3
    assert got["decode"] == (steps * 2 * 32 * 3 * 128 * 128,
                             steps * (per + 2 * 32 * 128 * 128 * 4))
    rule = 2 * 2_621_440
    assert got["prefill"] == (14336 * 2 * 3 * rule, 14336 * 2 * 3 * per)
    forward = (16384 * 2 * 3 * rule, 16384 * 2 * 3 * per)
    assert got["update_forward"] == got["update_recompute"] == forward
    assert got["update_backward"] == (2 * forward[0], 2 * forward[1])
    least = count.roofline_s(n, settings, 14336, PEAK, BANDWIDTH)
    decode = steps * (per + 2 * 32 * 128 * 128 * 4) / BANDWIDTH
    causal = (14336 + 4 * 16384) * 2 * 3 * per / BANDWIDTH
    assert rule / per < PEAK / BANDWIDTH
    assert least == pytest.approx(decode + causal, rel=1e-12)
    assert 0.06 < decode < 0.07 and 0.019 < causal < 0.02


def test_the_configuration_file_keeps_the_catalogs_keys():
    """Every number of the published config (the catalog's keys) is in the
    file under the same key, unchanged unless `reduced` lists it; no width
    is listed."""
    cfg = _config()
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
    }
    assert cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                              "vocab_size"}
    assert all(cfg["published"][k] == published[k] for k in cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["num_experts"] * 32 == published["num_experts"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    manifest = next(c for c in harness.load_manifest()["configs"]
                    if c["name"] == "impala_qwen3_next")
    assert manifest["reduced"] == cfg["reduced"] and manifest["source"] == cfg["source"]
    traffic = harness.load_json("traffic", "ctx16384.json")
    assert traffic["set"] == {"num_envs": 2, "rollout_steps": 16384}
    assert traffic["env_set"] == {"horizon": 16384, "prompt_min": 14336,
                                  "prompt_max": 15360, "prefill_len": 14336}
    assert traffic["env_set"]["prefill_len"] % cfg["network"]["chunk"] == 0
    for key, why in cfg["assumed"].items():
        assert len(why) > 40, key
    assert {"no_mtp", "gdn_init", "state_float32", "chunk", "value_head", "task",
            "initialization", "optimizer"} <= set(cfg["assumed"])


def test_the_preset_is_the_configuration_the_benchmark_states():
    """Every width of the file's `network` group is the preset's, and the
    catalog's keys at the file's top level agree with it."""
    from actor_critic_tpu import config as config_mod
    from actor_critic_tpu.models import gated_delta

    cfg = _config()
    preset = config_mod.PRESETS["impala_qwen3_next"]
    seq, net = preset.config.seq, cfg["network"]
    fields = {f.name for f in dataclasses.fields(seq)}
    for key, value in net.items():
        if key in fields:
            assert getattr(seq, key) == value, key
    assert net["chunk"] == gated_delta.CHUNK
    assert net["vocab_size"] == preset.env_kwargs["vocab_size"] == cfg["vocab_size"]
    assert cfg["num_experts"] == seq.experts_held == 16
    assert cfg["published"]["num_experts"] == seq.n_routed_experts == 512
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "partial_rotary_factor", "full_attention_interval",
                "linear_conv_kernel_dim", "linear_key_head_dim", "linear_num_key_heads",
                "linear_num_value_heads", "linear_value_head_dim", "rope_theta",
                "num_hidden_layers", "rms_norm_eps"):
        assert cfg[key] == net[key] == getattr(seq, key), key
    assert (preset.config.num_envs, preset.config.rollout_steps) == (2, 16384)
    assert preset.env_kwargs["prefill_len"] == 14336 and seq.compute_dtype == "bfloat16"


def test_the_reference_imports_nothing_of_the_programs_model():
    with open(os.path.join(ROOT, "benchmark/reference/impala_qwen3_next.py")) as fh:
        source = fh.read()
    assert "actor_critic_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "lax.scan" in source and "lax.map" in source
    assert "chunk" not in source.split('"""', 2)[2]
