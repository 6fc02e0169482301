"""The `impala_mellum2` configuration's own benchmark files: its cell rehearsed
on the CPU, its operation counts against a hand count, its readers on
hand-made rows and traces, its file against the catalog's keys."""

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

CELL = "impala_mellum2.ctx4096"
# The configuration's own readers: files and NOT manifest entries, for the
# reason `test_impala_joyai_flash.py` gives (PERF.md section 7). What a
# `benchmark` PR lists once the two harness tests are repaired:
OWN_READERS = {
    "attn_window_ms": ("ms", "device_trace"),
    "attn_full_ms": ("ms", "device_trace"),
    "prefill_ms": ("ms", "device_trace"),
    "window_kept_pct": ("%", "program_counter"),
    "prefill_pct": ("%", "program_counter"),
}
# The token cells' shared readers, which read this cell too once listed (the
# scopes `moe_route`, `moe_experts`, `lm_head` and the rows' counters are the
# standing ones).
SHARED_READERS = ("moe_experts_ms", "moe_route_ms", "lm_head_ms", "routed_here_pct",
                  "expert_load_max_over_mean", "response_tokens_pct")


def _config():
    return harness.load_json("configs", "impala_mellum2.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_long_context_cell(trace):
    """`impala_mellum2.ctx4096` through train.main at toy widths (every count
    of the block as published: 32 query heads over 4 key/value heads, three
    window layers to one full, 64 experts, 16 held, top-8; a prompt prefix
    prefilled in one pass): the check compares targets, loss and logits with
    the plain reference, and on the tree no router decides the causal pass's
    logits and the rollout's own behaviour log-probabilities."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    try:
        # At a low priority: the harness's own rehearsals run beside this one
        # in other workers, and a background compile that a busy machine
        # delays lands inside their window of a second. This cell's rehearsal
        # has no background compile (`--no-warmup` in its traffic file).
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
             "2147483659", "--seconds", "1", "--trace", str(trace), "--rehearsal"],
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


def test_the_cell_reads_the_accepted_entries_and_its_own_readers_once_listed(
        monkeypatch):
    manifest = harness.load_manifest()
    cell = harness.load_json("workloads", f"{CELL}.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    accepted = harness.per_layer_names(cell)
    token = harness.load_json("workloads", "impala_joyai_flash.seq512.json")
    assert accepted == harness.per_layer_names(token) and len(accepted) == 14
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
        return {"trace_path": str(path)}
    yield make
    _clear()


@pytest.mark.parametrize("reader, want", [
    ("attn_window_ms", 10.0 + 3.0 + 4.0), ("attn_full_ms", 20.0),
    ("prefill_ms", 3.0 + 7.0)])
def test_scope_readers_find_their_scope_at_any_depth(reader, want, as_run):
    """In the decode loop, inside the prefill, in the update's forward and
    its backward; a program without the scope reads nothing."""
    ops = [
        ["%fusion.1", 100.0, 10.0,
         {"tf_op": "jit(train_step)/rollout/while/body/attn_window/dot_general"}],
        ["%fusion.2", 110.0, 20.0,
         {"tf_op": "jit(train_step)/rollout/while/body/attn_full/dot_general"}],
        ["%fusion.3", 130.0, 3.0,
         {"tf_op": "jit(train_step)/rollout/prefill/checkpoint/attn_window/mul"}],
        ["%fusion.4", 133.0, 7.0,
         {"tf_op": "jit(train_step)/rollout/prefill/moe_experts/ragged_dot"}],
        ["%fusion.5", 140.0, 4.0, {"tf_op": "jit(train_step)/transpose(jvp(forward))"
                                            "/jvp(attn_window)/dot_general"}],
        ["%fusion.6", 150.0, 9.0, {"tf_op": "jit(train_step)/jvp(forward)/mla/dot_general"}],
    ]
    ctx = harness.Ctx({"rate_metric": "fused_steps_per_s", "name": "t"}, {},
                      {"step_module": "jit_train_step"}, 0, 1.0, True, False, "")
    mod = harness.load_module("layers", reader)
    assert mod.read(as_run(_trace_of_one_step(ops)), ctx) == pytest.approx(want / 1e6)
    assert mod.read(as_run(_trace_of_one_step(ops[-1:])), ctx) is None


@pytest.mark.parametrize("reader, key", [
    ("window_kept_pct", "window_kept_frac"), ("prefill_pct", "prefill_frac")])
def test_counter_readers_take_the_mean_over_the_windows_rows(reader, key):
    mod = harness.load_module("layers", reader)
    rows = [{key: 0.25, "iter": 1}, {key: 0.75, "iter": 2}, {"iter": 3}]
    assert mod.read({"rows": rows}, None) == pytest.approx(50.0)
    # The parent's rows lack the counter: nothing, and no error.
    assert mod.read({"rows": [{"iter": 1}]}, None) is None


def test_flops_of_the_decoder_against_a_hand_count():
    n = _config()["network"]
    kind = harness.load_module("networks", "gqa_window_moe")
    # Projections, multiply-accumulates a token: q and o 2304 x 4096 each,
    # k and v 2304 x 512 each.
    projections = 2 * 9_437_184 + 2 * 1_179_648
    assert projections == 21_233_664 == kind.projection_macs(n)
    # Keys a query meets, summed over a row of 4,096: a window layer's band
    # 1024 x 1025 / 2 + 3072 x 1024, a full layer's prefix 4096 x 4097 / 2.
    band, prefix = 524_800 + 3_145_728, 8_390_656
    assert (band, prefix) == (3_670_528, 8_390_656)
    assert kind.keys_a_query(n, "sliding_attention", 4096) == band / 4096
    assert kind.keys_a_query(n, "full_attention", 4096) == prefix / 4096
    assert band / prefix == pytest.approx(0.4375, abs=1e-4)
    assert kind.window_kept(n, 4096) == pytest.approx(0.4375 * 0.75 + 0.25, abs=1e-4)
    scores = 32 * 2 * 128 * (3 * band + prefix) / 4096     # all four layers
    expert = 3 * 2304 * 896                                # 6,193,152
    sparse = 2304 * 64 + 8 * (16 / 64) * expert
    head = 2304 * 24576 + 2304
    macs = 4 * projections + scores + 4 * sparse + head
    assert flops.forward_flops(n) == pytest.approx(2 * macs, rel=1e-12)
    assert 0.45e9 < flops.forward_flops(n) < 0.47e9
    # About a sixth of a position's operations are attention's scores (a
    # third would be with full layers only).
    assert 0.15 < 2 * scores / flops.forward_flops(n) < 0.18
    settings = {"rollout_steps": 4096, "num_envs": 8}
    # Rollout 1 forward (prefilled or decoded), update 3; nothing rematerialized.
    assert flops.per_decision(n, settings) == pytest.approx(8 * macs, rel=1e-12)
    # A shorter row meets fewer keys.
    assert flops.per_decision(n, {"rollout_steps": 512}) < flops.per_decision(n, settings)


def test_the_configuration_file_keeps_the_catalogs_keys():
    """Every number of the source's config.json is in the file under the same
    key, unchanged unless `reduced` lists it; nested groups are whole; no
    width is listed."""
    cfg = _config()
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "moe_intermediate_size": 896, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024, "vocab_size": 98304,
    }
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert all(cfg["published"][k] == published[k] for k in cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (
        4, 16, 24576)
    assert cfg["vocab_size"] * 4 == published["vocab_size"]
    assert cfg["num_experts"] * 4 == published["num_experts"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert (cfg["attention_bias"], cfg["norm_topk_prob"], cfg["tie_word_embeddings"],
            cfg["use_sliding_window"], cfg["hidden_act"], cfg["model_type"]) == (
        False, True, False, True, "silu", "mellum")
    manifest = next(c for c in harness.load_manifest()["configs"]
                    if c["name"] == "impala_mellum2")
    assert manifest["reduced"] == cfg["reduced"] and manifest["source"] == cfg["source"]
    traffic = harness.load_json("traffic", "ctx4096.json")
    assert traffic["set"] == {"num_envs": 8, "rollout_steps": 4096}
    assert traffic["env_set"] == {"horizon": 4096, "prompt_min": 3072,
                                  "prompt_max": 3584, "prefill_len": 3072}


def test_the_reference_imports_nothing_of_the_programs_model():
    with open(os.path.join(ROOT, "benchmark/reference/impala_mellum2.py")) as fh:
        source = fh.read()
    assert "actor_critic_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source


@pytest.mark.parametrize("T, E", [(1, 3), (7, 2), (33, 5)])
def test_the_references_vtrace_is_the_standing_references_recursion(T, E):
    """`reference/impala_mellum2.py::vtrace` is a reverse `lax.scan` (4,096
    unrolled steps took the compiler a quarter of an hour, and the check with
    them the run's whole time limit); `reference/impala_pong.py::vtrace`
    unrolls the same recursion as a Python loop. Same targets, with episode
    ends inside the rows, both clips biting and a bootstrap that is not zero."""
    import jax
    import numpy as np

    ours = harness.load_module("reference", "impala_mellum2").vtrace
    theirs = harness.load_module("reference", "impala_pong").vtrace
    keys = jax.random.split(jax.random.key(T * 31 + E), 6)
    target, behaviour, rewards, values = (
        jax.random.normal(k, (T, E)) for k in keys[:4])
    dones = (jax.random.uniform(keys[4], (T, E)) < 0.3).astype("float32")
    bootstrap = jax.random.normal(keys[5], (E,))
    got = ours(target, behaviour, rewards, values, dones, bootstrap, 0.97, 1.0, 0.9, 0.95)
    want = theirs(target, behaviour, rewards, values, dones, bootstrap, 0.97, 1.0, 0.9, 0.95)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)
