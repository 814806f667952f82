"""The benchmark's Nemotron-H cell at a size a CPU test holds:
`fedbench/harness.run` end to end through the cell's own driver,
reference, work and metric files (`fedbench/tests/tiny_nemotron/`: one
period E, M, E, M, E, M, *, hidden 64, 4 of 16 experts held, 128
positions in chunks of 8), the control and the fault, the files
`BENCHMARK.json` names, the new readers, and the parent's refusal."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "fedbench")
TINY = os.path.join(BENCH, "tests", "tiny_nemotron", "manifest.json")
CELL = "tiny_nemotron_unc"
SOURCE = ("https://huggingface.co/nvidia/"
          "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# The public config.json's numbers (SOURCE)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
}
NEW_METRICS = ("ssm_scan_ms", "ssm_proj_ms", "ssm_scan_roofline")


def load(part, name):
    spec = importlib.util.spec_from_file_location(
        f"t_{part}_{name}", os.path.join(BENCH, part, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def registered():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "nemotron3_nano_ep16")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return manifest, entry, json.load(f)


def run(**kw):
    from fedbench import harness
    return harness.run(CELL, 2 ** 31 + 29, 0.5, False, manifest_path=TINY,
                       expect_platform=None, **kw)


@pytest.fixture(scope="module")
def sound():
    return run()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert {"round_ms", "setup_s"} <= set(sound["metrics"])
    checks = sound["checks"]
    assert set(checks) == {"loss_gap", "first_grad_gap", "first_grad_diff",
                           "change_gap", "upload_bytes_gap",
                           "compiles_in_window"}
    assert checks["upload_bytes_gap"]["value"] == 0.0
    assert checks["compiles_in_window"]["value"] == 0.0


@pytest.mark.parametrize("what", ["bf16", "half_batch"])
def test_control_and_fault_fail(what):
    result = run(**({"bf16": True} if what == "bf16"
                    else {"fault": "half_batch"}))
    assert not result["correct"]
    over = [k for k, c in result["checks"].items()
            if c["value"] > c["limit"]]
    assert over, result["checks"]


def test_registered_file_is_the_catalog_row_but_for_reduced():
    """Every number of the public config under its own key, except the
    four `reduced`; the layers are the published 6-12; the D the
    program builds; the cell, its files and its metrics."""
    from commefficient_tpu.models import nemotron_h
    manifest, entry, config = registered()
    reduced = {"num_hidden_layers": 7, "hybrid_override_pattern": "EMEMEM*",
               "n_routed_experts": 8, "vocab_size": 16384}
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(reduced)
    assert entry["source"] == config["source"] == SOURCE
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
        if key in reduced:
            assert config["published"][key] == value, key
    first = config["first_layer"]
    assert first == 6 and PATTERN[first:first + 7] == "EMEMEM*"
    assert config["router_width"] == 128 and config["held_experts"] == [0, 8]
    cfg = nemotron_h.NemotronHConfig.from_published(
        config, num_experts=config["router_width"],
        held_experts=tuple(config["held_experts"]))
    assert cfg.layer_kinds == "EMEMEM*"
    assert nemotron_h.num_params(cfg) == config["grad_size"] == 528_093_120
    assert isinstance(config["weights_seed"], int)
    cell = next(w for w in manifest["workloads"]
                if w["config"] == "nemotron3_nano_ep16")
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "nemotron_unc_w2_l8192", "unc_w2_l8192_nemotron", 1)
    for part, key in (("configs", "reference"), ("drivers", "driver"),
                      ("work", "work")):
        assert os.path.isfile(os.path.join(
            BENCH, part, config[key] + ".py")), (part, config[key])
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["mode"], traffic["num_workers"],
            traffic["local_batch_size"], traffic["num_clients"],
            traffic["corpus"]["max_tokens"]) == (
        "uncompressed", 2, 1, 64, 8192)
    names = {m["name"] for m in manifest["per_layer"]
             if cell["name"] in m.get("workloads", ())}
    assert names == {"attention_full_ms", "attn_proj_ms", "moe_route_ms",
                     "expert_ffn_ms", "expert_ffn_roofline",
                     "shared_expert_ms", "norm_ms", "head_loss_ms",
                     "unravel_ms", *NEW_METRICS}
    for name in names:
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))


def test_work_counts_the_layers_each_by_its_kind():
    work = load("work", "nemotron3_nano_ep16")
    _, _, config = registered()
    by_kind = {config["hybrid_override_pattern"][i]:
               work.layer_flops_per_position(config, 8192, i)
               for i in range(7)}
    assert set(by_kind["M"]) == {"projections", "scan"}
    assert by_kind["M"]["projections"] == 2 * 2688 * 10304 + 2 * 4096 * 2688
    # C B^T and its weighted sum over a chunk of 128, the end states and
    # what each position reads of the entering one
    assert by_kind["M"]["scan"] == 2 * 128 * (1024 + 4096) + 4 * 4096 * 128
    assert by_kind["E"] == {"router": 2 * 2688 * 128,
                            "shared": 4 * 2688 * 3712,
                            "experts": 6 * 8 / 128 * 4 * 2688 * 1856}
    assert by_kind["*"] == {"projections": 2 * 2688 * (2 * 4096 + 2 * 256),
                            "attention": 4 * 4096 * 8193 / 2}
    # 28.9 TFLOP a round of two 8,192-position sequences
    flops = work.train_flops_per_example(
        config, {"corpus": {"max_tokens": 8192}}) * 2
    assert 28.9e12 < flops < 29.0e12
    ffn = work.expert_ffn_work(config, 16384)
    # 768 picks on each of the 8 held experts, in each of 3 layers
    assert ffn["flops"] == 3 * 3 * 8 * 768 * 4 * 2688 * 1856
    scan = work.ssm_scan_work(config, 16384)
    assert scan["flops"] == 3 * 3 * 16384 * by_kind["M"]["scan"]
    assert scan["bytes"] == 3 * 16384 * 4 * (3 * 10304 + 2 * 4096)


def test_parent_is_refused_when_the_driver_loads(monkeypatch):
    """A program without `models/nemotron_h.py` (the parent commit)
    ends the run as the driver file loads, before the device is
    touched."""
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith(".nemotron_h")
        else real(name, *a))
    with pytest.raises(SystemExit, match="no Nemotron-H"):
        load("drivers", "nemotron_train")


def _fake_plane(monkeypatch, paths):
    from fedbench import reduce as reducer
    from fedbench.metrics import _scopes

    ops = [(1, 0.0, 1.0), (2, 0.5, 2.0), (3, 2.0, 3.0), (4, 3.0, 7.0)]
    plane = {"tf_op": paths, "names": {},
             "lines": {reducer.OP_LINE: ops, reducer.MODULE_LINE: []}}
    monkeypatch.setattr(_scopes, "_xplane", lambda ctx: "fake.pb")
    monkeypatch.setattr(_scopes, "_device_planes",
                        lambda path: {"/device:TPU:0": plane})
    monkeypatch.setattr(_scopes, "_cache", {})


SSM_PATHS = {
    1: "jit(round_step)/fed_fwdbwd/checkpoint/fed_ssm_scan/cumsum",
    2: "jit(round_step)/fed_fwdbwd/transpose(jvp(fed_ssm_scan))/dot_general",
    3: "jit(round_step)/fed_fwdbwd/checkpoint/fed_ssm_proj/dot_general",
    4: "jit(round_step)/fed_fwdbwd/fed_expert_ffn/ragged_dot",
}


def test_ssm_readers(monkeypatch):
    """`ssm_scan_ms` and `ssm_proj_ms` read their names anywhere on an
    op's path; the roofline is the scan's least time over its measured
    time; a program without the names (the parent) gives None from
    all three, without an error."""
    _, _, config = registered()
    work = load("work", "nemotron3_nano_ep16")
    with open(os.path.join(BENCH, "traffic",
                           "unc_w2_l8192_nemotron.json")) as f:
        traffic = json.load(f)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"rounds": 2, "config": config, "traffic": traffic,
           "work": work, "peaks": peaks}
    paths = dict(SSM_PATHS)
    _fake_plane(monkeypatch, paths)
    readers = {name: load("metrics", name) for name in NEW_METRICS}
    # the scan's ops cover [0, 2): 1 s a round
    assert readers["ssm_scan_ms"].read(ctx) == pytest.approx(1000.0)
    assert readers["ssm_proj_ms"].read(ctx) == pytest.approx(500.0)
    scan = work.ssm_scan_work(config, 16384)
    least = max(scan["flops"] / 197e12, scan["bytes"] / 819e9)
    assert readers["ssm_scan_roofline"].read(ctx) == pytest.approx(
        least / 1.0 * 100.0)
    for k in (1, 2, 3):
        del paths[k]
    from fedbench.metrics import _scopes
    monkeypatch.setattr(_scopes, "_cache", {})
    for reader in readers.values():
        assert reader.read(ctx) is None
