"""The benchmark's Trinity cell at a size a CPU test holds:
`fedbench/harness.run` end to end through the cell's own driver,
reference, work and metric files (`fedbench/tests/tiny_trinity/`: a
dense layer and four expert layers, hidden 64, 4 of 16 experts held,
128 positions), the control and the fault, the files `BENCHMARK.json`
names, and the parent's refusal."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "fedbench")
TINY = os.path.join(BENCH, "tests", "tiny_trinity", "manifest.json")
CELL = "tiny_trinity_unc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# The public config.json's numbers (the catalog's row `Trinity-Mini`,
# https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json),
# without `layer_types`, which is checked apart
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}
LAYER_TYPES = (["sliding_attention"] * 3 + ["full_attention"]) * 8


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
                 if c["name"] == "trinity_mini_ep16")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return manifest, entry, json.load(f)


def run(**kw):
    from fedbench import harness
    return harness.run(CELL, 2 ** 31 + 13, 0.5, False, manifest_path=TINY,
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
    four `reduced`; `layer_types` copied whole; the D the program
    builds; the cell and its files."""
    from commefficient_tpu.models import afmoe
    manifest, entry, config = registered()
    reduced = {"num_hidden_layers": 5, "num_dense_layers": 1,
               "num_experts": 8, "vocab_size": 25024}
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(reduced)
    published = dict(PUBLISHED)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(l) for l in f
                       if json.loads(l)["name"] == "Trinity-Mini")
        assert row["source_url"] == entry["source"] == config["source"]
        published = {k: v for k, v in row["config"].items()
                     if k != "layer_types"}
        assert row["config"]["layer_types"] == LAYER_TYPES
    for key, value in published.items():
        want = reduced.get(key, value)
        assert config[key] == want, key
        if key in reduced:
            assert config["published"][key] == value, key
    assert config["layer_types"] == LAYER_TYPES
    assert config["router_width"] == 128 and config["held_experts"] == [0, 8]
    # layers 1-5: the second dense layer, then sliding, full, sliding,
    # sliding
    first = config["first_layer"]
    assert first == 1 and [t[0] for t in LAYER_TYPES[first:first + 5]] \
        == ["s", "s", "f", "s", "s"]
    cfg = afmoe.AfmoeConfig.from_published(
        config, num_experts=config["router_width"],
        held_experts=tuple(config["held_experts"]))
    assert cfg.window_layout == (1, 1, 0, 1, 1)
    assert afmoe.num_params(cfg) == config["grad_size"] == 504_147_712
    assert isinstance(config["weights_seed"], int)
    cell = next(w for w in manifest["workloads"]
                if w["config"] == "trinity_mini_ep16")
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "trinity_unc_w2_l8192", "unc_w2_l8192_trinity", 1)
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
    assert names == {"attention_full_ms", "attention_window_ms",
                     "moe_route_ms", "expert_ffn_ms", "expert_ffn_roofline",
                     "shared_expert_ms", "attn_proj_ms", "norm_ms",
                     "head_loss_ms", "unravel_ms", "dense_mlp_ms",
                     "fwdbwd_other_ms"}
    for name in names:
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))


def test_work_counts_the_layers_each_by_its_kind():
    work = load("work", "trinity_mini_ep16")
    _, _, config = registered()
    dense = work.layer_flops_per_position(config, 8192, 0)
    assert set(dense) == {"projections", "attention", "dense"}
    assert dense["projections"] == 2 * 2048 * (3 * 4096 + 2 * 512)
    assert dense["dense"] == 6 * 2048 * 6144
    full = work.layer_flops_per_position(config, 8192, 2)
    window = work.layer_flops_per_position(config, 8192, 1)
    assert full["attention"] > window["attention"]
    assert window["experts"] == 8 * 8 / 128 * 6 * 2048 * 1024
    assert window["shared"] == 6 * 2048 * 1024
    assert window["router"] == 2 * 2048 * 128
    assert work.forward_flops_per_position(config, 8192) == 712_785_920
    ffn = work.expert_ffn_work(config, 16384)
    # 1,024 picks on each of the 8 held experts, in each of 4 layers
    assert ffn["flops"] == 4 * 3 * 8 * 1024 * 6 * 2048 * 1024


def test_parent_is_refused_when_the_driver_loads(monkeypatch):
    """A program without `models/afmoe.py` (the parent commit) ends
    the run as the driver file loads, before the device is touched."""
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith(".afmoe") else real(name, *a))
    with pytest.raises(SystemExit, match="no Trinity"):
        load("drivers", "trinity_train")


def test_shared_expert_reader(monkeypatch):
    """`metrics/shared_expert_ms.py` reads the scope `shared_expert`
    anywhere on an op's path, and gives None where no op has it."""
    from fedbench import reduce as reducer
    from fedbench.metrics import _scopes

    paths = {1: "jit(round_step)/fed_fwdbwd/checkpoint/fed_shared_expert/dot",
             2: "jit(round_step)/fed_fwdbwd/transpose(jvp(fed_shared_expert))"
                "/mul",
             3: "jit(round_step)/fed_fwdbwd/fed_expert_ffn/ragged_dot"}
    plane = {"tf_op": paths, "names": {},
             "lines": {reducer.OP_LINE: [(1, 0.0, 1.0), (2, 0.5, 2.0),
                                         (3, 2.0, 3.0)],
                       reducer.MODULE_LINE: []}}
    monkeypatch.setattr(_scopes, "_xplane", lambda ctx: "fake.pb")
    monkeypatch.setattr(_scopes, "_device_planes",
                        lambda path: {"/device:TPU:0": plane})
    monkeypatch.setattr(_scopes, "_cache", {})
    reader = load("metrics", "shared_expert_ms")
    assert reader.read({"rounds": 2}) == pytest.approx(1000.0)
    del paths[1], paths[2]
    monkeypatch.setattr(_scopes, "_cache", {})
    assert reader.read({"rounds": 2}) is None


def _fake_plane(monkeypatch, paths, names, ops):
    from fedbench import reduce as reducer
    from fedbench.metrics import _scopes

    plane = {"tf_op": paths, "names": names,
             "lines": {reducer.OP_LINE: ops, reducer.MODULE_LINE: []}}
    monkeypatch.setattr(_scopes, "_xplane", lambda ctx: "fake.pb")
    monkeypatch.setattr(_scopes, "_device_planes",
                        lambda path: {"/device:TPU:0": plane})
    monkeypatch.setattr(_scopes, "_cache", {})


# a named op, an unnamed one and a `while` over both, inside `fwdbwd`;
# an op outside it; an op in the head's loop
NAMED_PATHS = {
    1: "jit(round_step)/fed_fwdbwd/transpose(jvp(fed_fwdbwd))/checkpoint"
       "/fed_attn_proj/dot_general",
    2: "jit(round_step)/fed_fwdbwd/jvp()/closed_call/while/body/add",
    3: "jit(round_step)/fed_fwdbwd/jvp()/closed_call/while",
    4: "jit(round_step)/fed_telemetry/reduce_sum",
    5: "jit(round_step)/fed_fwdbwd/jvp(fed_head_loss)/while/body/dot",
}
NAMED_HLO = {
    1: "%fusion.7 = f32[2,8192,4096]{1,2,0:T(8,128)} fusion(f32[2,8192,"
       "2048]{2,1,0:T(8,128)} %p.1), kind=kOutput, calls=%fused.7",
    2: "%add.3 = f32[2048]{0:T(1024)} add(f32[2048]{0} %a, f32[2048]{0} %b)",
    3: "%while.616 = (s32[], f32[2048]{0:T(1024)S(1)}) while((s32[], "
       "f32[2048]{0}) %tuple.2), condition=%cond.1, body=%body.1",
    4: "%reduce.9 = f32[] reduce(f32[8]{0} %x, f32[] %c), to_apply=%sum",
    5: "%convolution.4 = f32[2048,128]{1,0} convolution(f32[2048,64]{1,0}"
       " %h, f32[64,128]{1,0} %w), dim_labels=bf_io->bf",
}
NAMED_OPS = [(3, 0.0, 4.0), (1, 0.0, 1.0), (2, 1.5, 2.5), (2, 2.0, 3.0),
             (4, 5.0, 9.0), (5, 9.0, 10.0)]


def test_new_layer_reader(monkeypatch):
    """`metrics/attn_proj_ms.py` reads its name anywhere on an op's
    path (the `while` over it carries another path and is not its);
    a program without the name gives None."""
    paths = dict(NAMED_PATHS)
    _fake_plane(monkeypatch, paths, dict(NAMED_HLO), NAMED_OPS)
    reader = load("metrics", "attn_proj_ms")
    assert reader.read({"rounds": 2}) == pytest.approx(500.0)
    del paths[1]
    from fedbench.metrics import _scopes
    monkeypatch.setattr(_scopes, "_cache", {})
    assert reader.read({"rounds": 2}) is None


def test_fwdbwd_other_reader(monkeypatch, capsys):
    """`metrics/fwdbwd_other_ms.py` counts the interval union of the
    ops under `fwdbwd` with none of the ten inner names, leaves out the
    `while` that covers them, and the ops outside `fwdbwd`; it names
    the instructions it counted; a program that lays none of the five
    new names gives None."""
    paths = dict(NAMED_PATHS)
    _fake_plane(monkeypatch, paths, dict(NAMED_HLO), NAMED_OPS)
    reader = load("metrics", "fwdbwd_other_ms")
    # add.3 over [1.5, 3.0): 1.5 s in 2 rounds
    assert reader.read({"rounds": 2}) == pytest.approx(750.0)
    assert '["add.3:f32[2048]", 1000.0]' in capsys.readouterr().err
    assert reader.is_container(NAMED_HLO[3])
    assert reader.is_container("%conditional.2 = (f32[8]{0}) conditional("
                               "s32[] %i, f32[8]{0} %a), branch_computat"
                               "ions={%b0, %b1}")
    assert not any(reader.is_container(NAMED_HLO[k]) for k in (1, 2, 4, 5))
    # the parent: the old inner names only
    paths[1] = paths[1].replace("fed_attn_proj", "fed_attention_full")
    paths[5] = paths[5].replace("fed_head_loss", "fed_expert_ffn")
    from fedbench.metrics import _scopes
    monkeypatch.setattr(_scopes, "_cache", {})
    assert reader.read({"rounds": 2}) is None
