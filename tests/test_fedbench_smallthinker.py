"""The benchmark's SmallThinker cell at a size a CPU test holds:
`fedbench/harness.run` end to end through the cell's own driver,
reference, work and metric files (`fedbench/tests/tiny_smallthinker/`:
eight layers, hidden 64, 4 of 8 experts held, 128 positions), the
control and the fault, and the files `BENCHMARK.json` names."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "fedbench")
TINY = os.path.join(BENCH, "tests", "tiny_smallthinker", "manifest.json")
CELL = "tiny_smallthinker_unc"


def run(**kw):
    from fedbench import harness
    return harness.run(CELL, 2 ** 31 + 11, 0.5, False, manifest_path=TINY,
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


def test_registered_configuration_is_the_published_one():
    """`BENCHMARK.json`'s SmallThinker entry: every number of the
    public config.json under its own key except the three `reduced`,
    and the D the program builds from it."""
    from commefficient_tpu.models import smallthinker as st
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "smallthinker_21b_ep8")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["moe_ffn_hidden_size"], config["router_width"],
            config["moe_num_active_primary_experts"],
            config["sliding_window_size"]) == (
        2560, 128, 28, 4, 768, 64, 6, 4096)
    assert config["rope_layout"] == [0, 1, 1, 1] * 13
    assert config["sliding_window_layout"] == [0, 1, 1, 1] * 13
    cfg = st.SmallThinkerConfig.from_published(
        config, num_experts=config["router_width"],
        held_experts=tuple(config["held_experts"]))
    assert st.num_params(cfg) == config["grad_size"] == 370_547_200
    cell = next(w for w in manifest["workloads"]
                if w["config"] == "smallthinker_21b_ep8")
    assert cell["name"] == "smallthinker_unc_w2_l8192"
    for part, key in (("configs", "reference"), ("drivers", "driver"),
                      ("work", "work")):
        assert os.path.isfile(os.path.join(
            BENCH, part, config[key] + ".py")), (part, config[key])
    assert os.path.isfile(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    for m in manifest["per_layer"]:
        if cell["name"] in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(
                BENCH, "metrics", m["name"] + ".py")), m["name"]


def test_work_counts_allowed_pairs_and_held_picks():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "work_st", os.path.join(BENCH, "work", "smallthinker_21b_ep8.py"))
    work = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(work)
    assert work.allowed_pairs(8, 0) == 36
    assert work.allowed_pairs(8, 3) == sum(min(i + 1, 3) for i in range(8))
    with open(os.path.join(BENCH, "configs",
                           "smallthinker_21b_ep8.json")) as f:
        config = json.load(f)
    per = work.layer_flops_per_position(config, 8192, window=True)
    assert per["experts"] == 6 * 8 / 64 * 6 * 2560 * 768
    assert per["projections"] == 2 * 2560 * (2 * 3584 + 2 * 512)
    full = work.layer_flops_per_position(config, 8192, window=False)
    assert full["attention"] > per["attention"]


def test_layer_readers_find_inner_names(monkeypatch):
    """`metrics/_layers.py`: an op belongs to a model's layer where
    the layer's name is a component anywhere on its op path (inside
    `fed_fwdbwd`, under `jvp(...)`, `transpose(...)`, `checkpoint`),
    a layer's time is the union of its ops' intervals, and a program
    that lays no such name gives None."""
    from fedbench import reduce as reducer
    from fedbench.metrics import _layers, _scopes

    paths = {
        1: "jit(round_step)/fed_fwdbwd/checkpoint/fed_expert_ffn/ragged_dot",
        2: "jit(round_step)/fed_fwdbwd/transpose(jvp(fed_expert_ffn))/mul",
        3: "jit(round_step)/fed_fwdbwd/fed_attention_window/while/body/dot",
        4: "jit(round_step)/fed_fwdbwd/dot_general",
        5: "jit(round_step)/fed_fwdbwd/fed_expert_ffn_extra/add",
    }
    ops = [(1, 0.0, 1.0), (2, 0.5, 2.0), (3, 2.0, 2.5), (4, 3.0, 4.0),
           (5, 5.0, 9.0)]
    plane = {"tf_op": paths, "names": {},
             "lines": {reducer.OP_LINE: ops, reducer.MODULE_LINE: []}}
    monkeypatch.setattr(_scopes, "_xplane", lambda ctx: "fake.pb")
    monkeypatch.setattr(_scopes, "_device_planes",
                        lambda path: {"/device:TPU:0": plane})
    monkeypatch.setattr(_scopes, "_cache", {})
    ctx = {"rounds": 2, "cell": "x"}
    assert _layers.layer_ms(ctx, "expert_ffn") == pytest.approx(1000.0)
    assert _layers.layer_ms(ctx, "attention_window") == pytest.approx(250.0)
    assert _layers.layer_ms(ctx, "attention_full") is None
    assert _layers.layer_ms(ctx, "moe_route") is None

    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_roof", os.path.join(BENCH, "metrics", "expert_ffn_roofline.py"))
    roof = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roof)

    class Work:
        @staticmethod
        def expert_ffn_work(config, positions):
            assert positions == 2 * 1 * 1 * 64
            return {"flops": 1e12, "bytes": 1e9}

    ctx.update(work=Work, peaks={"bf16_flops_per_s": 2e12,
                                 "hbm_bytes_per_s": 1e12},
               config={"num_candidates": 1},
               traffic={"num_workers": 2, "local_batch_size": 1,
                        "corpus": {"max_tokens": 64}})
    # least time 0.5 s a round against 1.0 s measured
    assert roof.read(ctx) == pytest.approx(50.0)
    ctx["work"] = object()       # a configuration without the function
    assert roof.read(ctx) is None
