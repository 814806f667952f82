"""SmallThinker through the program's model against the benchmark's
plain reference (`fedbench/configs/smallthinker.py`) at a tiny size:
two periods of (full NoPE, 3 x window RoPE), hidden 64, 8 experts
top-2, window 8, 32 positions, seeded weights."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models import smallthinker as st

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "ref_smallthinker",
        os.path.join(ROOT, "fedbench", "configs", "smallthinker.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
L = 32
PAD = 4


def ref_config(cfg: st.SmallThinkerConfig) -> dict:
    """The reference's configuration (the published keys) for `cfg`."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "moe_ffn_hidden_size": cfg.expert_width,
        "router_width": cfg.num_experts,
        "moe_num_primary_experts": cfg.held_experts[1],
        "held_experts": list(cfg.held_experts),
        "moe_num_active_primary_experts": cfg.experts_per_token,
        "rope_layout": list(cfg.rope_layout),
        "sliding_window_layout": list(cfg.window_layout),
        "sliding_window_size": cfg.window_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
        "initializer_range": cfg.initializer_range,
        "pad_token_id": PAD,
    }


def ids_for(cfg, n, seed=0, pad_tail=5):
    rng = np.random.RandomState(seed)
    ids = rng.randint(5, cfg.vocab_size, size=(n, L)).astype(np.int32)
    ids[:, L - pad_tail:] = PAD
    return jnp.asarray(ids)


def batch_of(ids, W):
    """[W * B, L] ids as a cohort batch: leaves [W, B, C=1, ...]."""
    B = ids.shape[0] // W
    ids = ids.reshape(W, B, 1, L)
    z = jnp.zeros((W, B, 1), jnp.int32)
    return (ids, z, ids, jnp.zeros((W, B), jnp.int32), ids)


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat_g = jax.tree_util.tree_leaves_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol,
            atol=atol + rtol * float(np.abs(np.asarray(w)).max()),
            err_msg=jax.tree_util.keystr(path))


def stack_case(name):
    cfg = st.TINY
    if name == "full_layer":
        return cfg.replace(num_layers=1, rope_layout=(0,),
                           window_layout=(0,))
    if name == "window_layer":
        return cfg.replace(num_layers=1, rope_layout=(1,),
                           window_layout=(1,))
    return cfg


@pytest.mark.parametrize("case", ["full_layer", "window_layer", "stack"])
def test_model_matches_reference(case):
    """Loss, logits and every gradient leaf."""
    cfg = stack_case(case)
    rcfg = ref_config(cfg)
    params = REF.init_params(rcfg, seed=3)
    assert jax.tree.map(lambda x: x.shape, params) == st.param_shapes(cfg)
    ids = ids_for(cfg, 2)

    got = st.logits(cfg, params, ids)
    want = jnp.stack([REF.sequence_logits(rcfg, params, ids[i])
                      for i in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    loss = st.make_lm_loss(cfg, PAD)
    batch, mask = batch_of(ids, W=2), jnp.ones((2, 1), jnp.float32)

    def mine(p):
        losses, _ = loss(p, batch, mask)
        return losses.sum(), losses

    def theirs(p):
        losses = jnp.stack([
            REF.client_loss(rcfg, p, tuple(x[i] for x in batch), mask[i])
            for i in range(2)])
        return losses.sum(), losses

    (_, l_got), g_got = jax.value_and_grad(mine, has_aux=True)(params)
    (_, l_want), g_want = jax.value_and_grad(theirs, has_aux=True)(params)
    np.testing.assert_allclose(np.asarray(l_got), np.asarray(l_want),
                               rtol=1e-5)
    assert_trees_close(g_got, g_want)


def test_init_matches_the_reference_tree():
    cfg = st.TINY
    mine = st.init_params(cfg, jax.random.PRNGKey(3))
    theirs = REF.init_params(ref_config(cfg), 3)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert st.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(theirs))


def test_published_count_of_the_benchmark_share():
    """The D the benchmark's configuration states (four layers, 8 of
    64 experts held, an eighth of the vocabulary), and the D of the
    four-chip share the issue first asked for."""
    cfg = st.SmallThinkerConfig(num_layers=4, held_experts=(0, 8),
                                vocab_size=18992)
    assert st.num_params(cfg) == 370_547_200
    assert st.num_params(cfg.replace(held_experts=(0, 16),
                                     vocab_size=37984)) == 656_529_920


def skewed(cfg, params):
    """Router columns so that expert 0 takes (nearly) every position
    and expert 1 none: logits +50 and -50 whatever the input."""
    out = dict(params)
    for i in range(cfg.num_layers):
        lp = dict(params[f"layer_{i}"])
        # the embedding's first coordinate is made 1 below
        r = np.zeros(lp["router"].shape, np.float32)
        r[0, 0], r[0, 1] = 50.0, -50.0
        lp["router"] = lp["router"] * 0.01 + jnp.asarray(r)
        out[f"layer_{i}"] = lp
    return out


def test_skewed_router_drops_no_token():
    """One expert takes every position, one takes none: the loads say
    so, every position still gets its experts' output, and the
    gradient of the unreached expert is exactly zero."""
    cfg = st.TINY.replace(num_layers=1, rope_layout=(0,),
                          window_layout=(0,))
    rcfg = ref_config(cfg)
    params = REF.init_params(rcfg, seed=5)
    params["embed"] = params["embed"].at[:, 0].set(1.0)
    params = skewed(cfg, params)
    ids = ids_for(cfg, 2, seed=1, pad_tail=0)
    _, load = st.hidden(cfg, params, ids)
    load = np.asarray(load)                 # [N, layers, held + 1]
    assert load[:, 0, 0].tolist() == [L, L]
    assert load[:, 0, 1].tolist() == [0, 0]
    assert load[:, 0, :-1].sum(-1).tolist() == [2 * L, 2 * L]
    assert load[:, 0, -1].tolist() == [2 * L, 2 * L]
    got = st.logits(cfg, params, ids)
    want = jnp.stack([REF.sequence_logits(rcfg, params, ids[i])
                      for i in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    g = jax.grad(lambda p: st.logits(cfg, p, ids).sum())(params)
    for name in ("gate", "up", "down"):
        assert float(jnp.abs(g["layer_0"][name][1]).max()) == 0.0
        assert float(jnp.abs(g["layer_0"][name][0]).max()) > 0.0


@pytest.mark.parametrize("share", range(4))
def test_shares_add_up_to_the_uncut_layer(share):
    """Four shares of two experts each: share `share`'s expert output
    equals the uncut reference's expert layer restricted to those
    experts, and the four together equal the whole layer."""
    cfg = st.TINY.replace(num_layers=1)
    rcfg = ref_config(cfg)
    params = REF.init_params(rcfg, seed=7)["layer_0"]
    rng = np.random.RandomState(share)
    h2 = jnp.asarray(rng.randn(L, cfg.hidden_size).astype(np.float32))
    r = jnp.asarray(rng.randn(L, cfg.num_experts).astype(np.float32))
    whole = REF._experts(rcfg, params, h2, r)

    def part(s):
        lo = 2 * s
        held = {k: params[k][lo:lo + 2] for k in ("gate", "up", "down")}
        y, load = st.expert_chunk(
            cfg.replace(held_experts=(lo, 2)), held, h2, r)
        want = REF._experts({**rcfg, "held_experts": [lo, 2]}, held,
                            h2, r)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        return y, load

    mine, load = part(share)
    assert float(load[-1]) == L * cfg.experts_per_token
    others = sum(part(s)[0] for s in range(4) if s != share)
    np.testing.assert_allclose(np.asarray(mine + others),
                               np.asarray(whole), rtol=2e-4, atol=2e-5)


# ---------------- through the normal driver ------------------------------

def run_driver(tmp_path, *extra):
    from commefficient_tpu.training import gpt2_train
    return gpt2_train.main([
        "--test", "--model", "smallthinker", "--dataset_name", "PERSONA",
        "--dataset_dir", str(tmp_path / "ds"),
        "--local_momentum", "0.0", "--num_workers", "4",
        "--local_batch_size", "2", "--num_epochs", "1",
        "--valid_batch_size", "4", "--lr_scale", "0.1",
        "--journal_path", str(tmp_path / "journal.jsonl"), *extra])


@pytest.mark.parametrize("mode", ["uncompressed", "sketch"])
def test_driver_trains_smallthinker(tmp_path, mode):
    """`--model smallthinker` through gpt2_train.main, FedModel and
    round_step: finite losses, and the expert-load counters of every
    layer in the journal's round events."""
    import json
    extra = ["--mode", mode]
    if mode == "sketch":
        extra += ["--error_type", "virtual", "--virtual_momentum", "0.9"]
    assert run_driver(tmp_path, *extra)
    rounds = [json.loads(l) for l in open(tmp_path / "journal.jsonl")]
    rounds = [r for r in rounds if r.get("event") == "round"]
    assert rounds
    m = rounds[-1]["metrics"]
    for l in range(st.TINY.num_layers):
        assert m[f"moe{l}_routed"] > 0
        assert m[f"moe{l}_max_load"] >= m[f"moe{l}_min_load"] >= 0
        # every expert is held in the tiny preset
        assert m[f"moe{l}_absent_share"] == 0.0
    assert np.isfinite(m["train_loss"]) and m["examples"] > 0


def test_driver_refuses_a_per_client_mode(tmp_path):
    """The cohort loss trains only through the fused backward."""
    with pytest.raises(ValueError, match="whole cohort"):
        run_driver(tmp_path, "--mode", "local_topk", "--error_type",
                   "local", "--k", "10")
