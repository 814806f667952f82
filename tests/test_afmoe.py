"""Trinity (AFMoE) through the program's model against the benchmark's
plain reference (`fedbench/configs/afmoe.py`) at a tiny size: a dense
layer, then a whole period of expert layers (window, window, full,
window), hidden 64, 16 experts top-4 beside a shared one, window 8,
32 positions, seeded weights. And the routing by hand, the held
experts' layer at top-8 of 128, the sixteen shares of one layer, and
the normal driver."""
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models import afmoe, experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "ref_afmoe", os.path.join(ROOT, "fedbench", "configs", "afmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
L = 32
PAD = 4


def ref_config(cfg: afmoe.AfmoeConfig) -> dict:
    """The reference's configuration (the published keys) for `cfg`:
    its layers are the published layers 1.. of a stack whose layer
    types are `cfg.window_layout` behind one more."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_dense_layers": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.dense_width,
        "moe_intermediate_size": cfg.expert_width,
        "num_shared_experts": cfg.num_shared_experts,
        "router_width": cfg.num_experts,
        "num_experts": cfg.held_experts[1],
        "held_experts": list(cfg.held_experts),
        "num_experts_per_tok": cfg.experts_per_token,
        "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
        "layer_types": ["full_attention"] + [
            "sliding_attention" if w else "full_attention"
            for w in cfg.window_layout],
        "first_layer": 1,
        "sliding_window": cfg.window_size,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
        "mup_enabled": cfg.mup,
        "initializer_range": cfg.initializer_range,
        "expert_bias_std": 0.05,
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


def scaled_norms(params, seed):
    """Norm scales drawn about 1, so that each norm's scale (the q/k
    norms' above all) changes the result where it is used."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.RandomState(seed)
    out = []
    for path, x in leaves:
        name = path[-1].key
        if x.ndim == 1 and name != "expert_bias":
            x = x * jnp.asarray(1.0 + 0.3 * rng.randn(*x.shape),
                                jnp.float32)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def stack_case(name):
    cfg = afmoe.TINY
    if name == "dense_layer":
        return cfg.replace(num_layers=2, window_layout=(1, 0))
    if name == "full_layer":
        return cfg.replace(num_layers=1, num_dense_layers=0,
                           window_layout=(0,))
    if name == "window_layer":
        return cfg.replace(num_layers=1, num_dense_layers=0,
                           window_layout=(1,))
    if name == "held_share":
        return cfg.replace(held_experts=(4, 4))
    return cfg


@pytest.mark.parametrize("case", ["dense_layer", "full_layer",
                                  "window_layer", "stack", "held_share"])
def test_model_matches_reference(case):
    """Loss, logits and every gradient leaf; the norm scales drawn
    about one, so that the q/k norms and the sandwich norms weigh."""
    cfg = stack_case(case)
    rcfg = ref_config(cfg)
    params = scaled_norms(REF.init_params(rcfg, seed=3), seed=4)
    assert jax.tree.map(lambda x: x.shape, params) == afmoe.param_shapes(cfg)
    ids = ids_for(cfg, 2)

    got = jax.jit(functools.partial(afmoe.logits, cfg))(params, ids)
    want = jax.jit(jax.vmap(functools.partial(
        REF.sequence_logits, rcfg), (None, 0)))(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    loss = afmoe.make_lm_loss(cfg, PAD)
    batch, mask = batch_of(ids, W=2), jnp.ones((2, 1), jnp.float32)

    def mine(p):
        losses, _ = loss(p, batch, mask)
        return losses.sum(), losses

    def theirs(p):
        losses = jnp.stack([
            REF.client_loss(rcfg, p, tuple(x[i] for x in batch), mask[i])
            for i in range(2)])
        return losses.sum(), losses

    (_, l_got), g_got = jax.jit(jax.value_and_grad(mine, has_aux=True))(
        params)
    (_, l_want), g_want = jax.jit(jax.value_and_grad(theirs, has_aux=True))(
        params)
    np.testing.assert_allclose(np.asarray(l_got), np.asarray(l_want),
                               rtol=1e-5)
    assert_trees_close(g_got, g_want)
    for i in range(cfg.num_dense_layers, cfg.num_layers):
        # the bias selects and weighs nothing
        assert float(jnp.abs(g_got[f"layer_{i}"]["expert_bias"]).max()) \
            == 0.0


def test_init_matches_the_reference_tree():
    cfg = afmoe.TINY
    mine = afmoe.init_params(cfg, jax.random.PRNGKey(3))
    theirs = REF.init_params(ref_config(cfg), 3)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert afmoe.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(theirs))
    bias = np.asarray(theirs["layer_1"]["expert_bias"])
    assert bias.shape == (16,) and 0.01 < bias.std() < 0.15
    assert "expert_bias" not in theirs["layer_0"]      # the dense layer
    assert float(jnp.abs(mine["layer_1"]["expert_bias"]).max()) == 0.0


def test_published_count_of_the_benchmark_share():
    """Layers 1-5 of 32 (one dense, a period of four expert layers),
    8 of 128 experts held, an eighth of the vocabulary: the issue's
    504,147,200 and the four [128] expert-bias leaves."""
    cfg = afmoe.AfmoeConfig(num_layers=5, num_dense_layers=1,
                            held_experts=(0, 8), vocab_size=25024,
                            window_layout=(1, 1, 0, 1, 1))
    assert afmoe.num_params(cfg) == 504_147_200 + 4 * 128


# ---------------- the router, by hand -------------------------------------

def test_bias_moves_the_selection_and_weighs_nothing():
    """Four experts, top-2, bias on expert 3: the unbiased scores pick
    0 and 1, the biased ones 0 and 3; the weights are expert 0's and
    expert 3's UNBIASED scores over their sum, times route_scale."""
    cfg = afmoe.TINY.replace(num_experts=4, experts_per_token=2,
                             route_scale=2.5)
    r = jnp.asarray([[2.0, 1.0, -1.0, 0.5]])
    s = 1.0 / (1.0 + np.exp(-np.asarray(r[0], np.float64)))
    unbiased, _ = cfg.route(r, {"expert_bias": jnp.zeros(4)})
    assert np.asarray(unbiased).tolist() == [[0, 1]]
    idx, w = cfg.route(r, {"expert_bias": jnp.asarray([0.0, 0.0, 0.0, 0.2])})
    assert np.asarray(idx).tolist() == [[0, 3]]
    want = np.array([s[0], s[3]]) / (s[0] + s[3]) * 2.5
    np.testing.assert_allclose(np.asarray(w)[0], want, rtol=1e-6)
    # against the reference's router on the same numbers
    rcfg = ref_config(cfg)
    p = {"router": jnp.eye(4), "expert_bias": jnp.asarray(
        [0.0, 0.0, 0.0, 0.2])}
    r_idx, r_w = REF._route(rcfg, p, r)
    assert np.asarray(r_idx).tolist() == [[0, 3]]
    np.testing.assert_allclose(np.asarray(r_w), np.asarray(w), rtol=1e-6)


@pytest.mark.parametrize("norm", [True, False], ids=["route_norm", "raw"])
def test_route_norm_and_route_scale(norm):
    """With `route_norm` the k weights of a position add up to
    route_scale whatever the scores; without it each is its sigmoid
    score times route_scale. Held or absent, every pick counts."""
    cfg = afmoe.AfmoeConfig(num_experts=128, experts_per_token=8,
                            held_experts=(0, 8), route_norm=norm)
    rng = np.random.RandomState(0)
    r = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    b = jnp.asarray(rng.randn(128).astype(np.float32) * 0.05)
    idx, w = cfg.route(r, {"expert_bias": b})
    s = jax.nn.sigmoid(r)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.826, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)
                                      * 2.826), rtol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(w), np.asarray(picked * 2.826),
                                   rtol=1e-6)
    # the picks are the top 8 of score plus bias
    want = np.argsort(-np.asarray(s + b), axis=-1)[:, :8]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()


# ---------------- attention: NoPE, q/k norm, gate -------------------------

def one_layer(window, seed=0):
    cfg = afmoe.TINY.replace(num_layers=1, num_dense_layers=0,
                             window_layout=(window,))
    rcfg = ref_config(cfg)
    params = scaled_norms(REF.init_params(rcfg, seed=seed), seed)["layer_0"]
    x = jnp.asarray(np.random.RandomState(seed).randn(
        2, L, cfg.hidden_size).astype(np.float32))
    return cfg, rcfg, params, x


def test_full_layer_carries_no_position_encoding():
    """A full layer's attention, with the key and query rows of each
    sequence in another order, gives the same rows in that order where
    the causal mask lets every query see every key (the last query):
    no position enters. A window layer's RoPE breaks that."""
    for window, same in ((0, True), (1, False)):
        cfg, _, p, x = one_layer(window)
        cfg = cfg.replace(window_size=L)
        h = afmoe.rmsnorm(x, p["norm_in"], cfg.rms_eps)
        perm = np.r_[np.random.RandomState(1).permutation(L - 1), L - 1]
        a = afmoe.attention(cfg, p, h, bool(window))[:, -1]
        b = afmoe.attention(cfg, p, h[:, perm], bool(window))[:, -1]
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                           atol=1e-5) == same


@pytest.mark.parametrize("window", [0, 1], ids=["full", "window"])
def test_qk_norm_and_output_gate_against_reference(window):
    """The layer against the reference's with the q/k norms' scales
    and the gate drawn: equal; with the q norm's scale or the gate's
    matrix changed on the program's side only, not."""
    cfg, rcfg, p, x = one_layer(window, seed=2)
    want = jnp.stack([REF._layer(rcfg, p, x[i], bool(window), False)
                      for i in range(2)])
    got, _ = afmoe.layer(cfg, False, bool(window), p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for name, change in (("q_norm", lambda v: v * 1.5),
                         ("k_norm", lambda v: v * 0.5),
                         ("wg", lambda v: v * 3.0)):
        off, _ = afmoe.layer(cfg, False, bool(window),
                             {**p, name: change(p[name])}, x)
        assert not np.allclose(np.asarray(off), np.asarray(want),
                               rtol=1e-3, atol=1e-4), name


# ---------------- the held experts at top-8 of 128 ------------------------

TOP8 = afmoe.AfmoeConfig(hidden_size=32, expert_width=16, num_experts=128,
                         experts_per_token=8, held_experts=(0, 8),
                         moe_chunk=64)


def chunk_inputs(cfg, T, seed=0, skew=0.0):
    rng = np.random.RandomState(seed)
    H, F, n = cfg.hidden_size, cfg.expert_width, cfg.held_experts[1]
    p = {"gate": rng.randn(n, H, F), "up": rng.randn(n, H, F),
         "down": rng.randn(n, F, H)}
    p = {k: jnp.asarray(v.astype(np.float32) * 0.1) for k, v in p.items()}
    r = rng.randn(T, cfg.num_experts).astype(np.float32)
    r[:, :cfg.held_experts[1]] += skew       # draw picks to the held ones
    p["expert_bias"] = jnp.asarray(
        rng.randn(cfg.num_experts).astype(np.float32) * 0.05)
    return p, jnp.asarray(rng.randn(T, H).astype(np.float32)), jnp.asarray(r)


@pytest.mark.parametrize("skew", [0.0, 1.5, 4.0],
                         ids=["uniform", "first_capacity_over", "all_held"])
def test_compacted_matches_full_width_at_top8_of_128(skew):
    """A chunk of 64 positions, 512 picks, capacities 64 and 128: the
    chunk as the model runs it (the capacity its held picks fit, or
    every pick's row) and each branch alone, against every pick's row
    through `full_width`: y and the gradient of every input."""
    cfg = TOP8
    T = cfg.moe_chunk
    assert experts.compact_capacities(cfg, T) == (64, 128)
    p, h2, r = chunk_inputs(cfg, T, skew=skew)
    weight = jnp.asarray(np.random.RandomState(1).randn(T, cfg.hidden_size),
                         jnp.float32)

    def weighed(fn):
        def loss(p, h2, r):
            y = fn(p, h2, r)
            return (y * weight).sum(), y
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(p, h2, r)

    def full(p, h2, r):
        return experts.full_width(cfg, p, h2,
                                  *experts.sorted_picks(cfg, r, p))

    (_, y_want), g_want = weighed(full)
    (_, y_got), g_got = weighed(
        lambda p, h2, r: experts.expert_chunk(cfg, p, h2, r)[0])
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=2e-4, atol=2e-5)
    assert_trees_close(g_got, g_want)
    live = int(experts.sorted_picks(cfg, r, p)[2].sum())
    for C in (64, 128, T * 8):
        if C < live:
            continue
        (_, y_c), g_c = weighed(lambda p, h2, r, C=C: experts.compacted(
            cfg, C, p, h2, *experts.sorted_picks(cfg, r, p)))
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_want),
                                   rtol=2e-4, atol=2e-5)
        assert_trees_close(g_c, g_want)
    load = experts.expert_chunk(cfg, p, h2, r)[1]
    ran_compact = float(load[-2]) > 0
    assert ran_compact == (live <= 128)


SHARES = afmoe.AfmoeConfig(hidden_size=32, expert_width=16,
                           num_experts=128, experts_per_token=8,
                           held_experts=(0, 128), moe_chunk=L)


@functools.lru_cache(maxsize=None)
def share_case():
    """One layer's parameters with all 128 experts, its input and the
    uncut reference's whole expert layer (shared + all 128)."""
    rcfg = ref_config(SHARES.replace(num_layers=1, num_dense_layers=0,
                                     window_layout=(1,)))
    rcfg.update(vocab_size=64, intermediate_size=16)
    params = REF.init_params(rcfg, seed=11)["layer_0"]
    h2 = jnp.asarray(np.random.RandomState(5).randn(
        L, SHARES.hidden_size).astype(np.float32))
    shared = REF._swiglu(h2, params["shared_gate"], params["shared_up"],
                         params["shared_down"])
    return rcfg, params, h2, shared, shared + REF._experts(rcfg, params, h2)


@functools.lru_cache(maxsize=None)
def share_part(s):
    """Chip `s`'s routed part (experts 8s..8s+7) by the program, and
    the uncut reference's restricted to those experts."""
    rcfg, params, h2, _, _ = share_case()
    lo = 8 * s
    held = {**params, **{k: params[k][lo:lo + 8]
                         for k in ("gate", "up", "down")}}
    y, _ = experts.expert_chunk(SHARES.replace(held_experts=(lo, 8)),
                                held, h2, h2 @ params["router"])
    want = REF._experts({**rcfg, "held_experts": [lo, 8]}, held, h2)
    return np.asarray(y), np.asarray(want)


@pytest.mark.parametrize("share", range(16))
def test_sixteen_shares_add_up_to_the_uncut_layer(share):
    """128 experts over sixteen chips, 8 a chip: share `share`'s
    routed part equals the uncut reference's restricted to its experts,
    and the sixteen routed parts with the shared expert counted once
    equal the reference's whole expert layer (shared + all 128), as
    the program's uncut layer does."""
    _, params, h2, shared, whole = share_case()
    mine, want = share_part(share)
    np.testing.assert_allclose(mine, want, rtol=2e-4, atol=2e-5)
    routed = sum(share_part(s)[0] for s in range(16))
    np.testing.assert_allclose(np.asarray(shared) + routed,
                               np.asarray(whole), rtol=2e-4, atol=2e-5)
    if share == 0:
        mixed, _ = afmoe.mixture(SHARES, params, h2[None])
        np.testing.assert_allclose(np.asarray(mixed[0]), np.asarray(whole),
                                   rtol=2e-4, atol=2e-5)


# ---------------- through the normal driver ------------------------------

def run_driver(tmp_path, *extra):
    from commefficient_tpu.training import gpt2_train
    return gpt2_train.main([
        "--test", "--model", "trinity", "--dataset_name", "PERSONA",
        "--dataset_dir", str(tmp_path / "ds"),
        "--local_momentum", "0.0", "--num_workers", "4",
        "--local_batch_size", "2", "--num_epochs", "1",
        "--valid_batch_size", "4", "--lr_scale", "0.1",
        "--journal_path", str(tmp_path / "journal.jsonl"), *extra])


def test_driver_trains_trinity(tmp_path):
    """`--model trinity` through gpt2_train.main, FedModel and
    round_step: finite losses, and the expert-load counters of the
    four expert layers (the dense layer has none) in the journal."""
    assert run_driver(tmp_path, "--mode", "uncompressed")
    rounds = [json.loads(l) for l in open(tmp_path / "journal.jsonl")]
    rounds = [r for r in rounds if r.get("event") == "round"]
    assert rounds
    m = rounds[-1]["metrics"]
    layers = afmoe.TINY.expert_layers
    assert layers == 4
    for l in range(layers):
        assert m[f"moe{l}_routed"] > 0
        assert m[f"moe{l}_absent_share"] == 0.0
        # every layer's backward pass took the same whole chunks
        assert m[f"moe{l}_chunks"] == m["moe0_chunks"] > 0
        assert float(m[f"moe{l}_chunks"]).is_integer()
    assert f"moe{layers}_routed" not in m
    assert np.isfinite(m["train_loss"]) and m["examples"] > 0


def test_driver_refuses_a_per_client_mode(tmp_path):
    """The cohort loss trains only through the fused backward."""
    with pytest.raises(ValueError, match="whole cohort"):
        run_driver(tmp_path, "--mode", "local_topk", "--error_type",
                   "local", "--k", "10")


def test_model_is_chosen_by_name():
    from commefficient_tpu.config import Config
    from commefficient_tpu.models import smallthinker
    from commefficient_tpu.training import gpt2_train
    for name, want in (("trinity", afmoe.TINY),
                       ("smallthinker", smallthinker.TINY)):
        cfg = Config(model=name, do_test=True)
        assert gpt2_train.decoder_config(cfg) == want.replace(
            remat=cfg.do_remat)
    assert gpt2_train.decoder_config(Config(model="gpt2")) is None
    assert gpt2_train.smallthinker_config is gpt2_train.decoder_config
