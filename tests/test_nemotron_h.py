"""Nemotron-H through the program's model against the benchmark's plain
reference (`fedbench/configs/nemotron_h.py`) at a tiny size: one whole
period E, M, E, M, E, M, * (hidden 64, 8 mamba heads of 8 over 2
groups of state 16 in chunks of 8, 16 experts top-4 beside a shared
one), 32 positions, seeded weights. And the chunked scan against the
step recurrence, the ungated experts' sixteen shares, the mixer
counters and the normal driver."""
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models import experts, nemotron_h as nh
from commefficient_tpu.ops import ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "ref_nemotron_h",
        os.path.join(ROOT, "fedbench", "configs", "nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()
L = 32
PAD = 4


def ref_config(cfg: nh.NemotronHConfig) -> dict:
    """The reference's configuration (the published keys) for `cfg`."""
    lo, hi, floor = cfg.time_step
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "hybrid_override_pattern": cfg.layer_kinds,
        "published": {"num_hidden_layers": 52},
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "moe_intermediate_size": cfg.expert_width,
        "moe_shared_expert_intermediate_size": cfg.shared_width,
        "n_shared_experts": 1,
        "router_width": cfg.num_experts,
        "n_routed_experts": cfg.held_experts[1],
        "held_experts": list(cfg.held_experts),
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.route_scale,
        "norm_topk_prob": cfg.route_norm,
        "mamba_num_heads": cfg.ssm_heads,
        "mamba_head_dim": cfg.ssm_head_dim, "n_groups": cfg.ssm_groups,
        "ssm_state_size": cfg.ssm_state, "conv_kernel": cfg.conv_kernel,
        "chunk_size": cfg.ssm_chunk, "time_step_min": lo,
        "time_step_max": hi, "time_step_floor": floor,
        "layer_norm_epsilon": cfg.rms_eps,
        "initializer_range": cfg.initializer_range,
        "expert_bias_std": 0.05, "pad_token_id": PAD,
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
    """Float32 rounding over a few layers: the two sides sum the same
    products in other orders (the chunked scan against the step form
    above all), which moves the last few bits of a leaf's largest
    entries."""
    flat_g = jax.tree_util.tree_leaves_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol,
            atol=atol + rtol * float(np.abs(np.asarray(w)).max()),
            err_msg=jax.tree_util.keystr(path))


def worst_gap(got, want) -> float:
    """The largest leaf gap, against each leaf's largest entry."""
    return max(float(np.abs(np.asarray(g) - np.asarray(w)).max()
                     / max(float(np.abs(np.asarray(w)).max()), 1e-30))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def lively(params, seed):
    """Norm scales drawn about 1, and the mixers' weights scaled up, so
    that every leaf, the scan's inputs B and C above all, changes the
    result where it is used: under N(0, 0.02) weights the scan's
    output is a few thousandths of the D skip's."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.RandomState(seed)
    out = []
    for path, x in leaves:
        name = path[-1].key
        if x.ndim == 1 and name in ("norm", "gate_norm", "final_norm"):
            x = x * jnp.asarray(1.0 + 0.3 * rng.randn(*x.shape),
                                jnp.float32)
        if name == "in_proj":
            x = x * 10.0
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------- the chunked scan against the step recurrence ------------

def step_recurrence(x, dt, A, B, C, reset_every=None):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t, a step
    a position (the definition); with `reset_every`, the state is
    dropped at every such boundary: what a scan without its
    inter-chunk term would give."""
    N, T, Hh, P = x.shape
    G, S = B.shape[2:]
    Bh = jnp.repeat(B, Hh // G, axis=2)
    Ch = jnp.repeat(C, Hh // G, axis=2)

    def step(s, xs):
        t, xt, dtt, bt, ct = xs
        if reset_every:
            s = jnp.where(t % reset_every == 0, 0.0, s)
        s = (jnp.exp(dtt * A)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, (s * ct[:, :, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((N, Hh, P, S)), (
        jnp.arange(T), *(v.swapaxes(0, 1) for v in (x, dt, Bh, Ch))))
    return y.swapaxes(0, 1)


def scan_inputs(T, seed=0):
    """Two sequences, 4 heads over 2 groups: the first two heads decay
    slowly (exp(dt A) about 0.99 a position, 0.9 over a chunk of 8),
    so their state carries across chunks; the last two fast."""
    rng = np.random.RandomState(seed)
    N, Hh, P, G, S = 2, 4, 3, 2, 5
    x = rng.randn(N, T, Hh, P)
    dt = np.exp(rng.randn(N, T, Hh) * 0.3 - 2.0)
    A = -np.array([0.1, 0.12, 4.0, 9.0])
    B, C = rng.randn(N, T, G, S), rng.randn(N, T, G, S)
    return tuple(jnp.asarray(v, jnp.float32) for v in (x, dt, A, B, C))


@pytest.mark.parametrize("T,chunk", [(32, 8), (32, 4), (32, 16), (29, 8),
                                     (32, 32)])
def test_chunked_scan_matches_the_step_recurrence(T, chunk):
    """y and the gradients of x, dt, A, B and C to float32 rounding
    (1e-5 of the largest entry: the two forms sum the same terms in
    other orders), at several chunks and a length that is no chunk
    multiple. Dropping the state between chunks moves y by far more
    than that, so the tolerance sees the inter-chunk term."""
    x, dt, A, B, C = scan_inputs(T)
    want = step_recurrence(x, dt, A, B, C)
    y, carry = ssd.ssd_scan(x, dt, A, B, C, chunk)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * scale)
    assert carry.shape == (2, -(-T // chunk), 4)
    if chunk < T:
        # the slow heads carry most of their state into the next chunk
        assert float(carry[:, :, :2].min()) > 0.3
        dropped = step_recurrence(x, dt, A, B, C, reset_every=chunk)
        assert float(jnp.abs(dropped - want).max()) > 1e3 * 1e-5 * scale
    ct = jnp.asarray(np.random.RandomState(1).randn(*want.shape),
                     jnp.float32)
    got = jax.grad(lambda *a: (ssd.ssd_scan(*a, chunk)[0] * ct).sum(),
                   argnums=range(5))(x, dt, A, B, C)
    ref = jax.grad(lambda *a: (step_recurrence(*a) * ct).sum(),
                   argnums=range(5))(x, dt, A, B, C)
    for name, g, w in zip("x dt A B C".split(), got, ref):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_conv_and_gated_norm_by_hand():
    """The causal convolution reads only the past (a unit impulse at t
    comes out at t..t+K-1, scaled by the taps in order); the gated norm
    gates before it normalises, group by group."""
    x = jnp.zeros((1, 6, 2)).at[0, 2, 0].set(1.0)
    w = jnp.asarray([[1.0, 0.0], [2.0, 0.0], [3.0, 0.0], [4.0, 0.0]])
    y = ssd.causal_conv(x, w, jnp.asarray([0.5, 0.0]))
    np.testing.assert_allclose(np.asarray(y[0, :, 0]),
                               [0.5, 0.5, 4.5, 3.5, 2.5, 1.5])
    rng = np.random.RandomState(0)
    v, z = rng.randn(3, 8), rng.randn(3, 8)
    g = rng.randn(8)
    gated = v * z / (1.0 + np.exp(-z))
    groups = gated.reshape(3, 2, 4)
    want = (groups / np.sqrt((groups ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 8) * g
    got = ssd.gated_rmsnorm(jnp.asarray(v, jnp.float32),
                            jnp.asarray(z, jnp.float32),
                            jnp.asarray(g, jnp.float32), 4, 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


# ---------------- the model against the reference ------------------------

def stack_case(name):
    cfg = nh.TINY
    if name == "mixer_layer":
        return cfg.replace(layer_kinds="M")
    if name == "attention_layer":
        return cfg.replace(layer_kinds="*")
    if name == "held_share":
        return cfg.replace(held_experts=(4, 4))
    return cfg


def model_case(case):
    cfg = stack_case(case)
    rcfg = ref_config(cfg)
    params = lively(REF.init_params(rcfg, seed=3), seed=4)
    assert jax.tree.map(lambda x: x.shape, params) == nh.param_shapes(cfg)
    return cfg, rcfg, params, ids_for(cfg, 2)


def losses_and_grads(cfg, rcfg, params, ids, dtype=jnp.float32):
    """(program's, reference's) (client losses, gradient); `dtype` the
    program's parameters' (the reference stays float32)."""
    loss = nh.make_lm_loss(cfg, PAD)
    batch, mask = batch_of(ids, W=2), jnp.ones((2, 1), jnp.float32)

    def mine(p):
        losses, _ = loss(jax.tree.map(lambda x: x.astype(dtype), p),
                         batch, mask)
        losses = losses.astype(jnp.float32)
        return losses.sum(), losses

    def theirs(p):
        losses = jnp.stack([
            REF.client_loss(rcfg, p, tuple(x[i] for x in batch), mask[i])
            for i in range(2)])
        return losses.sum(), losses

    return tuple(jax.jit(jax.value_and_grad(f, has_aux=True))(params)
                 for f in (mine, theirs))


@pytest.mark.parametrize("case", ["mixer_layer", "attention_layer",
                                  "stack", "held_share"])
def test_model_matches_reference(case):
    """Logits, the clients' losses and every gradient leaf, with the
    norm scales drawn about one and the mixers' inputs scaled up so
    that the scan weighs; the correction bias gets no gradient."""
    cfg, rcfg, params, ids = model_case(case)
    got = jax.jit(functools.partial(nh.logits, cfg))(params, ids)
    want = jax.jit(jax.vmap(functools.partial(
        REF.sequence_logits, rcfg), (None, 0)))(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    ((_, l_got), g_got), ((_, l_want), g_want) = losses_and_grads(
        cfg, rcfg, params, ids)
    np.testing.assert_allclose(np.asarray(l_got), np.asarray(l_want),
                               rtol=1e-5)
    assert_trees_close(g_got, g_want)
    for i, kind in enumerate(cfg.layer_kinds):
        if kind == "E":
            assert float(jnp.abs(
                g_got[f"layer_{i}"]["expert_bias"]).max()) == 0.0


def test_a_bfloat16_forward_fails_the_tolerance():
    """The whole stack with the program's parameters in bfloat16: its
    gradient misses the reference's by far more than the tolerance of
    `test_model_matches_reference` (2e-4 of a leaf's largest entry)."""
    cfg, rcfg, params, ids = model_case("stack")
    ((_, _), g_low), ((_, _), g_want) = losses_and_grads(
        cfg, rcfg, params, ids, dtype=jnp.bfloat16)
    assert worst_gap(g_low, g_want) > 10 * 2e-4


def test_init_matches_the_reference_tree():
    cfg = nh.TINY
    mine = nh.init_params(cfg, jax.random.PRNGKey(3))
    theirs = REF.init_params(ref_config(cfg), 3)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert nh.num_params(cfg) == sum(x.size for x in jax.tree.leaves(theirs))
    m = theirs["layer_1"]
    np.testing.assert_allclose(np.asarray(m["A_log"]),
                               np.log(np.arange(1, 9)), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))     # softplus
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    np.testing.assert_allclose(np.asarray(m["D"]), 1.0)
    for tree in (mine, theirs):
        assert float(jnp.abs(tree["layer_1"]["conv_w"]).max()) <= 0.5
    bias = np.asarray(theirs["layer_0"]["expert_bias"])
    assert bias.shape == (16,) and 0.01 < bias.std() < 0.15
    assert float(jnp.abs(mine["layer_0"]["expert_bias"]).max()) == 0.0


def test_published_count_of_the_benchmark_share():
    """Published layers 6-12 (E, M, E, M, E, M, *), 8 of 128 experts
    held, an eighth of the vocabulary: 528,093,120 parameters."""
    cfg = nh.NemotronHConfig(layer_kinds="EMEMEM*", held_experts=(0, 8),
                             vocab_size=16384)
    assert nh.num_params(cfg) == 528_093_120
    shapes = nh.param_shapes(cfg)
    assert shapes["layer_1"]["in_proj"] == (2688, 4096 + 6144 + 64)
    assert shapes["layer_1"]["conv_w"] == (4, 6144)
    assert shapes["layer_0"]["up"] == (8, 2688, 1856)
    assert shapes["layer_6"]["wk"] == (2688, 256)


def test_layer_kinds_from_the_published_pattern():
    """A cut file's pattern is its layers; a whole published pattern is
    read from `first_layer` on."""
    path = os.path.join(ROOT, "fedbench", "configs",
                        "nemotron3_nano_ep16.json")
    with open(path) as f:
        c = json.load(f)
    cut = nh.NemotronHConfig.from_published(c)
    whole = nh.NemotronHConfig.from_published(
        {**c, "hybrid_override_pattern":
         c["published"]["hybrid_override_pattern"]})
    assert cut.layer_kinds == whole.layer_kinds == "EMEMEM*"
    with pytest.raises(ValueError, match="layer kinds"):
        nh.NemotronHConfig.from_published({**c, "first_layer": 50,
                                           "hybrid_override_pattern":
                                           c["published"][
                                               "hybrid_override_pattern"]})


# ---------------- the ungated experts -------------------------------------

def test_relu2_experts_route_by_the_shared_router():
    """The experts are ungated (up, down) with relu^2, and the router
    is AFMoE's own function: picks by score plus bias, weights the
    unbiased scores over their sum times 2.5."""
    cfg = nh.TINY.replace(num_experts=4, experts_per_token=2)
    assert experts.weights_of(cfg) == experts.UNGATED == ("up", "down")
    np.testing.assert_allclose(
        np.asarray(cfg.expert_act(jnp.asarray([-2.0, 0.5, 3.0]))),
        [0.0, 0.25, 9.0])
    r = jnp.asarray([[2.0, 1.0, -1.0, 0.5]])
    idx, w = cfg.route(r, {"expert_bias": jnp.asarray([0, 0, 0, 2.0])})
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    s = 1.0 / (1.0 + np.exp(-np.asarray(r[0], np.float64)))
    want = np.array([s[i] for i in np.asarray(idx[0])])
    np.testing.assert_allclose(np.asarray(w[0]),
                               want / want.sum() * 2.5, rtol=1e-6)


SHARES = nh.TINY.replace(num_experts=32, experts_per_token=6,
                         held_experts=(0, 32), moe_chunk=L)


@functools.lru_cache(maxsize=None)
def share_case():
    """One expert layer's parameters with all 32 experts, its input and
    the uncut reference's whole expert layer (shared + all 32)."""
    rcfg = ref_config(SHARES.replace(layer_kinds="E"))
    params = REF.init_params(rcfg, seed=11)["layer_0"]
    h = jnp.asarray(np.random.RandomState(5).randn(
        L, SHARES.hidden_size).astype(np.float32))
    shared = REF._relu2(h, params["shared_up"], params["shared_down"])
    return rcfg, params, h, shared, shared + REF._experts(rcfg, params, h)


@functools.lru_cache(maxsize=None)
def share_part(s):
    """Chip `s`'s routed part (experts 2s, 2s + 1) by the program, and
    the uncut reference's restricted to those experts."""
    rcfg, params, h, _, _ = share_case()
    lo = 2 * s
    held = {**params, **{k: params[k][lo:lo + 2] for k in ("up", "down")}}
    y, _ = experts.expert_chunk(SHARES.replace(held_experts=(lo, 2)),
                                held, h, h @ params["router"])
    want = REF._experts({**rcfg, "held_experts": [lo, 2]}, held, h)
    return np.asarray(y), np.asarray(want)


@pytest.mark.parametrize("share", range(16))
def test_sixteen_shares_add_up_to_the_uncut_layer(share):
    """32 ungated experts over sixteen chips, 2 a chip, top-6: share
    `share`'s routed part equals the uncut reference's restricted to
    its experts, and the sixteen routed parts with the shared expert
    counted once equal the reference's whole expert layer, as the
    program's uncut layer does."""
    _, params, h, shared, whole = share_case()
    mine, want = share_part(share)
    np.testing.assert_allclose(mine, want, rtol=2e-4, atol=2e-5)
    routed = sum(share_part(s)[0] for s in range(16))
    np.testing.assert_allclose(np.asarray(shared) + routed,
                               np.asarray(whole), rtol=2e-4, atol=2e-5)
    if share == 0:
        mixed, _ = nh.mixture(SHARES, params, h[None])
        np.testing.assert_allclose(np.asarray(mixed[0]), np.asarray(whole),
                                   rtol=2e-4, atol=2e-5)


# ---------------- the mixer counters --------------------------------------

def test_mixer_counters_name_and_read_the_stats():
    """`ssm<l>_carry` is the mean over heads and chunks of the carried
    share, `ssm<l>_chunks` the cohort's chunks, after the expert
    layers' counters."""
    from commefficient_tpu.telemetry import metrics as tm
    cfg, _, params, ids = model_case("stack")
    loss = jax.jit(nh.make_lm_loss(cfg, PAD))
    _, aux = loss(params, batch_of(ids, W=2), jnp.ones((2, 1)))
    load, stat = aux
    assert load.shape == (2, 3, 16 + 4) and stat.shape == (2, 3, 2)
    vec = jnp.concatenate([jnp.zeros(tm.NUM_METRICS),
                           *tm.layer_vectors(aux, cfg.mixer_layers)])
    named = tm.named(vec, cfg.mixer_layers)
    assert list(named)[-7:] == ["moe2_live_peak", "ssm0_carry",
                                "ssm0_chunks", "ssm1_carry", "ssm1_chunks",
                                "ssm2_carry", "ssm2_chunks"]
    # two sequences of 32 positions in chunks of 8
    h = jax.jit(lambda p, i: nh.hidden(cfg, p, i))(params, ids)
    for l in range(3):
        assert named[f"ssm{l}_chunks"] == 2 * L // cfg.ssm_chunk == 8
        carried = float(np.asarray(h[2])[:, l, 0].sum()) / 8
        assert named[f"ssm{l}_carry"] == pytest.approx(carried, rel=1e-6)
        assert 0.0 < named[f"ssm{l}_carry"] < 1.0
    # without mixer layers the same vector names no ssm counter
    assert not any(k.startswith("ssm") for k in tm.named(
        vec[:-6], 0))


# ---------------- through the normal driver ------------------------------

def run_driver(tmp_path, *extra):
    from commefficient_tpu.training import gpt2_train
    return gpt2_train.main([
        "--test", "--model", "nemotron", "--dataset_name", "PERSONA",
        "--dataset_dir", str(tmp_path / "ds"),
        "--local_momentum", "0.0", "--num_workers", "4",
        "--local_batch_size", "2", "--num_epochs", "1",
        "--valid_batch_size", "4", "--lr_scale", "0.1",
        "--journal_path", str(tmp_path / "journal.jsonl"), *extra])


def test_driver_trains_nemotron(tmp_path):
    """`--model nemotron` through gpt2_train.main, FedModel and
    round_step: finite losses, the three expert layers' load counters
    and the three mixers' counters in the journal."""
    assert run_driver(tmp_path, "--mode", "uncompressed")
    rounds = [json.loads(l) for l in open(tmp_path / "journal.jsonl")]
    rounds = [r for r in rounds if r.get("event") == "round"]
    assert rounds
    m = rounds[-1]["metrics"]
    assert nh.TINY.expert_layers == nh.TINY.mixer_layers == 3
    for l in range(3):
        assert m[f"moe{l}_routed"] > 0
        assert m[f"ssm{l}_chunks"] == m["ssm0_chunks"] > 0
        assert 0.0 < m[f"ssm{l}_carry"] < 1.0
    assert "moe3_routed" not in m and "ssm3_carry" not in m
    assert np.isfinite(m["train_loss"]) and m["examples"] > 0


def test_model_is_chosen_by_name():
    from commefficient_tpu.config import Config
    from commefficient_tpu.training import gpt2_train
    cfg = Config(model="nemotron", do_test=True)
    assert gpt2_train.decoder_config(cfg) == nh.TINY.replace(
        remat=cfg.do_remat)
