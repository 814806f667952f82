"""The expert layer's own backward pass (`models/experts._chunks`):
every chunk's weight gradient added into one accumulator a weight,
every chunk's rows' written in place of its inputs. Against the plain
formulation it replaced: `jax.lax.map` over the chunks, each a
`jax.checkpoint` of `expert_chunk`, differentiated by JAX's own rules
(the grouped products' custom backward taken out too), on the TINY
SmallThinker and Trinity configurations at several chunks, with some
experts held and with all held, and routings that put chunks into
every capacity and into the every-pick fallback."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models import afmoe, experts
from commefficient_tpu.models import smallthinker as st

N, L = 2, 64                 # 8 chunks of TINY's 16 positions


def plain_layer(cfg, p, h2, r):
    """The layer as the program formed it before its own backward
    pass: a map of rematerialised chunks."""
    B, S, H = h2.shape
    c = experts.block_of(S, cfg.moe_chunk)
    chunk = jax.checkpoint(functools.partial(experts.expert_chunk, cfg, p))
    y, load = jax.lax.map(lambda xs: chunk(*xs),
                          (h2.reshape(B * S // c, c, H),
                           r.reshape(B * S // c, c, -1)))
    return (y.reshape(B, S, H),
            experts.merge_loads(load.reshape(B, S // c, -1), 1))


@pytest.fixture
def by_jax_rules(monkeypatch):
    """The grouped products differentiated by JAX's own rules."""
    monkeypatch.setattr(experts, "_ffn",
                        lambda cfg, *a: experts._ffn_fwd(cfg, *a)[0])


# (configuration, held experts, router width, skew of each chunk toward
# the held experts): with some held, the chunks' skews put their held
# picks under the first capacity, between the two, and over both
CASES = {
    "smallthinker_some_held": (st.TINY, (2, 2), 16,
                               (0.0, 0.9, 4.0, 0.0, 0.9, 4.0, 0.6, 0.0)),
    "smallthinker_all_held": (st.TINY, (0, 8), 8, (0.0,) * 8),
    "trinity_some_held": (afmoe.TINY, (6, 2), 32,
                          (0.0, 0.9, 4.0, 0.0, 0.9, 4.0, 0.6, 0.0)),
    "trinity_all_held": (afmoe.TINY, (0, 16), 16, (0.0,) * 8),
}


def case_inputs(name, seed=0):
    base, held, experts_, skews = CASES[name]
    cfg = base.replace(held_experts=held, num_experts=experts_)
    rng = np.random.RandomState(seed)
    H, F, n = cfg.hidden_size, cfg.expert_width, held[1]
    p = {"gate": rng.randn(n, H, F), "up": rng.randn(n, H, F),
         "down": rng.randn(n, F, H), "router": rng.randn(H, experts_),
         "expert_bias": rng.randn(experts_) * 0.05}
    p = {k: jnp.asarray(v.astype(np.float32) * 0.1) for k, v in p.items()}
    r = rng.randn(N * L // cfg.moe_chunk, cfg.moe_chunk,
                  experts_).astype(np.float32)
    for i, skew in enumerate(skews):
        r[i, :, held[0]:held[0] + n] += skew
    h2 = rng.randn(N, L, H).astype(np.float32)
    dy = rng.randn(N, L, H).astype(np.float32)
    return (cfg, p, jnp.asarray(h2), jnp.asarray(r.reshape(N, L, -1)),
            jnp.asarray(dy))


def branches_taken(cfg, p, r):
    """The branch each chunk takes: an index into the capacities, their
    count for every pick."""
    caps = experts.compact_capacities(cfg, cfg.moe_chunk)
    return [int(experts._branch_of(
        caps, experts.sorted_picks(cfg, rc, p)[2]))
        for rc in r.reshape(-1, cfg.moe_chunk, r.shape[-1])]


def assert_close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.abs(want).max())
    assert scale > 0.0, what
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale,
                               err_msg=what)


@pytest.mark.parametrize("name", list(CASES))
def test_layer_backward_matches_the_plain_map(name, by_jax_rules):
    """dgate, dup, ddown, dh2 and dr of the layer's own backward pass
    equal `jax.vjp` of the plain map to float32 rounding, and so do y
    and the loads; with some experts held the chunks ran in every
    capacity and with every pick's row."""
    cfg, p, h2, r, dy = case_inputs(name)
    caps = experts.compact_capacities(cfg, cfg.moe_chunk)
    if cfg.held_experts[1] < cfg.num_experts:
        assert len(caps) == 2
        assert set(branches_taken(cfg, p, r)) == {0, 1, 2}
    else:
        assert caps == ()
    (y, load), back = jax.vjp(
        lambda p, h2, r: experts.expert_layer(cfg, p, h2, r), p, h2, r)
    (y_want, load_want), back_want = jax.vjp(
        lambda p, h2, r: plain_layer(cfg, p, h2, r), p, h2, r)
    zero = jnp.zeros_like(load)
    got = back((dy, zero))
    want = back_want((dy, jnp.zeros_like(load_want)))
    assert_close(y, y_want, "y")
    # the plain map's load with the chunks counted before its last entry
    chunks = L // cfg.moe_chunk
    np.testing.assert_array_equal(
        np.asarray(load), np.concatenate(
            [np.asarray(load_want)[:, :-1], np.full((N, 1), chunks),
             np.asarray(load_want)[:, -1:]], axis=1))
    for k in experts.WEIGHTS:
        assert_close(got[0][k], want[0][k], k)
    # the router's own parameters only choose the picks
    for k in ("router", "expert_bias"):
        assert float(jnp.abs(got[0][k]).max()) == 0.0
        assert float(jnp.abs(want[0][k]).max()) == 0.0
    assert_close(got[1], want[1], "dh2")
    assert_close(got[2], want[2], "dr")


def test_products_backward_is_jaxs_own():
    """The grouped products' own backward (the weights read transposed,
    passed in or not) against JAX's rules on the same products."""
    cfg, p, h2, r, dy = case_inputs("trinity_some_held")
    w = {k: p[k] for k in experts.WEIGHTS}
    prob, order, sizes = experts.sorted_picks(cfg, r[0, :16], p)
    C = 16 * cfg.experts_per_token
    rows = h2.reshape(-1, cfg.hidden_size)[order[:C] // 4 % 64]
    live = (jnp.arange(C) < sizes.sum())[:, None]
    ct = dy.reshape(-1, cfg.hidden_size)[:C]
    want = jax.vjp(lambda w, rows: experts._ffn_fwd(
        cfg, w, None, rows, sizes, live)[0], w, rows)[1](ct)
    for wt in (None, experts.transposed(w)):
        got = jax.vjp(lambda w, rows: experts._ffn(
            cfg, w, wt, rows, sizes, live), w, rows)[1](ct)
        for k in experts.WEIGHTS:
            assert_close(got[0][k], want[0][k], k)
        assert_close(jnp.where(live, got[1], 0), jnp.where(live, want[1], 0),
                     "drows")


def lm_case(module, held, experts_):
    cfg = module.TINY.replace(held_experts=held, num_experts=experts_)
    if module is st:             # one period of SmallThinker's four kinds
        cfg = cfg.replace(num_layers=4, rope_layout=cfg.rope_layout[:4],
                          window_layout=cfg.window_layout[:4])
    params = module.init_params(cfg, jax.random.PRNGKey(3))
    # a router that sends most positions to the held experts in some
    # sequences and few in others
    for name, layer in params.items():
        if "router" in layer:
            layer["router"] = layer["router"].at[:, held[0]].add(0.3)
    rng = np.random.RandomState(4)
    ids = rng.randint(5, cfg.vocab_size, size=(2, 1, 1, L)).astype(np.int32)
    ids[..., L - 6:] = 4
    ids = jnp.asarray(ids)
    z = jnp.zeros((2, 1, 1), jnp.int32)
    batch = (ids, z, ids, jnp.zeros((2, 1), jnp.int32), ids)
    return cfg, params, batch, jnp.ones((2, 1), jnp.float32)


@pytest.mark.parametrize("module,held,experts_", [
    (st, (0, 2), 16), (st, (0, 8), 8),
    (afmoe, (4, 2), 16), (afmoe, (0, 16), 16)],
    ids=["smallthinker_some_held", "smallthinker_all_held",
         "trinity_some_held", "trinity_all_held"])
def test_whole_model_gradient_matches_the_plain_map(module, held, experts_,
                                                    by_jax_rules,
                                                    monkeypatch):
    """The next-token loss's gradient, every leaf, and its losses,
    with the layer's own backward pass and with the plain map (which
    the models called before)."""
    cfg, params, batch, mask = lm_case(module, held, experts_)
    loss = module.make_lm_loss(cfg, 4)

    def grads():
        return jax.jit(jax.value_and_grad(
            lambda p: loss(p, batch, mask)[0].sum()))(params)

    got = grads()
    monkeypatch.setattr(module, "expert_layer",
                        functools.partial(plain_layer))
    want = grads()
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    flat_g = jax.tree_util.tree_leaves_with_path(got[1])
    flat_w = jax.tree_util.tree_leaves(want[1])
    for (path, g), w in zip(flat_g, flat_w):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * max(float(np.abs(w).max()), 1e-6),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("module,held,experts_", [
    (st, (0, 2), 16), (afmoe, (0, 16), 16)],
    ids=["smallthinker_some_held", "trinity_all_held"])
def test_chunk_counter_reads_the_chunk_count(module, held, experts_):
    """`moe<l>_chunks` of the round's telemetry, from the loss's loads:
    every expert layer counts the cohort's chunks, two sequences of 64
    positions in chunks of 16 (as the language cells' two sequences of
    8,192 in chunks of 2,048 read 8)."""
    from commefficient_tpu.telemetry import metrics as tm
    cfg, params, batch, mask = lm_case(module, held, experts_)
    _, (load,) = jax.jit(module.make_lm_loss(cfg, 4))(params, batch, mask)
    assert load.shape == (2, cfg.expert_layers, held[1] + 4)
    vec = tm.expert_load_vector(load)
    named = tm.named(jnp.concatenate([jnp.zeros(tm.NUM_METRICS), vec]))
    for l in range(cfg.expert_layers):
        assert named[f"moe{l}_chunks"] == 2 * L // cfg.moe_chunk == 8
    assert f"moe{cfg.expert_layers}_chunks" not in named
