"""SmallThinker through the program's model against the benchmark's
plain reference (`fedbench/configs/smallthinker.py`) at a tiny size:
two periods of (full NoPE, 3 x window RoPE), hidden 64, 8 experts
top-2, window 8, 32 positions, seeded weights."""
import functools
import importlib.util
import os
from collections import Counter

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


def skewed(cfg, params, pull0, pull1):
    """Router columns so that experts 0 and 1 get the logits `pull0`
    and `pull1` times the embedding's first coordinate, whatever else
    the input holds (+50 takes every position, -50 none)."""
    out = dict(params)
    for i in range(cfg.num_layers):
        lp = dict(params[f"layer_{i}"])
        r = np.zeros(lp["router"].shape, np.float32)
        r[0, 0], r[0, 1] = pull0, pull1
        lp["router"] = lp["router"] * 0.01 + jnp.asarray(r)
        out[f"layer_{i}"] = lp
    return out


# (experts held of how many, the pulls on experts 0 and 1, whether
# only every fourth position is drawn to expert 0, the rows the chunks
# carry, the held experts no position may reach). With 2 of 16 held a
# chunk of 16 positions has 32 picks and capacities 8 and 16.
SKEWS = {
    "all_held": ((0, 8), 8, 50.0, -50.0, False, "every", [1]),
    "under_first_capacity": ((0, 2), 16, 50.0, 0.0, True, 8, []),
    "between_capacities": ((0, 2), 16, 50.0, -50.0, False, 16, [1]),
    "over_both_capacities": ((0, 2), 16, 50.0, 50.0, False, "every", []),
}


@pytest.mark.parametrize("case", list(SKEWS))
def test_skewed_router_drops_no_token(case):
    """One expert takes every position (or every fourth, or two do):
    the loads say so, the chunk runs in the smallest capacity that
    holds its picks and with every pick's row over both, every
    position still gets its experts' output, and the gradient of an
    unreached expert is exactly zero."""
    held, experts, pull0, pull1, some, branch, unreached = SKEWS[case]
    cfg = st.TINY.replace(num_layers=1, rope_layout=(0,),
                          window_layout=(0,), num_experts=experts,
                          held_experts=held)
    picks = cfg.moe_chunk * cfg.experts_per_token
    caps = st.compact_capacities(cfg, cfg.moe_chunk)
    assert caps == (() if held[1] == experts else (8, 16))
    rcfg = ref_config(cfg)
    params = REF.init_params(rcfg, seed=5)
    ids = ids_for(cfg, 2, seed=1, pad_tail=0)
    first = jnp.ones((cfg.vocab_size,), jnp.float32)
    if some:
        # even tokens are drawn to expert 0, odd ones pushed off it
        first = jnp.where(jnp.arange(cfg.vocab_size) % 2 == 0, 1.0, -1.0)
        ids = (ids | 1).at[:, ::4].add(1)
    params["embed"] = params["embed"].at[:, 0].set(first)
    params = skewed(cfg, params, pull0, pull1)
    _, load = st.hidden(cfg, params, ids)
    load = np.asarray(load)[:, 0]                # [N, held + 4]
    sizes, total, compact, chunks, peak = (
        load[:, :-4], load[:, -4], load[:, -3], load[:, -2], load[:, -1])
    assert total.tolist() == [2 * L, 2 * L]
    assert chunks.tolist() == [L // cfg.moe_chunk] * 2
    if some:
        assert (sizes[:, 0] == L // 4).all()
    else:
        assert sizes[:, 0].tolist() == [L, L]
        assert sizes[:, 1].tolist() == [L * (pull1 > 0)] * 2
    if branch == "every":
        assert compact.tolist() == [0, 0]
        assert (peak * picks > max(caps, default=0)).all()
    else:
        assert compact.tolist() == total.tolist()
        under = (0,) + caps
        assert (under[caps.index(branch)] < peak * picks).all() \
            and (peak * picks <= branch).all()
    got = st.logits(cfg, params, ids)
    want = jnp.stack([REF.sequence_logits(rcfg, params, ids[i])
                      for i in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    g = jax.grad(lambda p: st.logits(cfg, p, ids).sum())(params)
    g_ref = jax.grad(lambda p: sum(
        REF.sequence_logits(rcfg, p, ids[i]).sum() for i in range(2)))(
            params)
    assert_trees_close(g, g_ref, rtol=1e-3)
    for name in ("gate", "up", "down"):
        for e in unreached:
            assert float(jnp.abs(g["layer_0"][name][e]).max()) == 0.0
        assert float(jnp.abs(g["layer_0"][name][0]).max()) > 0.0


def chunk_inputs(cfg, T, seed=0):
    rng = np.random.RandomState(seed)
    H, F, n = cfg.hidden_size, cfg.expert_width, cfg.held_experts[1]
    p = {"gate": rng.randn(n, H, F), "up": rng.randn(n, H, F),
         "down": rng.randn(n, F, H)}
    p = {k: jnp.asarray(v.astype(np.float32) * 0.1) for k, v in p.items()}
    return (p, jnp.asarray(rng.randn(T, H).astype(np.float32)),
            jnp.asarray(rng.randn(T, cfg.num_experts).astype(np.float32)))


def test_compacted_branch_matches_full_width():
    """Two of eight experts held: a chunk of 32 positions has 64
    picks, about 16 of them held, and runs compacted to 32 rows.
    Against the full-width path on the same inputs: y, load and the
    gradient of every input."""
    cfg = st.TINY.replace(held_experts=(0, 2))
    assert st.compact_capacities(cfg, L) == (L,)
    p, h2, r = chunk_inputs(cfg, L)
    weight = jnp.asarray(
        np.random.RandomState(1).randn(L, cfg.hidden_size), jnp.float32)

    def full(p, h2, r):
        prob, order, sizes = st.sorted_picks(cfg, r)
        y = st.full_width(cfg, p, h2, prob, order, sizes)
        return (y * weight).sum(), (y, sizes)

    def mine(p, h2, r):
        y, load = st.expert_chunk(cfg, p, h2, r)
        return (y * weight).sum(), (y, load)

    (_, (y_want, sizes)), g_want = jax.value_and_grad(
        full, (0, 1, 2), has_aux=True)(p, h2, r)
    (_, (y_got, load)), g_got = jax.value_and_grad(
        mine, (0, 1, 2), has_aux=True)(p, h2, r)
    live = int(sizes.sum())
    assert 0 < live <= L
    assert np.asarray(load).tolist() == [
        *np.asarray(sizes).tolist(), 2 * L, 2 * L, live / (2 * L)]
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(g_want[2]).max()) > 0.0
    assert_trees_close(g_got, g_want)


@pytest.mark.parametrize("C", [L, 2 * L], ids=["capacity", "every_pick"])
def test_compacted_matches_full_width_at_every_row_count(C):
    """`compacted` alone on the same chunk, at its capacity and with
    every pick's row (the branch of a chunk no capacity holds):
    `full_width`'s y and gradients."""
    cfg = st.TINY.replace(held_experts=(0, 2))
    p, h2, r = chunk_inputs(cfg, L)
    weight = jnp.asarray(
        np.random.RandomState(1).randn(L, cfg.hidden_size), jnp.float32)

    def weighed(branch):
        def fn(p, h2, r):
            y = branch(p, h2, *st.sorted_picks(cfg, r))
            return (y * weight).sum(), y
        return jax.value_and_grad(fn, (0, 1, 2), has_aux=True)(p, h2, r)

    (_, y_want), g_want = weighed(functools.partial(st.full_width, cfg))
    (_, y_got), g_got = weighed(functools.partial(st.compacted, cfg, C))
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=2e-4, atol=2e-5)
    assert_trees_close(g_got, g_want)


def eqns_of(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns_of(sub)


CELL = st.SmallThinkerConfig(num_layers=4, held_experts=(0, 8),
                             vocab_size=18992)


def wide_pick_rows(fn, cfg, T):
    """Shapes, in the forward and backward trace of `fn` on a chunk of
    T positions, that hold a row a pick (T * k of them) at the hidden
    or the expert width."""
    H, F, k = cfg.hidden_size, cfg.expert_width, cfg.experts_per_token
    n = cfg.held_experts[1]
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    p = {"gate": f32(n, H, F), "up": f32(n, H, F), "down": f32(n, F, H)}

    def fwdbwd(p, h2, r):
        prob, order, sizes = st.sorted_picks(cfg, r)
        return jax.grad(
            lambda p, h2, prob: fn(p, h2, prob, order, sizes).sum(),
            (0, 1, 2))(p, h2, prob)

    jaxpr = jax.make_jaxpr(fwdbwd)(p, f32(T, H), f32(T, cfg.num_experts))
    return sorted({
        v.aval.shape for eqn in eqns_of(jaxpr.jaxpr) for v in eqn.outvars
        if len(v.aval.shape) > 1 and v.aval.shape[-1] in (H, F)
        and np.prod(v.aval.shape[:-1]) == T * k})


def test_compacted_branch_carries_no_full_width_rows():
    """At the benchmark cell's shapes (4,096 positions a chunk, top-6,
    8 of 64 held): a compacted branch, forward and transposed, holds
    no array of 24,576 rows by 2,560 or by 768; the full-width path
    does."""
    T = CELL.moe_chunk
    assert st.compact_capacities(CELL, T) == (6144, 12288)
    for C in (6144, 12288):
        assert wide_pick_rows(
            functools.partial(st.compacted, CELL, C), CELL, T) == []
    assert (T * 6, 2560) in wide_pick_rows(
        functools.partial(st.full_width, CELL), CELL, T)


def primitives(fn, *args):
    return sorted(str(eqn.primitive)
                  for eqn in eqns_of(jax.make_jaxpr(fn)(*args).jaxpr))


def test_every_expert_held_traces_no_branch():
    """With all experts held (the tiny preset, every whole model) no
    capacity is under the picks in all: the chunk is the full-width
    path alone, no `cond` and no loop, forward or backward."""
    cfg = st.TINY
    assert st.compact_capacities(cfg, L) == ()
    assert st.compact_capacities(st.SmallThinkerConfig(), 4096) == ()
    # half held: twice the uniform share is every pick
    assert st.compact_capacities(cfg.replace(held_experts=(0, 4)), L) == ()
    p, h2, r = chunk_inputs(cfg, L)

    def mine(p, h2, r):
        return st.expert_chunk(cfg, p, h2, r)[0].sum()

    def full(p, h2, r):
        return st.full_width(cfg, p, h2, *st.sorted_picks(cfg, r)).sum()

    def layer(cfg):
        return lambda p, h2, r: st.expert_layer(
            cfg, p, h2[None], r[None])[0].sum()

    for fn in (mine, jax.grad(mine, (0, 1, 2)), layer(cfg),
               jax.grad(layer(cfg), (0, 1, 2))):
        assert not {"cond", "while"} & set(primitives(fn, p, h2, r))
    share = cfg.replace(held_experts=(0, 2))
    p2, _, _ = chunk_inputs(share, L)

    def shared(p, h2, r):
        return st.expert_chunk(share, p, h2, r)[0].sum()

    assert Counter(primitives(shared, p2, h2, r))["cond"] == 1
    # backward (the layer's, over its chunks) the choice is a loop of
    # one trip or none for each of the two branches, and no second
    # `cond`: with one there the chip's compiler keeps a copy of the
    # round's old weights through the program's peak
    # (tests/test_tpu_compile.py holds the total)
    backward = Counter(primitives(jax.grad(layer(share), (0, 1, 2)), p2,
                                  h2, r))
    assert backward["cond"] == 1 and backward["while"] == 2
    got = Counter(primitives(jax.grad(mine, (0, 1, 2)), p, h2, r))
    want = Counter(primitives(jax.grad(full, (0, 1, 2)), p, h2, r))
    # the load vector's assembly is the only other work
    assert not want - got
    assert set(got - want) <= {"concatenate", "convert_element_type",
                               "div", "reduce_sum", "broadcast_in_dim"}


def test_load_counters_of_a_hand_built_load():
    """Two clients, one layer, two experts held; client 0's two chunks
    ran one compacted and one not, client 1's one chunk compacted."""
    from commefficient_tpu.telemetry import metrics as tm
    assert tm.LOAD_COUNTERS == ("routed", "max_load", "min_load",
                                "absent_share", "compact_share", "chunks",
                                "live_peak")
    names = tm.metric_names(tm.NUM_METRICS + 2 * len(tm.LOAD_COUNTERS))
    assert names[:tm.NUM_METRICS] == tm.METRIC_NAMES
    assert names[tm.NUM_METRICS:] == tuple(
        f"moe{l}_{c}" for l in range(2) for c in tm.LOAD_COUNTERS)
    assert names.index("moe1_compact_share") == tm.NUM_METRICS + 11
    assert names.index("moe1_chunks") == tm.NUM_METRICS + 12
    assert names.index("moe1_live_peak") == tm.NUM_METRICS + 13
    chunks = jnp.asarray([[[3.0, 1.0, 32.0, 32.0, 1.0, 4 / 32],
                           [20.0, 4.0, 32.0, 0.0, 1.0, 24 / 32]]])
    one = st.merge_loads(chunks, 1)              # client 0: [1, held + 4]
    assert np.asarray(one).tolist() == [[23.0, 5.0, 64.0, 32.0, 2.0, 0.75]]
    load = jnp.stack(
        [one, jnp.asarray([[2.0, 2.0, 32.0, 32.0, 1.0, 4 / 32]])])
    vec = np.asarray(tm.expert_load_vector(load.reshape(2, 1, 6)))
    np.testing.assert_allclose(
        vec, [32.0, 25.0, 7.0, 1 - 32 / 96, 64 / 96, 3.0, 0.75], rtol=1e-6)
    # one chunk compacted of two
    half = np.asarray(tm.expert_load_vector(one[None]))
    assert half[4] == 0.5 and half[5] == 2.0 and half[6] == 0.75


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
    assert float(load[-3]) == L * cfg.experts_per_token
    # 64 picks, about 16 of them held, 32 rows carried
    assert float(load[-2]) == float(load[-3])
    assert float(load[-1]) * 64 == float(load[:-3].sum()) <= 32
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
        assert m[f"moe{l}_compact_share"] == 0.0
        assert m[f"moe{l}_chunks"] == m["moe0_chunks"] > 0
        assert float(m[f"moe{l}_chunks"]).is_integer()
        assert m[f"moe{l}_live_peak"] == 1.0
    assert np.isfinite(m["train_loss"]) and m["examples"] > 0


def test_driver_refuses_a_per_client_mode(tmp_path):
    """The cohort loss trains only through the fused backward."""
    with pytest.raises(ValueError, match="whole cohort"):
        run_driver(tmp_path, "--mode", "local_topk", "--error_type",
                   "local", "--k", "10")
