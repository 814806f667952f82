"""Layer scopes (ISSUE 27, commefficient_tpu/scopes.py): the lowered
round, gather, scatter and pack programs carry every scope name their
mode has; scopes are metadata, so the lowered program text without
locations, the three-program dispatch, its composed twin and three
rounds' ServerState are what they are without them; the
pallas_call is named. The language models' inner names are metadata
too, each alone on an op's path and inside `fwdbwd`."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import scopes
from commefficient_tpu.config import Config
from commefficient_tpu.federated import accounting, client, server
from commefficient_tpu.federated import round as fround
from commefficient_tpu.models import afmoe, decoder, experts, smallthinker
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel.mesh import make_client_mesh

D, W, B, POP = 64, 8, 4, 24

MODES = {
    # fused shard backward (no per-client state): no `residual`
    "sketch": dict(error_type="virtual", virtual_momentum=0.9, k=8,
                   num_rows=3, num_cols=32, num_blocks=1),
    "local_topk": dict(error_type="local", local_momentum=0.9, k=8),
    "true_topk": dict(error_type="virtual", virtual_momentum=0.9,
                      local_momentum=0.9, k=8),
}
# the names each mode's programs must carry (table A of ISSUE 27)
ROUND_SCOPES = {
    "sketch": {"fwdbwd", "unravel", "encode", "aggregate", "select",
               "server_state", "telemetry"},
    "local_topk": {"fwdbwd", "unravel", "residual", "aggregate",
                   "server_state", "telemetry"},
    "true_topk": {"fwdbwd", "unravel", "residual", "aggregate", "select",
                  "server_state", "telemetry"},
}


def _loss_fn(params, batch, mask):
    x, y = batch
    per_ex = 0.5 * (x @ params["w"] - y) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / denom
    return loss, (loss,)


def _build(mode, n_devices=8):
    cfg = Config(mode=mode, grad_size=D, weight_decay=5e-4,
                 num_workers=W, microbatch_size=-1, num_clients=POP,
                 donate_round_state=False,
                 **{"local_momentum": 0.0, "virtual_momentum": 0.0,
                    **MODES[mode]}).validate()
    vec, unravel = flatten_params({"w": jnp.zeros(D, jnp.float32)})
    mesh = make_client_mesh(n_devices)
    tr = fround.make_train_fn(_loss_fn, unravel, cfg, mesh)
    server_state = fround.init_server_state(cfg, vec)
    clients = fround.init_client_state(cfg, POP, vec)
    return cfg, tr, server_state, clients


def _batch(seed):
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.choice(POP, W, replace=False).astype(np.int32))
    x = jnp.asarray(rng.randn(W, B, D).astype(np.float32))
    y = jnp.asarray(rng.randn(W, B).astype(np.float32))
    return fround.RoundBatch(ids, (x, y), jnp.ones((W, B), jnp.float32))


def _names(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r"[/(\"]fed_([a-z_]+)(?=[/)\"])", text))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_lowered_programs_carry_their_scopes(mode):
    cfg, tr, srv, clients = _build(mode)
    b = _batch(0)
    lr, key = jnp.float32(0.1), jax.random.PRNGKey(0)
    cohort = tr.gather(clients, b.client_ids)
    got = _names(jax.jit(tr.round_step).lower(srv, cohort, b, lr, key))
    assert got == ROUND_SCOPES[mode]
    assert got <= set(scopes.SCOPES)
    # transformations wrap the name and keep it a whole component:
    # the per-client path vmaps the scope, the fused shard backward
    # differentiates inside it
    text = jax.jit(tr.round_step).lower(
        srv, cohort, b, lr, key).as_text(debug_info=True)
    assert ("fed_fwdbwd/transpose(jvp(" in text if mode == "sketch"
            else "vmap(fed_fwdbwd)/" in text)
    if fround._has_errors(cfg) or fround._has_velocities(cfg):
        assert _names(jax.jit(tr.gather_fn).lower(
            clients, b.client_ids)) == {"gather_cohort"}
        assert _names(jax.jit(tr.scatter_fn).lower(
            clients, b.client_ids, cohort)) == {"scatter_back"}
    # the composed program (the scanned span's step) holds them all
    full = _names(jax.jit(tr.round_full).lower(srv, clients, b, lr, key))
    assert ROUND_SCOPES[mode] <= full
    if fround._has_errors(cfg):
        assert {"gather_cohort", "scatter_back"} <= full
    assert _names(jax.jit(accounting.pack_change_bits).lower(
        srv.ps_weights)) == {"pack_change_bits"}


def test_scope_rejects_a_name_outside_the_table():
    with pytest.raises(ValueError, match="unknown layer scope"):
        scopes.scope("decode")
    assert all(not n.startswith(scopes.SCOPE_PREFIX)
               for n in scopes.SCOPES)


@pytest.fixture
def no_scopes(monkeypatch):
    """The program as it was before the scopes: every module's
    `scope` hands back a null context."""
    def off():
        for mod in (client, server, fround, accounting, afmoe,
                    smallthinker, decoder, experts):
            monkeypatch.setattr(mod, "scope",
                                lambda name: contextlib.nullcontext())
    return off


@pytest.mark.parametrize("mode", ["sketch", "local_topk"])
def test_scopes_are_metadata_three_rounds_bit_identical(mode, no_scopes):
    """Scoped three-program dispatch == its scoped composed twin ==
    the same programs traced with the scopes taken out: lowered text
    (locations stripped) equal, ServerState equal bit for bit after
    three rounds."""
    def run(strip):
        if strip:
            no_scopes()
        cfg, tr, srv, clients = _build(mode, n_devices=1)
        composed = jax.jit(tr.round_full)
        key = jax.random.PRNGKey(0)
        b0 = _batch(0)
        text = composed.lower(srv, clients, b0, 0.1, key).as_text()
        named = _names(composed.lower(srv, clients, b0, 0.1, key))
        sA, cA, sB, cB = srv, clients, srv, clients
        for r in range(3):
            b = _batch(r)
            sA, cA, _ = tr(sA, cA, b, 0.1, key)
            sB, cB, _ = composed(sB, cB, b, 0.1, key)
        for a, bb in zip(sA, sB):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
        return text, named, [np.asarray(x) for x in sA], \
            [np.asarray(x) for x in jax.tree.leaves(cA)]

    text1, named1, server1, clients1 = run(strip=False)
    text0, named0, server0, clients0 = run(strip=True)
    assert named1 >= ROUND_SCOPES[mode] and named0 == set()
    assert text1 == text0
    for a, b in zip(server1 + clients1, server0 + clients0):
        np.testing.assert_array_equal(a, b)


# the language models' newer inner names, each in both models' loss and
# gradient but `dense_mlp`, which only Trinity's dense layer lays
NEW_INNER = {"attn_proj", "dense_mlp", "norm", "head_loss", "unravel"}
MODELS = {"trinity": afmoe, "smallthinker": smallthinker}


def _laid(name):
    return NEW_INNER - ({"dense_mlp"} if name == "smallthinker" else set())


def _lm_grad(name):
    """The tiny model's loss and gradient as the fused backward runs
    them: the cohort loss lifted to the flat vector, under `fwdbwd`."""
    mod = MODELS[name]
    cfg = mod.TINY
    vec, unravel = flatten_params(mod.init_params(cfg, jax.random.PRNGKey(0)))
    flat_loss = client.make_flat_loss_fn(mod.make_lm_loss(cfg, 0), unravel)
    rcfg = Config(mode="uncompressed", grad_size=vec.size, num_workers=2,
                  weight_decay=5e-4, local_momentum=0.0,
                  virtual_momentum=0.0).validate()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 1, 1, 16), 1,
                             cfg.vocab_size)
    mask = jnp.ones((2, 1), jnp.float32)

    def grad(w):
        g, losses, _, _ = client.fused_shard_grads(flat_loss, w, (ids,),
                                                   mask, rcfg)
        return g, losses

    lowered = jax.jit(grad).lower(vec)
    compiled = lowered.compile()
    g, losses = compiled(vec)
    return {"text": lowered.as_text(), "names": _names(lowered),
            "compiled": compiled.as_text(), "grad": np.asarray(g),
            "losses": np.asarray(losses)}


@pytest.fixture(scope="module")
def lm_scoped():
    """`_lm_grad` of each model with its names, made once."""
    made = {}

    def get(name):
        if name not in made:
            made[name] = _lm_grad(name)
        return made[name]
    return get


@pytest.mark.parametrize("name", sorted(MODELS))
def test_model_names_are_metadata(name, lm_scoped, no_scopes):
    """With the inner names and without: the lowered loss and gradient
    (locations stripped) are the same text, loss and gradient the same
    bits; every new name is in the program, `dense_mlp` in Trinity's
    only."""
    scoped = lm_scoped(name)
    no_scopes()
    bare = _lm_grad(name)
    assert scoped["names"] & NEW_INNER == _laid(name)
    assert bare["names"] == set()
    assert scoped["text"] == bare["text"]
    for key in ("grad", "losses"):
        np.testing.assert_array_equal(scoped[key], bare[key])
    assert np.isfinite(scoped["losses"]).all()
    assert np.abs(scoped["grad"]).max() > 0


@pytest.mark.parametrize("name", sorted(MODELS))
def test_new_names_stand_alone_inside_fwdbwd(name, lm_scoped):
    """In the compiled program, an op that carries a new name carries
    no other inner name and lies under `fwdbwd`, so the outermost-name
    rule still gives it to `client_fwdbwd_ms`. Where the compiler
    merges instructions it joins their names with ';': each name is
    checked on its own."""
    text = lm_scoped(name)["compiled"]
    seen = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for path in op_name.split(";"):
            inner = set(re.findall(
                r"(?:^|[/(])fed_([a-z_]+)(?=[/):]|$)", path)) - {"fwdbwd"}
            new = inner & NEW_INNER
            if new:
                assert inner == new and len(new) == 1, path
                assert re.search(r"(?:^|[/(])fed_fwdbwd(?=[/):]|$)",
                                 path), path
                seen |= new
    assert seen == _laid(name)


def test_pallas_calls_are_named():
    """A kernel is found in a trace by name: every `pallas_call` of
    the repo (the flash forward; the grouped attention's forward and
    backward, through one call site) passes `name=`."""
    import inspect

    from commefficient_tpu.ops import attention

    src = inspect.getsource(attention)
    assert 'name="flash_fwd"' in src
    assert '"bw_attn_fwd"' in src and '"bw_attn_bwd"' in src
    calls = re.findall(r"pl\.pallas_call\((.*?)\)", src, re.S)
    assert len(calls) == 2
    assert all("name=" in c for c in calls)
