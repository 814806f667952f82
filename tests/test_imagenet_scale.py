"""ImageNet-scale shape + memory proof (VERDICT r2 missing #5 / next
#7): demonstrate that `max_local_batch` bounds the staging arrays at
ResNet50/224px shapes and that the round engine traces the full
FixupResNet50 training step at those shapes — the configuration of the
committed launch recipe (scripts/imagenet.sh, mirroring the
reference's tuned CommEfficient/imagenet.sh:2-21).

The real-data run needs an ImageNet on disk and a TPU pod; what is
checkable everywhere is (a) the sampler's memory math and (b) that the
whole sharded round program type-checks end to end at 224px ResNet50
shapes (jax.eval_shape traces the program — shapes, dtypes, shardings
— without spending the FLOPs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.data.sampler import FedSampler
from commefficient_tpu.federated import round as fround
from commefficient_tpu.models import build_model
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel.mesh import make_client_mesh

IMG = (224, 224, 3)
IMG_BYTES = int(np.prod(IMG)) * 4


def test_max_local_batch_bounds_staging_memory():
    """7 IID ImageNet clients carry ~183k images each; whole-client
    batches (-1) would size the static [W, B, 224, 224, 3] staging
    buffer by the LARGEST client — ~718 GiB. The recipe's
    --max_local_batch 64 caps B at 64 -> ~0.67 GiB, and clients simply
    participate in consecutive rounds on successive chunks."""
    W = 7
    data_per_client = np.full(W, 1_281_167 // W)  # ImageNet train, IID

    uncapped_B = int(data_per_client.max())
    uncapped_bytes = W * uncapped_B * IMG_BYTES
    assert uncapped_bytes > 500 * 2**30  # the hazard: ~718 GiB staging

    s = FedSampler(data_per_client, num_workers=W, local_batch_size=-1,
                   max_local_batch=64)
    assert s.round_batch_size == 64
    capped_bytes = W * s.round_batch_size * IMG_BYTES
    assert capped_bytes < 2**30  # < 1 GiB
    # every image still seen exactly once per epoch
    assert (s.steps_per_epoch() * W * 64 >= data_per_client.sum())

    # chunked participation really happens: one epoch's rounds visit
    # each client ceil(n/64) times in order, no index repeated
    small = FedSampler(np.full(W, 130), num_workers=W,
                       local_batch_size=-1, max_local_batch=64)
    seen = {c: [] for c in range(W)}
    for r in small.epoch():
        for w, cid in enumerate(r.client_ids):
            n_valid = int(r.mask[w].sum())
            seen[int(cid)].extend(r.idx_within[w, :n_valid].tolist())
    for c in range(W):
        assert sorted(seen[c]) == list(range(130))


def test_round_engine_traces_resnet50_at_224px():
    """The recipe's training step — FixupResNet50, uncompressed mode,
    virtual momentum, 7 workers — type-checks through the sharded
    round engine at full 224px shapes (eval_shape: no FLOPs, real
    tracing through shard_map/psum/vmap/grad)."""
    W = 7
    mesh = make_client_mesh(1)  # 7 workers on 1 shard: W % shards == 0
    model = build_model("FixupResNet50", num_classes=1000)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1,) + IMG, jnp.float32)))
    vec_shape = jax.eval_shape(lambda p: flatten_params(p)[0], params)
    D = int(vec_shape.shape[0])
    assert D > 20_000_000  # ResNet50-class parameter count

    # a concrete (tiny) param template only for unravel's tree-def;
    # the traced weights stay abstract
    params_c = model.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8, 8, 3), jnp.float32))
    _, unravel = flatten_params(params_c)

    cfg = Config(mode="uncompressed", error_type="virtual",
                 virtual_momentum=0.9, local_momentum=0.0,
                 weight_decay=1e-4, microbatch_size=-1, num_workers=W,
                 num_clients=W, grad_size=D, k=1_000_000, num_rows=1,
                 num_cols=10_000_000, do_iid=True).validate()

    def loss_fn(p, batch, mask):
        xb, yb = batch
        logits = model.apply(p, xb)
        logp = jax.nn.log_softmax(logits)
        per = -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        return (per * mask).sum() / denom, ()

    train_round = fround.make_train_fn(loss_fn, unravel, cfg, mesh)

    B = 2  # per-client batch kept tiny: shapes under test are the
    #        224px images and the 25M-param flat vector, not B
    S = jax.ShapeDtypeStruct
    server = fround.ServerState(S((D,), jnp.float32), S((D,), jnp.float32),
                                S((D,), jnp.float32), S((), jnp.int32))
    clients = fround.ClientState(*(S((0,), jnp.float32),) * 3)
    batch = fround.RoundBatch(
        S((W,), jnp.int32),
        (S((W, B) + IMG, jnp.float32), S((W, B), jnp.int32)),
        S((W, B), jnp.float32))

    out = jax.eval_shape(
        lambda s, c, b: train_round(s, c, b, 0.1, jax.random.PRNGKey(0)),
        server, clients, batch)
    new_server = out[0]
    assert new_server.ps_weights.shape == (D,)
    assert new_server.Vvelocity.shape == (D,)
