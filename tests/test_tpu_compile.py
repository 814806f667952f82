"""The chip's compiler, asked without the chip.

The TPU compiler installed beside JAX compiles for a chip that is
described, not attached (`jax.experimental.topologies`), so what
Mosaic refuses is found here at no chip time. Kept to one file, the
topology described inside a module-scoped fixture (never at import:
every xdist worker imports this file, and only the worker that runs
it may load the TPU library), no child process, the persistent
compile cache off (an entry written for a described chip cannot be
read back without one).

Cases: the flash-attention forward (models/gpt2.py takes it for every
L >= 256 on a TPU), at GPT2-small head shapes; the grouped, windowed
attention's forward and backward kernels at the language cells'
shapes (8,192 positions, 32/4 and 28/4 heads of 128), inside their
VMEM limit. And the two client state-motion programs at the shapes of the benchmark's local top-k
cell (2 x 100 clients x D=6,568,640, 16 a round): the rows must move
as whole tiles, which only the chip's compiler can say. And the
per-client `masked_topk` at that cell's `[16, D]`: its threshold's
sample must be a strided slice, not a gather.

And the language model's round program whole, attention by the
kernels as a TPU runs it, with one chunk of its expert layer alone:
compacted to the held experts' rows it must move a fraction of the
full-width path's bytes. And the expert layer over its chunks, forward
and backward: its grouped products counted in the lowered program, and
no weight-sized zeros or copies inside the backward's loops.

A compile that passes is not a chip run: chip_smoke.py is.
"""
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from commefficient_tpu.ops import attention, flat

FLAGSHIP = dict(d=6_568_640, c=500_000, r=5, num_blocks=20)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("L", [256, 1024])
def test_flash_forward_compiles(one_chip, L, dtype):
    shape = ((4, 12, L, 64), dtype)
    block = attention.DEFAULT_BLOCK
    _compile(lambda q, k, v: attention._flash_fwd_pallas(
        q, k, v, 0.125, block, block), shape, shape, shape,
        sharding=one_chip)


@pytest.mark.parametrize("heads,window", [
    ((32, 4), 2048), ((32, 4), None), ((28, 4), 4096), ((28, 4), None),
    ((32, 2), None)],
    ids=["trinity-window", "trinity-full", "smallthinker-window",
         "smallthinker-full", "nemotron-full"])
def test_blockwise_kernels_compile_at_the_cells_shapes(one_chip, heads,
                                                       window):
    """Two sequences of 8,192 positions in blocks of 512, Dh 128: the
    forward and the backward kernel, one grid step per visible pair."""
    (Hq, Hkv), (B, L, Dh) = heads, (2, 8192, 128)
    q, kv = ((B, L, Hq, Dh), jnp.float32), ((B, L, Hkv, Dh), jnp.float32)
    lse = ((B, Hkv, Hq // Hkv, L), jnp.float32)
    fwd = _compile(lambda q, k, v: attention._bw_fwd_pallas(
        q, k, v, window, 512), q, kv, kv, sharding=one_chip)
    bwd = _compile(lambda q, k, v, o, do, lse: attention._bw_bwd_pallas(
        q, k, v, o, do, lse, window, 512), q, kv, kv, q, q, lse,
        sharding=one_chip)
    pairs = len(attention.pair_table(L // 512, window, 512)[0])
    for compiled, name in ((fwd, "bw_attn_fwd"), (bwd, "bw_attn_bwd")):
        text = compiled.as_text()
        assert name in text
        # the pair table rides in as two s32[pairs] scalar operands
        assert f"s32[{pairs}]" in text


def test_blockwise_backward_compiles_for_bfloat16_operands(one_chip):
    """The bfloat16 control (Config.do_bf16) hands the kernels bfloat16
    q, k, v, o and do; the backward's row sums of do * o go to float32
    scratch, which the chip's compiler takes only as float32."""
    (B, L, Hq, Hkv, Dh) = (2, 8192, 32, 2, 128)
    q, kv = ((B, L, Hq, Dh), jnp.bfloat16), ((B, L, Hkv, Dh), jnp.bfloat16)
    lse = ((B, Hkv, Hq // Hkv, L), jnp.float32)
    bwd = _compile(lambda q, k, v, o, do, lse: attention._bw_bwd_pallas(
        q, k, v, o, do, lse, None, 512), q, kv, kv, q, q, lse,
        sharding=one_chip)
    assert "bw_attn_bwd" in bwd.as_text()


# ---------------------------------------------------------------------------
# the local top-k cell's per-client selection


def test_topk_threshold_sample_is_a_strided_slice(one_chip):
    """`jax.vmap`-ed `masked_topk` at the cell's `f32[16, 6568640]`,
    k = 50,000: the threshold's ~1M sample is one `slice` with a step
    (1.12 GB accessed). As `sq[::6]` it traced to a `gather` of
    1,094,774 16-wide columns (`fusion:f32[1094774,16]`, 11.32 GB
    accessed, 17.6 ms of the cell's 64.5 ms round on the chip)."""
    d, k = FLAGSHIP["d"], 50_000
    assert d > flat.TOPK_THRESHOLD_MIN_D
    v = jax.ShapeDtypeStruct((16, d), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x: flat.masked_topk(x, k)).lower(v).compile()
    text = compiled.as_text()
    assert not re.findall(r" gather\(", text)
    assert "f32[1094774,16]" not in text
    stride = d // flat._TOPK_SAMPLE
    assert f"[0:{d}:{stride}]" in text
    assert compiled.cost_analysis()["bytes accessed"] < 3e9


# ---------------------------------------------------------------------------
# client state motion at the local top-k cell's shapes


@pytest.fixture(scope="module")
def state_motion(topo, one_chip):
    """The compiled cohort-gather and scatter-back of the real round
    factory, for one described chip (one_chip: the compile cache
    off), built as `scripts/state_motion_layout.py` builds them."""
    import importlib.util
    import sys

    from commefficient_tpu.federated.round import lane_rows

    spec = importlib.util.spec_from_file_location(
        "state_motion_layout",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts",
            "state_motion_layout.py"))
    layout = importlib.util.module_from_spec(spec)
    sys.modules["state_motion_layout"] = layout
    spec.loader.exec_module(layout)
    D, pop, W = FLAGSHIP["d"], 100, 16
    gather, scatter, _ = layout.compile_state_motion(
        topo.devices[:1], D, pop, W)
    return {"gather": gather, "scatter": scatter,
            "block_bytes": 2 * pop * lane_rows(D) * 128 * 4,
            "cohort_bytes": 2 * W * D * 4}


@pytest.mark.parametrize("program", ["gather", "scatter"])
def test_state_motion_block_is_tiled_over_the_rows_own_dims(
        state_motion, program):
    """`f32[100,51320,128]{2,1,0:T(8,128)}`: the (8, 128) tiles lie
    over one client's coordinates, so a row is whole tiles in one
    piece — not `f32[100,6568640]{1,0:T(8,128)}`, where a row is one
    sublane of tiles shared with seven other clients."""
    text = state_motion[program].as_text()
    blocks = set(re.findall(
        r"= (f32\[100,[\d,]+\]\{[^}]*\}) parameter\(", text))
    assert blocks == {"f32[100,51320,128]{2,1,0:T(8,128)}"}, blocks


def test_gather_moves_rows_without_the_gather_op(state_motion):
    """No `gather` instruction (the compiler made 201 strided pieces
    and 10 GB of traffic a table of it): a row loop a table whose
    body is one in-place copy of a `[1, 51320, 128]` row, and traffic
    within 1.6x of the passes each table needs (the rows, the tile
    transpose, the cut)."""
    compiled = state_motion["gather"]
    text = compiled.as_text()
    assert not re.findall(r" gather\(", text)
    assert len(re.findall(r" while\(", text)) == 2
    assert len(set(re.findall(
        r"%(dynamic-slice_dynamic-update-slice_fusion\S*) = "
        r"f32\[16,51320,128\]", text))) == 2
    assert "f32[1,6568640]" not in text and "f32[1,6568960]" not in text
    # the compiler counts a loop at one trip: fifteen more of a row
    # read and written, for both tables
    accessed = (compiled.cost_analysis()["bytes accessed"]
                + 15 * 2 * state_motion["cohort_bytes"] / 16)
    assert accessed <= 1.6 * 6 * state_motion["cohort_bytes"], accessed


def test_scatter_writes_whole_tile_rows_in_place(state_motion):
    """The donated blocks are the results (no copy of 5 GB), and the
    row writes are `[1, 51320, 128]` pieces, never a `[1, D]` row
    padded to eight sublanes."""
    compiled = state_motion["scatter"]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == state_motion["block_bytes"]
    assert mem.temp_size_in_bytes < 3 * state_motion["cohort_bytes"]
    text = compiled.as_text()
    assert "f32[1,51320,128]" in text
    assert "f32[1,6568640]" not in text and "f32[1,6568960]" not in text


# ---------------------------------------------------------------------------
# the same two programs on a four-chip `clients` mesh


@pytest.fixture(scope="module")
def state_motion_mesh(topo, one_chip, state_motion):
    """As `state_motion`, the blocks sharded over the four described
    chips (`scripts/state_motion_layout.py --devices 4`)."""
    import sys
    layout = sys.modules["state_motion_layout"]
    D, pop, W = FLAGSHIP["d"], 100, 16
    gather, scatter, _ = layout.compile_state_motion(
        topo.devices[:4], D, pop, W)
    return {"gather": gather, "scatter": scatter,
            "share_bytes": state_motion["block_bytes"] // 4}


@pytest.mark.parametrize("program", ["gather", "scatter"])
def test_mesh_state_motion_takes_a_quarter_of_the_blocks(
        state_motion_mesh, program):
    """A device is handed its 25 clients of each block (plus ids and,
    for the scatter, its share of the cohort) and no collective
    gathers a block: the cohort crosses chips, the blocks never do."""
    compiled = state_motion_mesh[program]
    share = state_motion_mesh["share_bytes"]
    args = compiled.memory_analysis().argument_size_in_bytes
    assert share <= args < 1.2 * share, (args, share)
    text = compiled.as_text()
    assert "f32[25,51320,128]{2,1,0:T(8,128)} parameter(" in text
    assert "f32[100,51320,128]" not in text


@pytest.mark.xfail(strict=True, reason=(
    "round.take_rows(sharded=True) leaves a mesh's cohort gather to "
    "GSPMD's partitioning of tiles[ids]: XLA's gather op per shard, 52 "
    "gather instructions and 16.5 GB accessed a device where 1.3 GB of "
    "rows move (a shard_map row loop deadlocked the CPU runtime's "
    "virtual devices; ROADMAP S3, PERF.md section 7). The branch and "
    "this marker go once the row loop is shown on a real mesh."))
def test_mesh_gather_moves_rows_without_the_gather_op(state_motion_mesh):
    compiled = state_motion_mesh["gather"]
    assert not re.findall(r" gather\(", compiled.as_text())
    assert compiled.cost_analysis()["bytes accessed"] < 4e9


# ---------------- the language model's round program, whole ---------------

def _lm_round(topo, module, config_file):
    """(cfg, compiled): the round program of the benchmark cell whose
    configuration is `config_file` (two sequences of 8,192 positions,
    uncompressed with momentum), compiled for one described chip as
    FedModel would dispatch it there: attention by the kernels, which a
    process on a CPU would not take by itself."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "_on_tpu", lambda: True)
        return _lm_round_on_tpu(topo, module, config_file)


def _lm_round_on_tpu(topo, module, config_file):
    import json
    import numpy as np
    from jax.flatten_util import ravel_pytree
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated import round as fround

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "fedbench", "configs",
                           config_file)) as f:
        published = json.load(f)
    mcfg = type(module.TINY).from_published(
        published, num_experts=published["router_width"],
        held_experts=tuple(published["held_experts"]))
    W, L, D = 2, 8192, module.num_params(mcfg)
    mesh = Mesh(np.array(topo.devices[:1]), ("clients",))
    rep, cl = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    holder = {}

    def flat():
        vec, holder["unravel"] = ravel_pytree(jax.tree.map(
            lambda s: jnp.zeros(s, jnp.float32), module.param_shapes(mcfg),
            is_leaf=lambda x: isinstance(x, tuple)))
        return vec

    jax.eval_shape(flat)
    cfg = Config(mode="uncompressed", error_type="none",
                 virtual_momentum=0.9, local_momentum=0.0, num_workers=W,
                 local_batch_size=1, weight_decay=0.0, num_clients=64,
                 expert_load_layers=mcfg.expert_layers,
                 mixer_layers=getattr(mcfg, "mixer_layers", 0)) \
        .replace(grad_size=D).validate()
    handle = fround.make_train_fn(module.make_lm_loss(mcfg, 4),
                                  holder["unravel"], cfg, mesh)

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    server = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype, rep), jax.eval_shape(
            lambda: fround.init_server_state(
                cfg, jnp.zeros((D,), jnp.float32))))
    cohort = fround.CohortState(
        *(shaped((W,), jnp.float32, cl) for _ in range(3)))
    ids = shaped((W, 1, 1, L), jnp.int32, cl)
    batch = fround.RoundBatch(
        client_ids=shaped((W,), jnp.int32, rep),
        data=(ids, shaped((W, 1, 1), jnp.int32, cl), ids,
              shaped((W, 1), jnp.int32, cl), ids),
        mask=shaped((W, 1), jnp.float32, cl))
    compiled = jax.jit(
        handle.round_step, donate_argnums=handle.round_donate_argnums) \
        .lower(server, cohort, batch, shaped((), jnp.float32, rep),
               shaped((2,), jnp.uint32, rep)).compile()
    return cfg, compiled


def _assert_attention_by_the_kernels(text, pairs):
    """Every layer's attention is the kernels: a forward in the forward
    pass, its rematerialised twin in the backward pass and one backward,
    each over its layer kind's pair table (`pairs`: {pairs a layer:
    layers}), each under its layer's attention scope, which the
    benchmark's `attention_*_ms` read from the trace."""
    calls = re.findall(r"%(bw_attn_fwd|bw_attn_bwd)[.\d]* = .*?"
                       r"operand_layout_constraints=\{s32\[(\d+)\]"
                       r".*?op_name=\"([^\"]*)\"", text)
    want = {}
    for n, layers in pairs.items():
        want[("bw_attn_fwd", str(n))] = 2 * layers
        want[("bw_attn_bwd", str(n))] = layers
    assert collections.Counter(c[:2] for c in calls) == want
    for name, n, path in calls:
        kind = "full" if n == "136" else "window"
        assert re.search(rf"[/(]fed_attention_{kind}[/)]", path), path


@pytest.fixture(scope="module")
def smallthinker_round(topo, one_chip):
    """The `smallthinker_unc_w2_l8192` cell's round (D=370,547,200:
    four layers, 8 of 64 experts)."""
    from commefficient_tpu.models import smallthinker
    return _lm_round(topo, smallthinker, "smallthinker_21b_ep8.json")


def test_trinity_round_fits_one_chip(topo, one_chip):
    """The `trinity_unc_w2_l8192` cell's round (D=504,147,712: a dense
    layer and four expert layers, 8 of 128 experts beside a shared
    one): 10.2 GiB, where chunks of 4,096 positions asked for 16.35,
    over a v5e's 15.75. The expert layer's forward runs once a layer
    (one `conditional` each): the layer keeps the routed sum for its
    backward pass and does not run the experts again.

    12.0 GiB with the XLA attention, whose transposes and blocks left
    the compiler laying out the forward's reads of the donated weights
    as a 2.0 GB copy made on entry; with the attention kernels reading
    the projections in their own (position-minor) layout, no copy."""
    from commefficient_tpu.models import afmoe
    cfg, compiled = _lm_round(topo, afmoe, "trinity_mini_ep16.json")
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= 2 * 4 * cfg.grad_size
    assert total < 10.5 * 2 ** 30, total
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert len(re.findall(r" conditional\(", text)) == 4
    assert "copy(%server_ps_weights" not in text
    # four 2,048-window layers (70 pairs each), one full layer (136)
    _assert_attention_by_the_kernels(text, {70: 4, 136: 1})


def test_nemotron_round_fits_one_chip(topo, one_chip):
    """The `nemotron_unc_w2_l8192` cell's round (D=528,093,120: three
    expert layers, three Mamba-2 mixers and one attention layer, 8 of
    128 experts beside a shared one): 12.53 GiB. The expert layer's
    forward runs once a layer (one `conditional` each); attention is
    the kernels at G = 16 query heads a key-value head; the mixers'
    ops carry their two scope names."""
    from commefficient_tpu.models import nemotron_h
    cfg, compiled = _lm_round(topo, nemotron_h, "nemotron3_nano_ep16.json")
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes >= 2 * 4 * cfg.grad_size
    assert total < 13.0 * 2 ** 30, total
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert len(re.findall(r" conditional\(", text)) == 3
    assert "copy(%server_ps_weights" not in text
    _assert_attention_by_the_kernels(text, {136: 1})
    for name in ("ssm_scan", "ssm_proj"):
        assert re.search(rf"[/(]fed_{name}[/)]", text), name


def test_smallthinker_round_fits_one_chip(smallthinker_round):
    """Weights and momentum updated in place, no D-sized error, the
    change bits packed inside: the program's arguments, outputs and
    temporaries fit a v5e's 15.75 GiB with room for the reference's
    and the harness's buffers beside them."""
    cfg, compiled = smallthinker_round
    assert cfg.server_in_place
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and momentum alias their outputs
    assert m.alias_size_in_bytes >= 2 * 4 * cfg.grad_size
    assert m.argument_size_in_bytes < 2.1 * 4 * cfg.grad_size
    # 9.3 GiB. With a `conditional` in the backward pass it was 10.7:
    # the compiler then copies the old weights (1.48 GB; the telemetry
    # reads them after the new ones exist) on entry and not behind
    # their last use, and the copy lives through the program's peak.
    # So the expert layer's backward makes its choice with loops
    # (models/smallthinker._once). 9.19 with the XLA attention: the
    # compiler defers the output projection's weight gradient of the
    # three window layers past the program's peak and keeps each one's
    # attention output for it, a float32 [2, 3584, 8192] from the
    # kernel where it kept a bfloat16 copy from the XLA path's last
    # transpose
    assert total < 9.3 * 2 ** 30, total
    text = compiled.as_text()
    assert "copy(%server_ps_weights" not in text
    # one full layer (136 pairs), three 4,096-window layers (108 each)
    _assert_attention_by_the_kernels(text, {136: 1, 108: 3})


def test_smallthinker_round_runs_the_grouped_expert_kernel(
        smallthinker_round):
    """The expert layer reaches the chip as the compiler's own grouped
    matmul (`ragged_dot` -> a Mosaic call with the groups' tiles as
    metadata), not as sixteen dense products, and the change bits are
    assembled across sublanes: no operand with a minor dimension of 16
    exists at [D/32, 2, 16]."""
    cfg, compiled = smallthinker_round
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert not re.search(r"\[\d+,2,16\]", text)
    # 8 of 64 experts held: each chunk picks its capacity as it runs,
    # forward as a `conditional` in each of the four layers
    assert len(re.findall(r" conditional\(", text)) == 4


def test_compacted_expert_chunk_moves_a_fraction_of_the_bytes(one_chip):
    """One chunk of the cell's expert layer (4,096 positions, top-6,
    8 of 64 experts held), forward and backward as the model
    rematerialises it: compacted to its first capacity the compiler
    counts under 9 GB accessed where the full-width path reads 19,
    and the grouped products are still the chip's kernel."""
    import functools

    from commefficient_tpu.models import smallthinker as st

    cfg = st.SmallThinkerConfig(num_layers=4, held_experts=(0, 8),
                                vocab_size=18992)
    T, H, F = cfg.moe_chunk, cfg.hidden_size, cfg.expert_width
    first = st.compact_capacities(cfg, T)[0]
    assert first == 6144

    def fwdbwd(branch):
        def chunk(gate, up, down, h2, r):
            p = {"gate": gate, "up": up, "down": down}
            return branch(p, h2, *st.sorted_picks(cfg, r)).sum()
        return jax.grad(jax.checkpoint(chunk), (0, 1, 2, 3, 4))

    shapes = [((8, H, F), jnp.float32), ((8, H, F), jnp.float32),
              ((8, F, H), jnp.float32), ((T, H), jnp.float32),
              ((T, cfg.num_experts), jnp.float32)]
    compact = _compile(fwdbwd(functools.partial(st.compacted, cfg, first)),
                       *shapes, sharding=one_chip)
    full = _compile(fwdbwd(functools.partial(st.full_width, cfg)),
                    *shapes, sharding=one_chip)
    assert "ragged-dot" in compact.as_text()
    assert not re.search(rf"f32\[{T * 6},({H}|{F})\]", compact.as_text())
    moved = compact.cost_analysis()["bytes accessed"]
    assert moved < 9e9, moved
    assert full.cost_analysis()["bytes accessed"] > 2 * moved


def _computations(text):
    """{name: (is the entry, body text)} of a compiled module's text."""
    out = {}
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ [\(\{])", text):
        m = re.match(r"(ENTRY )?%([\w.\-]+)", comp)
        if m:
            out[m.group(2)] = (bool(m.group(1)), comp)
    return out


def _instructions(body, shapes):
    """(opcode, result type) of each instruction of `body` whose result
    has one of `shapes` (as `f32[8,2560,768]`)."""
    return [(op, typ) for typ, op in re.findall(
        r"\n\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", body)
        if typ.split("{")[0] in shapes]


@pytest.fixture(scope="module")
def expert_layer_program(one_chip):
    """The SmallThinker cell's expert layer (8 of 64 experts held,
    top-6, chunks of 4,096 positions) over its two sequences of 8,192
    positions, four chunks, forward and backward, compiled for one
    described chip."""
    from commefficient_tpu.models import smallthinker as st

    cfg = st.SmallThinkerConfig(num_layers=4, held_experts=(0, 8),
                                vocab_size=18992)
    H, F, E = cfg.hidden_size, cfg.expert_width, cfg.num_experts

    def layer(gate, up, down, h2, r):
        p = {"gate": gate, "up": up, "down": down}
        return st.expert_layer(cfg, p, h2, r)[0].sum()

    compiled = _compile(
        jax.value_and_grad(layer, (0, 1, 2, 3, 4)),
        ((8, H, F), jnp.float32), ((8, H, F), jnp.float32),
        ((8, F, H), jnp.float32), ((2, 8192, H), jnp.float32),
        ((2, 8192, E), jnp.float32), sharding=one_chip)
    return (f"f32[8,{H},{F}]", f"f32[8,{F},{H}]"), compiled.as_text()


def test_expert_chunk_backward_runs_the_chosen_branch_alone(
        expert_layer_program):
    """The layer as the model differentiates it: a loop over the
    chunks forward (each chunk's capacity a `conditional`) and one
    backward, whose body holds one loop for each branch, of one trip
    or none, and every grouped product of the backward pass inside
    them. Were the bodies lifted out (they depend on nothing a trip
    changes, but for the barrier of `_once`) all three would run for
    every chunk: 30.3 ms a chunk on the chip where one branch takes
    10.9 (PERF.md section 6)."""
    _, text = expert_layer_program
    comps = _computations(text)
    entry = next(body for is_entry, body in comps.values() if is_entry)
    assert len(re.findall(r" conditional\(", text)) == 1
    assert "ragged-dot" not in entry
    loops = re.findall(r" while\(.*?body=%([\w.\-]+)", entry)
    assert len(loops) == 2
    backward = [comps[name][1] for name in loops
                if len(re.findall(r" while\(", comps[name][1])) == 3]
    assert len(backward) == 1
    assert "ragged-dot" not in backward[0]
    assert " conditional(" not in backward[0]


def test_expert_layer_backward_adds_into_one_accumulator(
        expert_layer_program):
    """No weight-sized zeros, copy or select a chunk: the layer's
    gradient accumulators are made once (three `broadcast`s of the
    weights' shapes, in the entry), the weights transposed once (three
    `copy`s, in the entry), and inside the loops each branch's trip
    adds its three products into them. Before, every chunk made the
    zeros, copied the three weights inside each trip, and the map's
    transpose added each chunk's gradient to its carried sum."""
    weights, text = expert_layer_program
    made = {"entry": [], "loops": []}
    for is_entry, body in _computations(text).values():
        made["entry" if is_entry else "loops"] += _instructions(body, weights)
    ops = {k: collections.Counter(op for op, _ in v)
           for k, v in made.items()}
    assert ops["entry"]["broadcast"] == 3 and ops["entry"]["copy"] == 3
    for op in ("broadcast", "copy", "select", "fusion"):
        assert ops["loops"][op] == 0, (op, made["loops"])
    # three branches, three weights
    assert ops["loops"]["add"] == 9


@pytest.mark.parametrize("family", ["smallthinker", "trinity"])
def test_expert_layer_grouped_products_do_not_grow(one_chip, family):
    """The grouped products (`chlo.ragged_dot`) of one expert layer in
    the lowered program, at the cell's shapes: forward three a branch
    (the switch's three branches), backward nine a branch (the chunk's
    three again, and two transposes each), as the map of
    rematerialised chunks had them."""
    from commefficient_tpu.models import afmoe, experts
    from commefficient_tpu.models import smallthinker as st

    if family == "smallthinker":
        cfg = st.SmallThinkerConfig(num_layers=4, held_experts=(0, 8),
                                    vocab_size=18992)
    else:
        cfg = afmoe.AfmoeConfig(num_layers=5, num_dense_layers=1,
                                held_experts=(0, 8), vocab_size=25024)
    H, F, E = cfg.hidden_size, cfg.expert_width, cfg.num_experts
    assert len(experts.compact_capacities(cfg, cfg.moe_chunk)) == 2

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    p = {"gate": shaped(8, H, F), "up": shaped(8, H, F),
         "down": shaped(8, F, H), "expert_bias": shaped(E)}
    h2, r = shaped(2, 8192, H), shaped(2, 8192, E)

    def layer(p, h2, r):
        return experts.expert_layer(cfg, p, h2, r)[0]

    def count(fn, *args):
        text = jax.jit(fn).lower(*args).as_text()
        return len(re.findall(r"= \"chlo\.ragged_dot\"", text))

    def both(p, h2, r, dy):
        y, back = jax.vjp(layer, p, h2, r)
        return y, back(dy)

    forward = count(layer, p, h2, r)
    assert (forward, count(both, p, h2, r, h2) - forward) == (9, 27)
