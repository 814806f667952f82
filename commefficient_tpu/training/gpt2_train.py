"""GPT2 / PersonaChat federated training driver.

The reference driver's launch surface re-created on the TPU runtime
(reference: CommEfficient/gpt2_train.py — double-heads loss callbacks
:77-99, special-token handling :101-112, per-batch-logging train loop
`run_batches` :169-253, val NLL/accuracy/perplexity :242-253, main
wiring :255-313): same flags (config.parse_args, default lr 4e-2 at
:256), same loss-callback contract, same epoch-1-only download
reporting (:132-137). The federated core underneath is the identical
workload-agnostic round engine cv_train uses — preserving the
reference's key API contract (SURVEY.md §3.5).

Run: python -m commefficient_tpu.training.gpt2_train --dataset_name
PERSONA --mode sketch --error_type virtual ...
"""
from __future__ import annotations

import contextlib
import math
import os
import time
from typing import Optional

import jax

import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config, parse_args
from commefficient_tpu.data.loader import FedLoader, FedValLoader
from commefficient_tpu.data.persona import (
    FedPERSONA, HashTokenizer, IGNORE_INDEX, make_tokenizer,
)
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.models.gpt2 import (
    GPT2Config, GPT2DoubleHeads, PRESETS, build_gpt2, load_pretrained_dir,
    resize_position_embeddings, resize_token_embeddings, save_pretrained,
    try_load_pretrained,
)
from commefficient_tpu.models import afmoe, nemotron_h, smallthinker
from commefficient_tpu.parallel import multihost as mh
from commefficient_tpu.parallel.mesh import make_multihost_client_mesh
from commefficient_tpu.parallel.tp import tp_loss
from commefficient_tpu.telemetry.trace import TRACE
from commefficient_tpu.training.scanloop import (
    make_span_checkpoint, numeric_rollback, run_scanned_rounds,
)
from commefficient_tpu.utils.cache import enable_persistent_compilation_cache
from commefficient_tpu.utils.checkpoint import (
    save_checkpoint, save_final, save_rotating,
)
from commefficient_tpu.utils.logging import (
    NullLogger, TableLogger, Timer, make_logdir,
)
from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear


# ---------------- loss callbacks (reference gpt2_train.py:77-99) ---------

def _lm_nll(lm_logits, lm_labels, mask):
    """Shifted next-token NLL over non-ignored labels of valid
    examples (reference inference() shift at gpt2_train.py:63-68 +
    CrossEntropyLoss(ignore_index=-1) at :78)."""
    logits = lm_logits[..., :-1, :]
    labels = lm_labels[..., 1:]
    valid = ((labels != IGNORE_INDEX)
             * mask[:, None, None]).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def _mc_loss_acc(mc_logits, mc_labels, mask):
    """Candidate-choice cross-entropy + accuracy (the double head)."""
    logp = jax.nn.log_softmax(mc_logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, mc_labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    acc = ((mc_logits.argmax(-1) == mc_labels) * mask).sum() / denom
    return loss, acc


def make_compute_loss_train(model: GPT2DoubleHeads, cfg: Config):
    def compute_loss(params, batch, mask):
        input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = batch
        lm_logits, mc_logits = model.apply(
            params, input_ids, token_type_ids, mc_token_ids)
        lm = _lm_nll(lm_logits, lm_labels, mask)
        mc, _ = _mc_loss_acc(mc_logits, mc_labels, mask)
        loss = lm * cfg.lm_coef + mc * cfg.mc_coef
        return loss, (lm, mc)
    return compute_loss


def make_compute_loss_val(model: GPT2DoubleHeads):
    """Val = (NLL, (accuracy,)); perplexity is exp(mean NLL), computed
    by the caller over the whole val set (reference gpt2_train.py:253)."""
    def compute_loss(params, batch, mask):
        input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = batch
        lm_logits, mc_logits = model.apply(
            params, input_ids, token_type_ids, mc_token_ids)
        nll = _lm_nll(lm_logits, lm_labels, mask)
        _, acc = _mc_loss_acc(mc_logits, mc_labels, mask)
        return nll, (acc,)
    return compute_loss


# ---------------- data (reference gpt2_train.py:315-355) -----------------

def get_data_loaders(cfg: Config, tokenizer):
    synthetic = (8, 2, 3) if cfg.do_test else None
    common = dict(dataset_dir=cfg.dataset_dir, tokenizer=tokenizer,
                  num_candidates=cfg.num_candidates,
                  max_history=cfg.max_history, do_iid=cfg.do_iid,
                  seed=cfg.seed, synthetic_examples=synthetic)
    train_set = FedPERSONA(
        personality_permutations=cfg.personality_permutations,
        num_clients=cfg.num_clients, train=True, **common)
    val_set = FedPERSONA(
        personality_permutations=cfg.personality_permutations,
        train=False, **common)
    train_loader = FedLoader(train_set, cfg.num_workers,
                             cfg.local_batch_size, seed=cfg.seed,
                             max_local_batch=cfg.max_local_batch)
    val_loader = FedValLoader(val_set, cfg.valid_batch_size,
                              num_shards=min(jax.device_count(),
                                             cfg.num_workers))
    return train_loader, val_loader


# ---------------- eval (reference test_gpt2, gpt2_train.py:149-167) ------

def run_eval(model: FedModel, val_loader):
    model.train(False)
    tot_nll = tot_acc = tot_n = 0.0
    for data, mask in val_loader.batches():
        nll, acc, count = model((data, mask))
        tot_nll += float((nll * count).sum())
        tot_acc += float((acc * count).sum())
        tot_n += float(count.sum())
    model.train(True)
    denom = max(tot_n, 1.0)
    nll = tot_nll / denom
    return nll, tot_acc / denom, float(np.exp(min(nll, 50.0)))


# ---------------- training loop (reference run_batches, :169-253) --------

def train_gpt2(model: FedModel, opt: FedOptimizer, lr_scheduler,
               train_loader, cfg: Config,
               logger=None, timer: Optional[Timer] = None,
               log_dir: str = ""):
    timer = timer or Timer()
    logger = logger or TableLogger()
    spe = train_loader.steps_per_epoch
    epoch_download = epoch_upload = 0.0
    # --debug_transfer_guard: implicit host<->device transfers raise in
    # the steady-state loop (every dispatch after the compiling first
    # one) — same wiring as cv_train.train
    guard = None
    if cfg.debug_transfer_guard:
        from commefficient_tpu.analysis.runtime import forbid_transfers
        guard = forbid_transfers
    warmed = [False]
    # on resume, num_epochs is the TOTAL budget: rounds already done
    # (restored round_idx) count against it — same contract as
    # cv_train.train (cv_train.py:136-140); without this the resumed
    # run replays the whole budget and the lr schedule's final knot is
    # exceeded (np.interp clamps lr to 0)
    batch_idx = int(model.server.round_idx)
    start_epoch = batch_idx // spe
    # mid-epoch resume: fast-forward the first resumed epoch's loader
    # stream past the rounds already trained, so the epoch's early
    # batches aren't re-trained while batch_idx continues mid-epoch
    # (data coverage matches an uninterrupted run up to the sampler's
    # fresh permutation; LR schedule and budget were already correct).
    # With checkpointed sampler state (smp_* keys) resolve_resume
    # collapses the skip to 0 and the restored cursor continues the
    # exact stream — same contract as cv_train.train.
    skip_rounds = train_loader.sampler.resolve_resume(
        batch_idx % spe)
    # a stream restored AT the per-epoch cap was abandoned right
    # there by the uninterrupted run — discard it so the resumed
    # epoch draws fresh (cv_train applies the same rule; here the
    # absolute batch_idx cap already bounds the remainder, so no
    # budget subtraction is needed)
    if (train_loader.sampler.pending_pos or 0) >= spe:
        train_loader.sampler.discard_pending()
    ckpt_path = os.path.join(cfg.checkpoint_path, "gpt2")

    if cfg.do_profile:
        jax.profiler.start_trace(os.path.join(log_dir or ".", "profile"))
    for epoch in range(start_epoch, math.ceil(cfg.num_epochs)):
        frac = (cfg.num_epochs - epoch
                if epoch == math.ceil(cfg.num_epochs) - 1 else 1.0)
        losses = []

        # per-batch metrics are logged with a ONE-ROUND lag: round t-1
        # is already computed when round t dispatches, so float() costs
        # nothing; float()ing the fresh round would block the host
        # every round (PERF.md). NaN abort latency grows by one round.
        def emit(p) -> bool:
            bidx, lr_v, l_, *parts = p
            # gather_host: metrics are cross-process sharded in
            # multi-controller runs (np.asarray otherwise)
            losses.append(float(np.mean(mh.gather_host(l_))))
            row = {
                "batch_idx": bidx,
                "lr": round(lr_v, 5),
                "train_time": timer(),
                "train_loss": losses[-1],
            }
            if len(parts) == 2 and not cfg.expert_load_layers:
                # the double-heads loss reports its two terms; a
                # language-model-only loss (DECODERS) reports its
                # layers' counters, which the telemetry names
                row["lm_loss"] = float(np.mean(mh.gather_host(parts[0])))
                row["mc_loss"] = float(np.mean(mh.gather_host(parts[1])))
            row["total_time"] = timer.total_time
            logger.append(row)
            return not (np.isnan(losses[-1])
                        or losses[-1] > cfg.nan_threshold)

        pending = None
        aborted = False
        if model.scheduler is not None:
            # sync the scheduler's round counter to the epoch stream
            # (resume replays the skipped head — same as cv_train)
            model.scheduler.begin_epoch(batch_idx - skip_rounds)
        # sampler-level skip: the skipped rounds advance index math
        # only, never materializing batch data (O(skip) host work was
        # O(skip × batch fetch+transform) before)
        epoch_stream = train_loader.epoch(skip=skip_rounds)
        skip_rounds = 0
        if cfg.scan_rounds:
            # scanned device programs, flushed every --scan_span rounds
            # (symmetric with cv_train; bounds the staged token arrays)
            def stream():
                # cap-BEFORE-pull: never draw-and-discard a round at
                # the epoch cap, and mark the abandonment before any
                # checkpoint that follows (same contract as
                # cv_train's scanned stream)
                nonlocal batch_idx
                stream_it = iter(epoch_stream)
                while batch_idx - epoch * spe < spe * frac:
                    try:
                        client_ids, data, mask = next(stream_it)
                    except StopIteration:
                        return
                    lr_scheduler.step()
                    batch_idx += 1
                    lr_v = opt.param_groups[0]["lr"]
                    yield ((batch_idx, float(lr_v)), client_ids, data,
                           mask, lr_v)
                train_loader.sampler.abandon_epoch()

            def on_comm(d, u):
                nonlocal epoch_download, epoch_upload
                if epoch == 0:
                    epoch_download += d.sum() / (1024 ** 2)
                    epoch_upload += u.sum() / (1024 ** 2)

            aborted = not run_scanned_rounds(
                model, stream(),
                # palette mode hands the controller bank in as the
                # adaptive span provider; static --scan_span otherwise
                model.control_bank if cfg.span_palette
                else (cfg.scan_span if cfg.scan_span > 0 else spe),
                lambda tag, l_, *parts: emit(
                    (tag[0], tag[1], l_, *parts)),
                on_comm,
                # span-boundary saves bound a mid-span preemption's
                # loss to ckpt_every_spans spans, not one epoch
                checkpoint=make_span_checkpoint(
                    ckpt_path, model, cfg, lr_scheduler),
                guard=guard,
                # --pipeline: double-buffered dispatch (ISSUE 10)
                pipeline=cfg.pipeline)
        else:
            stream_it = iter(epoch_stream)
            while True:
                if batch_idx - epoch * spe >= spe * frac:
                    # epoch cap: abandon WITHOUT pulling — the epoch-
                    # cadence checkpoint below must record in_epoch=0
                    # and no phantom draw may advance the rng
                    train_loader.sampler.abandon_epoch()
                    break
                try:
                    client_ids, data, mask = next(stream_it)
                except StopIteration:
                    break
                lr_scheduler.step()
                ctx = (guard() if guard is not None and warmed[0]
                       else contextlib.nullcontext())
                with ctx:
                    loss, *parts, down, up = model(
                        (client_ids, data, mask))
                warmed[0] = True
                opt.step()
                batch_idx += 1
                if epoch == 0:
                    # download deltas are only trusted for epoch 1
                    # (reference gpt2_train.py:132-137)
                    epoch_download += down.sum() / (1024 ** 2)
                    epoch_upload += up.sum() / (1024 ** 2)
                if pending is not None and not emit(pending):
                    pending = None
                    aborted = True
                    break
                pending = (batch_idx, float(opt.param_groups[0]["lr"]),
                           loss, *parts)
            if pending is not None and not emit(pending):
                aborted = True
        if aborted:
            if mh.is_coordinator():
                print(f"found nan/divergent loss {losses[-1]}, aborting")
            if cfg.do_profile and epoch == start_epoch:
                jax.profiler.stop_trace()
            return False
        if cfg.do_profile and epoch == start_epoch:
            jax.profiler.stop_trace()
            print(f"profile trace written to "
                  f"{os.path.join(log_dir or '.', 'profile')}")
        # mid-run checkpoint so --resume has something to pick up when
        # the run is killed (symmetric with cv_train.py's per-epoch
        # save; the resume-read half alone would be unreachable)
        if model.telemetry is not None:
            # drain the one-round-lag metric buffer + journal an epoch
            # summary (symmetric with cv_train.train); after one full
            # epoch the train programs are compiled — later train-loop
            # compiles journal as compile_warning (the final eval runs
            # under expect_compiles, see main)
            model.telemetry.flush()
            model.telemetry.journal_event(
                "epoch", epoch=epoch,
                train_loss=(losses[-1] if losses else None),
                rounds=batch_idx)
            model.telemetry.mark_steady_state()
        if cfg.checkpoint_every and epoch % cfg.checkpoint_every == 0:
            # atomic rotated save (keep-last-k + `latest` manifest) —
            # the preemption-safe half of --resume (utils/checkpoint)
            t0 = time.monotonic()
            # queued span-boundary writes (--pipeline) must land
            # before this synchronous save rotates the manifest
            model.drain_persistence()
            with TRACE.span("checkpoint",
                            round=int(getattr(model, "_rounds_done",
                                              0))):
                written = save_rotating(
                    ckpt_path, model.server, model.clients,
                    keep_last=cfg.keep_checkpoints,
                    max_age_hours=cfg.ckpt_max_age_hours,
                    scheduler_step=lr_scheduler.step_count,
                    accountant=model.accountant,
                    prev_change_words=model._prev_change_words,
                    fingerprint=model.checkpoint_fingerprint,
                    throughput=model.throughput.state_dict(),
                    scheduler=model.scheduler_state(),
                    sampler=model.sampler_state(),
                    async_admit=model.async_admit_state(),
                    client_rows=model.client_rows_payload())
            if model.telemetry is not None:
                model.telemetry.journal_event(
                    "checkpoint", path=written,
                    seconds=round(time.monotonic() - t0, 3))
            if mh.is_coordinator():
                print(f"checkpointed to {written}")

    n_clients = model.num_clients
    if mh.is_coordinator():
        print(f"Total Download (MiB): {epoch_download:0.2f} (only epoch 1)")
        print(f"Total Upload (MiB): {epoch_upload:0.2f} (only epoch 1)")
        print(f"Avg Download Per Client: {epoch_download / n_clients:0.2f}"
              f" (only epoch 1)")
        print(f"Avg Upload Per Client: {epoch_upload / n_clients:0.2f}"
              f" (only epoch 1)")
    return True


def test_gpt2(model: FedModel, val_loader, timer: Optional[Timer] = None,
              logger=None):
    timer = timer or Timer()
    nll, acc, ppl = run_eval(model, val_loader)
    stats = {"val_nll": nll, "val_acc": acc, "val_ppl": ppl,
             "val_time": timer(), "total_time": timer.total_time}
    (logger or TableLogger()).append(stats)
    return stats


# ---------------- main (reference train(), gpt2_train.py:255-313) --------

def build_model_and_params(cfg: Config, tokenizer, seq_len: int,
                           source: Optional[str] = None,
                           require_load: bool = False):
    """Build the Flax GPT2 sized for the tokenizer + corpus; import
    weights from `source` — a save_pretrained artifact directory (the
    --finetune path), a local HF checkpoint, or a preset name — with
    random init as the fallback. require_load=True turns the fallback
    into an error (the --finetune contract: evaluating a fresh init as
    if it were the finetuned model would silently report garbage; the
    reference fails inside from_pretrained the same way)."""
    vocab = len(tokenizer)
    key = jax.random.PRNGKey(cfg.seed)
    source = source or cfg.model_checkpoint

    loaded = load_pretrained_dir(source, key=key)
    if loaded is not None:
        # our own HF-style artifact: config rides along, any scale
        # (incl. the tiny --test model a smoke run saved). Widen the
        # position table if this corpus pads longer than the artifact's
        # (same hazard the other branches handle via max(., seq_len))
        pretrained, gcfg = loaded
        if seq_len > gcfg.n_positions:
            pretrained = resize_position_embeddings(
                pretrained, seq_len, key=key,
                initializer_range=gcfg.initializer_range)
            gcfg = gcfg.replace(n_positions=seq_len)
    elif require_load:
        # finetune_path may also name a stock HF checkpoint directory
        # (the reference hands it straight to from_pretrained)
        gcfg = PRESETS["gpt2"].replace(
            n_positions=max(PRESETS["gpt2"].n_positions, seq_len))
        pretrained = try_load_pretrained(source, gcfg, key=key)
        if pretrained is None:
            raise FileNotFoundError(
                f"--finetune: no loadable artifact at {source!r} "
                "(expected config.json + pytorch_model.bin/.npz from a "
                "previous run's save_pretrained, or a local HF "
                "checkpoint)")
    elif cfg.do_test:
        gcfg = GPT2Config(vocab_size=vocab, n_positions=max(seq_len, 8),
                          n_embd=32, n_layer=2, n_head=2)
        pretrained = None
    else:
        base = PRESETS.get(source, PRESETS["gpt2"])
        gcfg = base.replace(n_positions=max(base.n_positions, seq_len))
        pretrained = try_load_pretrained(source, gcfg, key=key)
        if pretrained is None:
            # from-scratch: size the embedding directly for the
            # tokenizer (no resize step needed)
            gcfg = gcfg.replace(vocab_size=vocab)

    # remat is an execution-layout choice, not part of the artifact:
    # apply the flag regardless of where the config came from
    gcfg = gcfg.replace(remat=cfg.do_remat)

    if pretrained is not None:
        params = pretrained
        if vocab > gcfg.vocab_size:
            # special-token embedding resize (reference :101-112);
            # the module is rebuilt at the grown vocab to match
            params = resize_token_embeddings(params, vocab, key=key)
            gcfg = gcfg.replace(vocab_size=vocab)
        module = GPT2DoubleHeads(gcfg)
    else:
        module = GPT2DoubleHeads(gcfg)
        C = max(cfg.num_candidates, 1)
        L = min(seq_len, gcfg.n_positions)
        params = module.init(key,
                             jnp.zeros((1, C, L), jnp.int32),
                             jnp.zeros((1, C, L), jnp.int32),
                             jnp.zeros((1, C), jnp.int32))
    return module, params


# ---------------- --model smallthinker | trinity | nemotron --------------

# the mixture-of-experts language models, by the start of --model: the
# model's module, its configuration class, and the key of its
# config.json that counts its routed experts
DECODERS = {
    "smallthinker": (smallthinker, smallthinker.SmallThinkerConfig,
                     "moe_num_primary_experts"),
    "trinity": (afmoe, afmoe.AfmoeConfig, "num_experts"),
    "nemotron": (nemotron_h, nemotron_h.NemotronHConfig, "n_routed_experts"),
}


def _decoder(cfg: Config):
    return next((entry for name, entry in DECODERS.items()
                 if cfg.model.lower().startswith(name)), None)


def decoder_config(cfg: Config):
    """None unless --model names one of DECODERS; else the model's
    sizes: the tiny preset under --test, otherwise the public
    `config.json` found in --model_checkpoint (a directory, as for
    GPT2), where the keys `held_experts` [first, count] and
    `router_width` may say which experts of how many this process
    holds (one chip's share of an expert-parallel deployment). Weights
    are drawn from --seed: no checkpoint of these families is read."""
    entry = _decoder(cfg)
    if entry is None:
        return None
    module, config_class, experts_key = entry
    if cfg.do_test:
        return module.TINY.replace(remat=cfg.do_remat)
    import json
    path = os.path.join(cfg.model_checkpoint, "config.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"--model {cfg.model}: no config.json in "
            f"--model_checkpoint {cfg.model_checkpoint!r}")
    with open(path) as f:
        published = json.load(f)
    experts = int(published.get("router_width", published[experts_key]))
    return config_class.from_published(
        published, num_experts=experts,
        held_experts=tuple(published.get("held_experts", (0, experts))))


def build_decoder(cfg: Config, mcfg, tokenizer, params=None):
    """(cfg with the model's counters switched on, train loss, val
    loss, params): the language-model loss over every real token of
    a sequence, for FedModel. `params` may be handed in (the
    benchmark makes its own from the seed)."""
    module = next(m for m, c, _ in DECODERS.values() if isinstance(mcfg, c))
    mcfg = mcfg.replace(vocab_size=len(tokenizer))
    pad = tokenizer.special_ids()["<pad>"]
    loss_train = module.make_lm_loss(mcfg, pad)

    def loss_val(p, batch, mask):
        # no candidate is chosen, so no accuracy: zero in its place
        nll, _ = loss_train(p, batch, mask)
        return nll, (jnp.zeros_like(nll),)

    loss_val.cohort = True
    if params is None:
        params = module.init_params(mcfg, jax.random.PRNGKey(cfg.seed))
    return (cfg.replace(expert_load_layers=mcfg.expert_layers,
                        mixer_layers=getattr(mcfg, "mixer_layers", 0)),
            loss_train, loss_val, params)


# the names the benchmark's SmallThinker driver calls
smallthinker_config, build_smallthinker = decoder_config, build_decoder


def main(argv=None) -> bool:
    enable_persistent_compilation_cache()
    cfg = parse_args(default_lr=4e-2, argv=argv)
    if cfg.multihost:
        # must precede every backend touch (jax.device_count below)
        mh.initialize_from_config(cfg)
    if cfg.do_test:
        # smoke shrink of the compression geometry (cv_train applies
        # the same pattern; reference cv_train.py:329-336)
        cfg = cfg.replace(num_rows=1, num_cols=1000, k=10, num_blocks=1)
    if mh.is_coordinator():
        print(cfg)
    timer = Timer()
    np.random.seed(cfg.seed)

    lm_cfg = decoder_config(cfg)
    if lm_cfg is not None:
        # ids are drawn from the vocabulary the model holds
        tokenizer = HashTokenizer(lm_cfg.vocab_size)
    else:
        tokenizer = make_tokenizer(
            cfg.model_checkpoint,
            fallback_vocab=500 if cfg.do_test else 5000)
    train_loader, val_loader = get_data_loaders(cfg, tokenizer)
    # each split pads to its own corpus max; position embeddings must
    # cover both (out-of-range ids would silently clamp, not raise)
    seq_len = max(train_loader.dataset.seq_len,
                  val_loader.dataset.seq_len)

    # --finetune redirects the model source to the finetuned artifact
    # (reference swaps model_checkpoint = finetune_path,
    # gpt2_train.py:270-272; it skips the swap under --test because its
    # finetune_path then names a full HF checkpoint — here a --test
    # smoke SAVES a loadable tiny artifact, so honor one when present)
    source = cfg.model_checkpoint
    if cfg.do_finetune and (
            not cfg.do_test
            or any(os.path.isfile(os.path.join(cfg.finetune_path, f))
                   for f in ("pytorch_model.bin", "pytorch_model.npz"))):
        source = cfg.finetune_path

    if lm_cfg is not None:
        module = None
        cfg, loss_train, loss_val, params = build_decoder(
            cfg, lm_cfg, tokenizer)
    else:
        module, params = build_model_and_params(
            cfg, tokenizer, seq_len, source=source,
            require_load=(source == cfg.finetune_path
                          and cfg.do_finetune))
        loss_train = make_compute_loss_train(module, cfg)
        loss_val = make_compute_loss_val(module)
    mesh = None
    if cfg.model_parallel > 1:
        # (clients, model) mesh: manual DP over clients, GSPMD tensor
        # parallelism over the model axis (parallel/tp.py); slice-major
        # clients layout auto-detected or emulated via --num_slices
        # (parallel/mesh.py), so TP activation collectives stay on ICI
        shards = max(len(jax.devices()) // cfg.model_parallel, 1)
        while cfg.num_workers % shards:
            shards -= 1
        mesh = make_multihost_client_mesh(
            model_parallel=cfg.model_parallel,
            devices=jax.devices()[:shards * cfg.model_parallel],
            num_slices=cfg.num_slices if cfg.num_slices > 1 else None)
        loss_train = tp_loss(loss_train, mesh)
        loss_val = tp_loss(loss_val, mesh)
        if mh.is_coordinator():
            print(f"tensor parallel: mesh {dict(mesh.shape)}")

    model = FedModel(None, loss_train, cfg, loss_val=loss_val,
                     params=params, mesh=mesh,
                     num_clients=train_loader.dataset.num_clients)
    opt = FedOptimizer(model)

    # round scheduler, attached BEFORE --resume so sched_* checkpoint
    # counters restore into this instance (wiring shared with
    # cv_train; uniform/no-deadline default is bit-identical)
    from commefficient_tpu.scheduler import attach_round_scheduler
    attach_round_scheduler(model, train_loader)

    # coordinator-broadcast control plane (ISSUE 12): the configured
    # plan transport rides on the scheduler above — wiring shared
    # with cv_train (parallel/plantransport.attach_config_transport)
    from commefficient_tpu.parallel.plantransport import (
        attach_config_transport,
    )
    attach_config_transport(model, train_loader, cfg)

    coord = mh.is_coordinator()
    if mh.is_multihost():
        # per-process batch feeding — or, on non-contiguous layouts,
        # the globalize() fallback (one shared implementation:
        # multihost.apply_feed_slices)
        mh.apply_feed_slices(model, train_loader, val_loader,
                             cfg.num_workers, val_loader.num_shards)

    spe = train_loader.steps_per_epoch
    if coord:
        print("Steps per epoch", spe)
    schedule = PiecewiseLinear([0, cfg.num_epochs * spe],
                               [cfg.lr_scale, 0.0])
    lr_scheduler = LambdaLR(opt, lr_lambda=schedule)

    # mid-run resume, symmetric with cv_train.main: newest rotated
    # checkpoint via the manifest, legacy fixed-name fallback,
    # fingerprint-validated (utils/checkpoint)
    ckpt_path = os.path.join(cfg.checkpoint_path, "gpt2")
    ckpt_fallbacks = []
    if cfg.resume:
        # corruption-tolerant resume (ISSUE 12 satellite, shared
        # contract with cv_train): checksum-verify the newest rotated
        # checkpoint and fall back to the previous rotation on a
        # corrupt/truncated file, journaling `checkpoint_fallback`
        # once the telemetry session exists
        from commefficient_tpu.utils.checkpoint import load_resilient
        loaded = load_resilient(
            ckpt_path,
            expect_fingerprint=model.checkpoint_fingerprint,
            on_fallback=lambda p, why: ckpt_fallbacks.append((p, why)))
        if loaded is not None:
            ck_file, ckpt = loaded
            lr_scheduler.load_state_dict(
                {"step_count": model.load_state(ckpt)})
            if coord:
                print(f"resumed from {ck_file} at round "
                      f"{int(ckpt.server.round_idx)}")
        if model.plan_transport is not None and cfg.journal_path:
            # deterministic restart: cross-check replayed rounds
            # against the pre-crash write-ahead plan stream
            model.load_plan_stream(cfg.journal_path)

    # only the coordinator creates a run dir (its artifacts are the
    # run's outputs; workers would just litter empty dirs)
    log_dir = make_logdir(cfg) if coord else ""
    # run journal + on-device metrics + throughput tracking (wiring
    # shared with the CV driver, owned by the telemetry package)
    from commefficient_tpu.telemetry import attach_run_telemetry
    tele = attach_run_telemetry(model, cfg, log_dir, coord,
                                driver="gpt2_train",
                                materialize=mh.gather_host)
    if tele is not None:
        for p, why in ckpt_fallbacks:
            tele.journal_event("checkpoint_fallback", path=p,
                               error=why[:200])
    if coord:
        print(f"Finished initializing in {timer():.2f} seconds")

    ok = False
    try:
        if cfg.do_finetune:
            test_gpt2(model, val_loader, timer=timer,
                      logger=TableLogger() if coord else NullLogger())
            ok = True
        else:
            from commefficient_tpu.telemetry import NumericTripError
            trips = 0
            while True:
                try:
                    ok = train_gpt2(model, opt, lr_scheduler,
                                    train_loader, cfg,
                                    logger=TableLogger() if coord
                                    else NullLogger(),
                                    timer=timer, log_dir=log_dir)
                    break
                except NumericTripError as trip:
                    # finite-frontier auto-rollback (ISSUE 16),
                    # shared contract with cv_train: walk back to
                    # the newest finite checkpoint, replay with
                    # screening forced on; bounded, then fail loud
                    trips += 1
                    if trips > cfg.max_numeric_rollbacks:
                        raise
                    sched_step = numeric_rollback(
                        model, ckpt_path, cfg, tele, trip)
                    if sched_step is None:
                        raise
                    lr_scheduler.load_state_dict(
                        {"step_count": sched_step})
            save_checkpoint(os.path.join(log_dir, "gpt2"), model.server,
                            scheduler_step=lr_scheduler.step_count)
            if cfg.do_checkpoint:
                # stamped + manifest (what --resume prefers) AND the
                # fixed-name artifact, in one collective gather
                model.drain_persistence()
                save_final(ckpt_path, model.server, model.clients,
                           keep_last=cfg.keep_checkpoints,
                           max_age_hours=cfg.ckpt_max_age_hours,
                           scheduler_step=lr_scheduler.step_count,
                           accountant=model.accountant,
                           prev_change_words=model._prev_change_words,
                           fingerprint=model.checkpoint_fingerprint,
                           throughput=model.throughput.state_dict(),
                           scheduler=model.scheduler_state(),
                           sampler=model.sampler_state(),
                           async_admit=model.async_admit_state(),
                           client_rows=model.client_rows_payload())
            # HF-style final artifact: tokenizer + config + weights
            # (reference gpt2_train.py:275-283, fed_aggregator.py:208-211)
            if coord and module is not None:
                save_pretrained(log_dir, model.state_dict(), module.cfg,
                                tokenizer)
            # the final eval legitimately first-compiles after the
            # train loop's steady state — not a retrace warning
            with (tele.expect_compiles("final eval") if tele is not None
                  else contextlib.nullcontext()):
                test_gpt2(model, val_loader, timer=timer,
                          logger=TableLogger() if coord
                          else NullLogger())
        model.finalize()
    finally:
        # close even when training raises (fault drill, NaN abort):
        # the global compile listener and any live profiler capture
        # must not leak into the next in-process run. The persistence
        # writer drains FIRST (--pipeline): a queued span checkpoint
        # flushes at a crash exactly like at a clean shutdown.
        try:
            model.close_persistence()
        finally:
            if tele is not None:
                tele.close(ok=bool(ok))
    return ok


def cli() -> None:
    """Console entry point (`gpt2-train`, pyproject.toml)."""
    raise SystemExit(0 if main() else 1)


if __name__ == "__main__":
    cli()
