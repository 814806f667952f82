"""GPT2-small at REAL scale: pretrained load -> federated sketch
rounds -> held-out eval (VERDICT r4 next #4).

The reference starts from actual gpt2-small weights via
`from_pretrained` (reference CommEfficient/gpt2_train.py:262-273),
trains federated sketch rounds, and evals NLL/ppl (:242-253). This
smoke proves the same pipeline end to end at the same 124M-parameter
geometry: a GENUINE torch `GPT2LMHeadModel.save_pretrained` checkpoint
(generated locally at the real gpt2-small config — zero-egress, so
the weights are a seeded random init; geometry, artifact format, and
every code path are the real ones), loaded through the driver's
`build_model_and_params` (the --finetune/--model_checkpoint load
path), special-token-resized for the PersonaChat tokenizer (reference
:101-112), then N sketch rounds on PersonaChat-shaped data through
FedModel/FedOptimizer with the reference's default sketch geometry
(5 x 500k, k=50k, utils.py:142-145) and a before/after held-out eval.

Verifies the pretrained rows genuinely drive the trained model
(checksum of embedding rows vs the torch artifact) and that training
moves the loss.

Writes benchmarks/gpt2_full_results.json (+ one stdout JSON line).
A CPU-degraded run never clobbers a landed TPU artifact — it goes to
gpt2_full_results_cpu.json instead.

Usage:  python benchmarks/gpt2_full_smoke.py            (TPU if up)
        JAX_PLATFORMS=cpu GPT2_FULL_SMALL=1 python benchmarks/gpt2_full_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root harness

SMALL = os.environ.get("GPT2_FULL_SMALL", "") == "1"
# run the REAL 124M geometry even on a CPU backend (pipeline proof at
# real scale when no TPU is reachable; slow — tens of seconds/round)
FORCE_FULL = os.environ.get("GPT2_FULL_FORCE", "") == "1"
ROUNDS = int(os.environ.get("GPT2_FULL_ROUNDS", "16"))
WORKERS = int(os.environ.get("GPT2_FULL_WORKERS", "4"))
BATCH = int(os.environ.get("GPT2_FULL_BATCH", "2"))
STAGE_TIMEOUT = int(os.environ.get("BENCH_STAGE_TIMEOUT", "1200"))
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "gpt2_full_results.json")


def make_torch_checkpoint(small: bool) -> str:
    """A genuine `GPT2LMHeadModel.save_pretrained` artifact at the
    real gpt2-small geometry (124M params; tiny geometry when small),
    cached across runs — the exact artifact class the reference hands
    to from_pretrained."""
    import torch
    import transformers

    tag = "tiny" if small else "gpt2small"
    ckpt_dir = f"/tmp/gpt2_full_smoke_ckpt_{tag}"
    if os.path.isfile(os.path.join(ckpt_dir, "pytorch_model.bin")):
        return ckpt_dir
    if small:
        hf_cfg = transformers.GPT2Config(
            vocab_size=97, n_positions=64, n_embd=48, n_layer=2,
            n_head=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    else:
        # transformers.GPT2Config() IS gpt2-small: vocab 50257,
        # n_positions 1024, n_embd 768, n_layer 12, n_head 12
        hf_cfg = transformers.GPT2Config(
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    torch.manual_seed(21)
    pt = transformers.GPT2LMHeadModel(hf_cfg).eval()
    pt.save_pretrained(ckpt_dir, safe_serialization=False)
    return ckpt_dir


def main() -> int:
    jax, platform = bench.acquire_backend()
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache()

    from commefficient_tpu.config import Config
    from commefficient_tpu.data.loader import FedLoader, FedValLoader
    from commefficient_tpu.data.persona import FedPERSONA, HashTokenizer
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.training import gpt2_train
    from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

    small = SMALL and not FORCE_FULL
    t0 = time.time()
    with bench.alarm_guard(STAGE_TIMEOUT, "torch checkpoint"):
        ckpt_dir = make_torch_checkpoint(small)
    bench.log(f"torch save_pretrained artifact: {ckpt_dir} "
              f"({time.time() - t0:.1f}s)")

    # tokenizer sized like GPT2 BPE + the 5 PersonaChat special tokens
    # (50257 + 5; reference gpt2_train.py:26-32) so the load exercises
    # the special-token embedding resize exactly as the reference does
    tokenizer = HashTokenizer(102 if small else 50262)

    cfg = Config(
        mode="sketch", error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, weight_decay=0.0, microbatch_size=-1,
        # the reference's default sketch geometry (utils.py:142-145)
        k=100 if small else 50_000,
        num_rows=1 if small else 5,
        num_cols=1000 if small else 500_000,
        num_blocks=1 if small else 20,
        num_workers=WORKERS, local_batch_size=BATCH,
        lm_coef=1.0, mc_coef=1.0, seed=21,
    ).validate()

    # PersonaChat-shaped corpus: one persona per client (the natural
    # partition, reference fed_persona.py:144-147)
    n_personas = 8 if small else 4 * WORKERS
    train_set = FedPERSONA(
        f"/tmp/gpt2_full_data_{'t' if small else 'f'}", tokenizer=tokenizer,
        num_candidates=cfg.num_candidates, max_history=cfg.max_history,
        train=True, synthetic_examples=(n_personas, 2, 3), seed=21)
    val_set = FedPERSONA(
        f"/tmp/gpt2_full_data_{'t' if small else 'f'}", tokenizer=tokenizer,
        num_candidates=cfg.num_candidates, max_history=cfg.max_history,
        train=False, synthetic_examples=(n_personas, 2, 3), seed=21)
    seq_len = max(train_set.seq_len, val_set.seq_len)

    # the driver's production load path: genuine torch artifact ->
    # Flax params + special-token resize (require_load: a silent
    # fresh-init fallback would fake the "pretrained" claim)
    with bench.alarm_guard(STAGE_TIMEOUT, "pretrained load"):
        module, params = gpt2_train.build_model_and_params(
            cfg, tokenizer, seq_len, source=ckpt_dir, require_load=True)
    vec, _ = flatten_params(params)
    D = int(vec.shape[0])
    bench.log(f"loaded D={D} ({D / 1e6:.1f}M params) from {ckpt_dir}")

    # load verification: the artifact's embedding rows must BE the
    # model's first vocab rows (mean |.| agreement, not a fresh init)
    import torch
    sd = torch.load(os.path.join(ckpt_dir, "pytorch_model.bin"),
                    map_location="cpu", weights_only=True)
    want = sd["transformer.wte.weight"].numpy()
    got = np.asarray(
        params["params"]["transformer"]["wte"]["embedding"])[:want.shape[0]]
    load_max_err = float(np.max(np.abs(got - want)))
    if load_max_err > 1e-5:
        raise AssertionError(
            f"pretrained rows do not drive the model (max err "
            f"{load_max_err})")
    bench.log(f"pretrained load verified: wte max|err|={load_max_err:.2e}")

    loss_train = gpt2_train.make_compute_loss_train(module, cfg)
    loss_val = gpt2_train.make_compute_loss_val(module)
    model = FedModel(None, loss_train, cfg, loss_val=loss_val,
                     params=params, num_clients=train_set.num_clients)
    opt = FedOptimizer(model)
    train_loader = FedLoader(train_set, WORKERS, BATCH, seed=21)
    val_loader = FedValLoader(val_set, 4,
                              num_shards=min(jax.device_count(), WORKERS))
    spe = train_loader.steps_per_epoch
    sched = PiecewiseLinear([0, ROUNDS], [4e-2, 4e-3])
    lr_sched = LambdaLR(opt, lr_lambda=sched)

    with bench.alarm_guard(STAGE_TIMEOUT, "eval before"):
        nll0, acc0, ppl0 = gpt2_train.run_eval(model, val_loader)
    bench.log(f"eval before: nll {nll0:.3f} ppl {ppl0:.1f}")

    losses, round_times = [], []
    rounds_done = 0
    with bench.alarm_guard(STAGE_TIMEOUT * 2, "sketch rounds"):
        while rounds_done < ROUNDS:
            for client_ids, data, mask in train_loader.epoch():
                if rounds_done >= ROUNDS:
                    break
                lr_sched.step()
                t1 = time.time()
                loss, lm, mc, down, up = model((client_ids, data, mask))
                opt.step()
                losses.append(float(np.mean(np.asarray(loss))))
                round_times.append(time.time() - t1)
                rounds_done += 1
                if rounds_done in (1, 2) or rounds_done % 4 == 0:
                    bench.log(f"round {rounds_done} loss "
                              f"{losses[-1]:.3f} "
                              f"({round_times[-1]:.2f}s)")

    with bench.alarm_guard(STAGE_TIMEOUT, "eval after"):
        nll1, acc1, ppl1 = gpt2_train.run_eval(model, val_loader)
    bench.log(f"eval after: nll {nll1:.3f} ppl {ppl1:.1f}")

    # round 1 carries the compile; steady-state is the median of the rest
    steady_ms = float(np.median(round_times[1:]) * 1e3) \
        if len(round_times) > 1 else None

    out = {
        "metric": "gpt2_small_pretrained_federated_finetune",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "grad_size": D,
        "params_millions": round(D / 1e6, 1),
        "checkpoint": "torch GPT2LMHeadModel.save_pretrained "
                      "(real gpt2-small geometry, locally generated)",
        "load_wte_max_err": load_max_err,
        "vocab_after_resize": len(tokenizer),
        "sketch_geometry": {"rows": cfg.num_rows, "cols": cfg.num_cols,
                            "k": cfg.k, "blocks": cfg.num_blocks},
        "rounds": rounds_done,
        "num_workers": WORKERS, "local_batch": BATCH,
        "seq_len": seq_len, "steps_per_epoch": spe,
        "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
        "round_ms_steady": round(steady_ms, 1) if steady_ms else None,
        "eval_before": {"nll": round(nll0, 4), "ppl": round(ppl0, 2),
                        "mc_acc": round(acc0, 4)},
        "eval_after": {"nll": round(nll1, 4), "ppl": round(ppl1, 2),
                       "mc_acc": round(acc1, 4)},
        "wall_clock_s": round(time.time() - t0, 1),
    }

    # training from the (random-weight) checkpoint must actually move:
    # eval NLL after N sketch rounds below eval NLL before
    assert np.isfinite(nll1), "eval NLL not finite"
    assert nll1 < nll0, \
        f"sketch rounds did not reduce held-out NLL ({nll0} -> {nll1})"

    dest = bench.artifact_dest(OUT, platform)
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(bench.worker_entry(main))
