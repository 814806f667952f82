"""The one general traffic generator: a traffic file's parameters in,
the corpus on disk and the driver's arguments out.

A traffic mix is data (`traffic/<name>.json`): mode, cohort, local
batch, population, table, k, the corpus and its seed. The corpus is
written once per checkout under the benchmark's cache directory and
found again by later runs of any cell that names the same one; which
clients are drawn, which of their rows, in which order and how they
are augmented follows `--seed` through the program's own sampler and
transforms, so every seed gets the same sizes in another order.

`write_cifar10` is a copy of `commefficient_tpu.data.cifar.
write_cifar10_archive` (the original is listed in PERF.md for a later
PR to point here or delete).
"""
from __future__ import annotations

import json
import os
import pickle

import numpy as np

CIFAR10_LABELS = [
    b"airplane", b"automobile", b"bird", b"cat", b"deer",
    b"dog", b"frog", b"horse", b"ship", b"truck",
]


def load_traffic(bench_dirs, name: str) -> dict:
    for d in bench_dirs:
        path = os.path.join(d, "traffic", name + ".json")
        if os.path.isfile(path):
            with open(path) as f:
                spec = json.load(f)
            spec["name"] = name
            return spec
    raise FileNotFoundError(
        f"no traffic file {name}.json under "
        f"{[os.path.join(d, 'traffic') for d in bench_dirs]}")


def _dump(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(obj, f, protocol=2)
    os.replace(tmp, path)


def write_cifar10(root: str, seed: int, n_per_batch: int) -> str:
    """A `cifar-10-batches-py` directory in the real download's format
    (5 train pickles + test_batch + batches.meta, CHW uint8 rows),
    holding class-pattern-plus-noise images drawn from `seed`."""
    d = os.path.join(root, "cifar-10-batches-py")
    if os.path.isfile(os.path.join(d, "batches.meta")):   # written last
        return d
    os.makedirs(d, exist_ok=True)
    rng = np.random.RandomState(seed)
    protos = rng.rand(10, 32, 32, 3).astype(np.float32)

    def rows(n, tag):
        labels = rng.randint(0, 10, size=n)
        noise = rng.rand(n, 32, 32, 3).astype(np.float32)
        imgs = ((0.6 * protos[labels] + 0.4 * noise) * 255) \
            .astype(np.uint8)
        data = imgs.transpose(0, 3, 1, 2).reshape(n, 3072)
        names = [b"%s_s_%06d.png" % (CIFAR10_LABELS[l], i)
                 for i, l in enumerate(labels)]
        return {b"batch_label": tag, b"labels": labels.tolist(),
                b"data": data, b"filenames": names}

    for i in range(1, 6):
        _dump(os.path.join(d, f"data_batch_{i}"),
              rows(n_per_batch, b"training batch %d of 5" % i))
    _dump(os.path.join(d, "test_batch"),
          rows(max(n_per_batch // 10, 16), b"testing batch 1 of 1"))
    _dump(os.path.join(d, "batches.meta"),
          {b"num_cases_per_batch": n_per_batch,
           b"label_names": CIFAR10_LABELS, b"num_vis": 3072})
    return d


def _sequence_tokens(persona, history, reply, max_history: int) -> int:
    """Tokens of one candidate sequence under a word-level tokenizer:
    <bos> + persona words, a speaker token before each of the last
    2 * max_history + 1 turns and before the reply, <eos> after it."""
    turns = history[-(2 * max_history + 1):]
    return (1 + sum(len(p.split()) for p in persona)
            + sum(1 + len(t.split()) for t in turns)
            + 1 + len(reply.split()) + 1)


def write_personachat(root: str, seed: int, num_personas: int,
                      dialogs_per_persona: int,
                      utterances_per_dialog: int, num_candidates: int,
                      max_history: int, max_tokens: int,
                      vocab_words: int, tail_alpha: float) -> str:
    """A `personachat_self_original.json` in the raw schema (after
    `commefficient_tpu.data.persona.write_personachat_raw`, with
    sentence lengths as parameters): persona sentences of 5 to 12
    words, turns of 4 + Pareto(tail_alpha) * 4 words, every candidate
    sequence cut to `max_tokens` and the first one padded up to it,
    so the corpus-wide longest sequence is exactly `max_tokens`."""
    path = os.path.join(root, "PERSONA", "personachat_self_original.json")
    if os.path.isfile(path):
        return path
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rng = np.random.RandomState(seed)
    words = np.array([f"w{i}" for i in range(vocab_words)])

    def sent(n):
        return " ".join(rng.choice(words, size=int(n)))

    def turn():
        return sent(min(4 + int(rng.pareto(tail_alpha) * 4), 60))

    def trim_history(persona, history):
        """Shorten the longest of the turns a sequence will hold until
        a three-word reply still fits."""
        lo = max(len(history) - (2 * max_history + 1), 0)
        while _sequence_tokens(persona, history, "a b c",
                               max_history) > max_tokens:
            i = max(range(lo, len(history)),
                    key=lambda j: len(history[j].split()))
            history[i] = " ".join(history[i].split()[:-1])

    def fit(persona, history, reply, exact=False):
        """Cut (or, with `exact`, pad) the reply so the sequence has
        at most (exactly) max_tokens."""
        n = _sequence_tokens(persona, history, reply, max_history)
        w = reply.split()
        if n > max_tokens:
            w = w[:len(w) - (n - max_tokens)]
        elif exact and n < max_tokens:
            w = w + list(rng.choice(words, size=max_tokens - n))
        return " ".join(w)

    first = [True]
    personas = {}

    def dialog(pid):
        if pid not in personas:
            personas[pid] = [
                f"persona {pid} trait {t} " + sent(rng.randint(2, 9))
                for t in range(rng.randint(4, 6))]
        persona = personas[pid]
        utts, history = [], [turn()]
        for _ in range(utterances_per_dialog):
            trim_history(persona, history)
            cands = [fit(persona, history, turn())
                     for _ in range(num_candidates)]
            if first[0]:
                cands[-1] = fit(persona, history, cands[-1], exact=True)
                first[0] = False
            utts.append({"history": list(history), "candidates": cands})
            history.append(cands[-1])
            history.append(turn())
        return {"personality": persona, "utterances": utts}

    train = [dialog(p) for p in range(num_personas)
             for _ in range(dialogs_per_persona)]
    valid = [dialog(10_000 + p) for p in range(2)]
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"train": train, "valid": valid}, f)
    os.replace(tmp, path)
    return path


WRITERS = {"cifar10": write_cifar10, "personachat": write_personachat}


def ensure_corpus(traffic: dict, cache_dir: str) -> str:
    """Write the traffic's corpus unless this checkout has it; returns
    the directory a driver takes as `--dataset_dir`."""
    corpus = traffic["corpus"]
    kind = corpus["kind"]
    params = {k: v for k, v in corpus.items() if k != "kind"}
    key = kind + "".join(f"_{k}{params[k]}" for k in sorted(params))
    root = os.path.join(cache_dir, "data", key)
    os.makedirs(root, exist_ok=True)
    WRITERS[kind](root, **params)
    return root
