"""The plain reference of one federated round, and the comparison that
decides `correct`.

What the program does in one fused device program per round is redone
here step by step: per-client forward and backward through the
configuration's plain model (`configs/<reference>.py`), weight decay,
the count sketch by its per-coordinate definition, the sum over
clients, momentum and error feedback, median-of-rows estimates, an
exact top-k, the re-sketch that zeroes what was sent, per-client
top-k with local error and momentum, and the weight update. Nothing of
the program is imported and nothing it made is read: the reference
gets the weights the benchmark made from the seed and the batches the
benchmark fed.

The model's arithmetic runs through jax on whatever device is there
(after the program's state has been freed); the compressor and the
server run in numpy on the host.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np


# --------------------------------------------------------------------------
# the count sketch, per coordinate


class Sketch:
    """r x c count sketch of a d-vector. Coordinate i lies in chunk
    b = i // c at position p = i % c; row j puts it in bucket
    (p + offset[j, b]) % c with sign eps[j, p] * delta[j, b]. The
    three tables are the configuration's (drawn from its hash seed in
    the order offsets, eps, delta), as weights are."""

    def __init__(self, d: int, c: int, r: int, seed: int = 42):
        self.d, self.c, self.r = int(d), int(c), int(r)
        self.n_chunks = -(-self.d // self.c)
        rng = np.random.RandomState(seed)
        self.offsets = rng.randint(0, self.c, size=(self.r, self.n_chunks))
        self.eps = rng.choice([-1.0, 1.0], size=(self.r, self.c)) \
            .astype(np.float32)
        self.delta = rng.choice([-1.0, 1.0],
                                size=(self.r, self.n_chunks)) \
            .astype(np.float32)

    def _chunks(self):
        for b in range(self.n_chunks):
            lo = b * self.c
            hi = min(lo + self.c, self.d)
            yield b, lo, hi

    def _runs(self, j: int, b: int, n: int):
        """Positions 0..n-1 of chunk b go to buckets (p + offset) % c:
        two contiguous runs. Yields (positions, buckets) slices."""
        off = int(self.offsets[j, b])
        first = min(n, self.c - off)
        yield slice(0, first), slice(off, off + first)
        if first < n:
            yield slice(first, n), slice(0, n - first)

    def encode(self, vec: np.ndarray) -> np.ndarray:
        table = np.zeros((self.r, self.c), np.float32)
        for b, lo, hi in self._chunks():
            seg = np.asarray(vec[lo:hi], np.float32)
            for j in range(self.r):
                signed = seg * self.eps[j, :hi - lo] * self.delta[j, b]
                # positions of one chunk land in distinct buckets
                for pos, bucket in self._runs(j, b, hi - lo):
                    table[j, bucket] += signed[pos]
        return table

    def estimates(self, table: np.ndarray) -> np.ndarray:
        """Median over rows of sign x bucket, for every coordinate."""
        out = np.empty(self.d, np.float32)
        for b, lo, hi in self._chunks():
            rows = np.empty((self.r, hi - lo), np.float32)
            for j in range(self.r):
                for pos, bucket in self._runs(j, b, hi - lo):
                    rows[j, pos] = table[j, bucket]
                rows[j] *= self.eps[j, :hi - lo] * self.delta[j, b]
            out[lo:hi] = median_rows(rows)
        return out


def median_rows(rows: np.ndarray) -> np.ndarray:
    """Median over axis 0; five rows go through a fixed network of
    minima and maxima, which is what makes 124M columns affordable."""
    if rows.shape[0] != 5:
        return np.median(rows, axis=0)
    a, b, c, d, e = rows
    lo1, hi1 = np.minimum(a, b), np.maximum(a, b)
    lo2, hi2 = np.minimum(c, d), np.maximum(c, d)
    lo = np.maximum(lo1, lo2)          # drops the smallest of a..d
    hi = np.minimum(hi1, hi2)          # drops the largest of a..d
    # the median of five is the median of {lo, hi, e}
    return np.maximum(np.minimum(lo, hi),
                      np.minimum(np.maximum(lo, hi), e))


def top_k_dense(vec: np.ndarray, k: int) -> np.ndarray:
    """`vec` at its k entries of largest magnitude, zero elsewhere
    (entries that tie with the k-th are all kept)."""
    k = min(int(k), vec.shape[0])
    mag = np.abs(vec)
    kth = np.partition(mag, vec.shape[0] - k)[vec.shape[0] - k]
    return np.where(mag >= max(kth, np.finfo(np.float32).tiny), vec,
                    np.float32(0.0)).astype(vec.dtype)


# --------------------------------------------------------------------------
# one job's rounds


class Job(NamedTuple):
    """What the reference needs to know of a cell (from its traffic
    and configuration files, never from the program)."""
    mode: str                 # "sketch" | "local_topk" | "uncompressed"
    d: int
    k: int
    num_rows: int
    num_cols: int
    hash_seed: int
    num_workers: int
    num_clients: int
    weight_decay: float
    virtual_momentum: float
    local_momentum: float
    error_type: str           # "virtual" | "local" | "none"
    upload_bytes_per_client: int


def job_from(config: dict, traffic: dict) -> Job:
    mode = traffic["mode"]
    if mode == "sketch":
        up = traffic["num_rows"] * traffic["num_cols"] * 4
    elif mode == "local_topk":
        up = traffic["k"] * 4
    else:
        up = config["grad_size"] * 4
    return Job(mode=mode, d=int(config["grad_size"]), k=int(traffic["k"]),
               num_rows=int(traffic.get("num_rows", 0)),
               num_cols=int(traffic.get("num_cols", 0)),
               hash_seed=int(config["sketch_hash"]["seed"]),
               num_workers=int(traffic["num_workers"]),
               num_clients=int(traffic["num_clients"]),
               weight_decay=float(config["weight_decay"]),
               virtual_momentum=float(traffic["virtual_momentum"]),
               local_momentum=float(traffic["local_momentum"]),
               error_type=traffic["error_type"],
               upload_bytes_per_client=up)


class StepReadings(NamedTuple):
    loss: float                       # mean over clients of their mean loss
    upload_bytes: float               # billed for the round
    state: Dict[str, np.ndarray]      # named rows of optimizer state after the step


class Readings(NamedTuple):
    """What is compared, from either side: one StepReadings per
    checked round (state kept for the first only) and the flat
    weights after the last."""
    steps: List[StepReadings]
    weights: np.ndarray
    weights_first: Optional[np.ndarray] = None   # after the first round


class ModelFns(NamedTuple):
    """The configuration's plain model, closed over its config:
    client_grad(weights_flat, client_data, client_mask) ->
    (the gradient of the client's loss times its count of valid
    examples [d] (a device array), the client's loss)."""
    client_grad: Callable
    to_host: Callable
    add: Callable


def make_model_fns(ref_module, config: dict, template, dtype=None,
                   precision: Optional[str] = None) -> ModelFns:
    """Jitted per-client gradient of the plain model. `template` is a
    parameter tree (shapes only are used); `dtype` computes the model
    in a lower precision (the control); `precision` is the matmul
    precision the configuration states."""
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    _, unravel = ravel_pytree(jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), template))
    stated = precision or config["precision"]["matmul"]

    def loss_of(vec, data, mask):
        params = unravel(vec)
        if dtype is not None:
            params = jax.tree.map(lambda x: x.astype(dtype), params)
            data = ref_module.cast_data(data, dtype)
        return ref_module.client_loss(config, params, data, mask) \
            .astype(jnp.float32)

    @jax.jit
    def client_grad(vec, data, mask):
        with jax.default_matmul_precision(stated):
            loss, g = jax.value_and_grad(loss_of)(vec, data, mask)
        # what a client sends is its mean gradient times its count
        return g * mask.sum(), loss

    add = jax.jit(lambda a, b: a + b)
    return ModelFns(client_grad, lambda x: np.asarray(x), add)


def run_reference(job: Job, fns: ModelFns, weights0: np.ndarray,
                  feeds: list, lrs: list) -> Readings:
    """Follow the rounds in `feeds` (each (client_ids, data, mask) as
    the program was fed) from `weights0`."""
    import jax.numpy as jnp

    w = np.array(weights0, np.float32)
    sketch = (Sketch(job.d, job.num_cols, job.num_rows, job.hash_seed)
              if job.mode == "sketch" else None)
    if job.mode == "sketch":
        V = np.zeros((job.num_rows, job.num_cols), np.float32)
        E = np.zeros_like(V)
    else:
        V = np.zeros(job.d, np.float32)
        E = np.zeros(job.d, np.float32)
    # per-client rows, held for the clients that took part
    c_err: Dict[int, np.ndarray] = {}
    c_vel: Dict[int, np.ndarray] = {}
    steps = []
    for (client_ids, data, mask), lr in zip(feeds, lrs):
        wdev = jnp.asarray(w)
        counts = mask.sum(axis=1)
        total = float(counts.sum())
        losses = []
        state = {}
        if job.mode == "local_topk":
            agg = np.zeros(job.d, np.float64)
            for i, cid in enumerate(np.asarray(client_ids)):
                g, loss = fns.client_grad(
                    wdev, tuple(x[i] for x in data), mask[i])
                losses.append(float(loss))
                g = fns.to_host(g) / max(float(counts[i]), 1.0)
                g = g + (job.weight_decay / job.num_workers) * w
                g = g * float(counts[i])
                vel = c_vel.get(int(cid), 0.0)
                err = c_err.get(int(cid), 0.0)
                if job.local_momentum > 0:
                    vel = g + job.local_momentum * vel
                    acc = vel
                else:
                    acc = g
                if job.error_type == "local":
                    err = err + acc
                    acc = err
                sent = top_k_dense(np.asarray(acc, np.float32), job.k)
                keep = (sent == 0)
                if job.error_type == "local":
                    c_err[int(cid)] = (err * keep).astype(np.float32)
                if job.local_momentum > 0:
                    c_vel[int(cid)] = (vel * keep).astype(np.float32)
                agg += sent
                if len(steps) == 0:
                    if job.local_momentum > 0:
                        state[f"velocity[{i}]"] = c_vel[int(cid)]
                    if job.error_type == "local":
                        state[f"error[{i}]"] = c_err[int(cid)]
            gradient = (agg / max(total, 1.0)).astype(np.float32)
            V = gradient + job.virtual_momentum * V
            update = V
        else:
            acc = None
            for i in range(len(client_ids)):
                g, loss = fns.client_grad(
                    wdev, tuple(x[i] for x in data), mask[i])
                losses.append(float(loss))
                acc = g if acc is None else fns.add(acc, g)
            g = fns.to_host(acc)
            g = g + (job.weight_decay / job.num_workers) * w * total
            gradient = (g / max(total, 1.0)).astype(np.float32)
            if job.mode == "sketch":
                V = sketch.encode(gradient) + job.virtual_momentum * V
                if job.error_type == "virtual":
                    E = E + V
                    table = E
                else:
                    table = V
                update = top_k_dense(sketch.estimates(table), job.k)
                keep = (sketch.encode(update) == 0)
                if job.error_type == "virtual":
                    E = E * keep
                V = V * keep
                if len(steps) == 0:
                    for j in range(job.num_rows):
                        state[f"momentum[{j}]"] = V[j].copy()
            else:
                V = gradient + job.virtual_momentum * V
                update = V
                if len(steps) == 0:
                    state["momentum"] = V.copy()
        w = (w - np.float32(lr) * update).astype(np.float32)
        if not steps:
            w_first = w.copy()
        steps.append(StepReadings(
            loss=float(np.mean(losses)),
            upload_bytes=float(job.upload_bytes_per_client
                               * len(client_ids)),
            state=state))
    return Readings(steps, w, w_first)


# --------------------------------------------------------------------------
# the comparison


def _gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def leaf_slices(template) -> list:
    """(name, start, stop) of every parameter leaf in the flat
    vector's order."""
    import jax

    out, at = [], 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(template)[0]:
        n = int(np.prod(leaf.shape))
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out.append((name, at, at + n))
        at += n
    return out


def compare(program: Readings, reference: Readings, weights0: np.ndarray,
            slices: list, limits: dict) -> dict:
    """Every number compared, each with its limit. Returns
    {name: {"value": v, "limit": l}} for the numbers the cell's
    traffic file gives a limit; `correct` is all(v <= l).

    loss_gap          worst over the checked rounds of |loss - ref| / ref
    first_grad_diff   as first_grad_gap, but the norm of the rows'
                      difference over the shared cells against the
                      reference's norm: a gap of norms sees rounding
                      noise only in second order, this sees it in first
    first_grad_gap    the first gradient as the optimizer got it, from
                      the state after round one: by the worst row of
                      the momentum state, the gap between the norms of
                      the program's and the reference's row over the
                      cells that both still hold (what was sent is
                      zeroed on both sides, and the two selections
                      differ where the program's approximate top-k
                      does), against the reference's norm
    change_gap        the parameters' change over the checked rounds,
                      by the worst leaf (see `change_gap`)
    upload_bytes_gap  bytes billed per round against the
                      configuration's arithmetic (exact)
    """
    out = {}
    loss = max(_gap(p.loss, r.loss)
               for p, r in zip(program.steps, reference.steps))
    out["loss_gap"] = loss
    worst = worst_diff = 0.0
    ref_state = reference.steps[0].state
    for name, ref_row in ref_state.items():
        row = np.asarray(program.steps[0].state[name], np.float32)
        both = (row != 0) & (ref_row != 0)
        if not both.any():
            worst = worst_diff = 1.0
            continue
        p64 = row[both].astype(np.float64)
        r64 = ref_row[both].astype(np.float64)
        b = float(np.linalg.norm(r64))
        shared = both.sum() / max(int((ref_row != 0).sum()), 1)
        # rows that share few cells were not built from one gradient
        few = 0.0 if shared > 0.5 else 1.0
        worst = max(worst, _gap(float(np.linalg.norm(p64)), b), few)
        worst_diff = max(worst_diff, float(np.linalg.norm(p64 - r64))
                         / max(b, 1e-30), few)
    out["first_grad_gap"] = worst
    out["first_grad_diff"] = worst_diff
    out["change_gap"] = change_gap(program.weights, reference.weights,
                                   weights0, slices)
    out["upload_bytes_gap"] = max(
        abs(p.upload_bytes - r.upload_bytes)
        for p, r in zip(program.steps, reference.steps))
    result = {}
    for name, value in out.items():
        if name not in limits:
            continue          # read by `diagnostics`, not compared here
        if not math.isfinite(value):
            value = float("inf")
        result[name] = {"value": float(value),
                        "limit": float(limits[name])}
    return result


_ALL = {name: 0.0 for name in (
    "loss_gap", "first_grad_gap", "first_grad_diff", "change_gap",
    "upload_bytes_gap")}


def change_gap(program_w, reference_w, weights0, slices,
               floor: str = "rms") -> float:
    """The parameters' change by the worst leaf: the gap between the
    norms of the program's and the reference's change of that leaf,
    against the reference's norm of that leaf or a floor, whichever
    is larger. The floor is the larger of the median leaf's norm and
    the root mean square of the leaves' norms: a top-k update moves
    50,000 of up to 124 million coordinates, so in a model of many
    leaves the median leaf hardly moves at all and a floor of the
    median alone lets one coordinate selected on one side decide the
    number (PERF.md, section 2). `floor="median"` is the median
    alone, kept as a diagnostic."""
    dp = np.asarray(program_w, np.float64) - weights0
    dr = np.asarray(reference_w, np.float64) - weights0
    ref_norms = np.array([np.linalg.norm(dr[a:b]) for _, a, b in slices])
    base = float(np.median(ref_norms))
    if floor == "rms":
        base = max(base, float(np.sqrt(np.mean(ref_norms ** 2))))
    worst = 0.0
    for (_, a, b), rn in zip(slices, ref_norms):
        worst = max(worst, abs(float(np.linalg.norm(dp[a:b])) - rn)
                    / max(float(rn), base, 1e-30))
    return worst


def diagnostics(program: Readings, reference: Readings,
                weights0: np.ndarray, slices: list) -> dict:
    """Not compared: how the two sides' first updates overlap. The
    program selects with the chip's approximate top-k (recall about
    0.95) or a sampled threshold, the reference with an exact top-k,
    so `support_overlap` (the share of the reference's updated
    coordinates that the program updated too) says how much of
    `change_gap` is selection."""
    every = compare(program, reference, weights0, slices, _ALL)
    out = {"first_grad_diff": every["first_grad_diff"]["value"],
           "change_gap_median_floor": change_gap(
               program.weights, reference.weights, weights0, slices,
               floor="median")}
    if program.weights_first is None or reference.weights_first is None:
        return out
    sp = np.asarray(program.weights_first) != weights0
    sr = np.asarray(reference.weights_first) != weights0
    return {**out, "program_updated": float(sp.sum()),
            "reference_updated": float(sr.sum()),
            "support_overlap": float((sp & sr).sum())
            / max(float(sr.sum()), 1.0)}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
