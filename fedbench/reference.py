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
server run in numpy on the host, in float32 vectors updated in place.
The comparison follows the reference round by round and keeps float64
sums taken a block at a time (`take_sums`), so that at D = 6.6e8 the
phase holds five float32 D-vectors at its peak (`weights0`, the
program's first-round momentum, and the reference's weights, momentum
and gradient) and none of float64.
"""
from __future__ import annotations

import dataclasses
import math
from typing import (
    Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple,
)

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


@dataclasses.dataclass
class Readings:
    """What the program's side hands to the comparison: one
    StepReadings per checked round (state kept for the first only),
    the flat weights after the last and, where the driver keeps them,
    after the first. `take_sums` lets each vector go once it has read
    it (the field becomes None, the first round's rows leave their
    dict), so whoever built this keeps no other name for them."""
    steps: List[StepReadings]
    weights: Optional[np.ndarray]
    weights_first: Optional[np.ndarray] = None


class ModelFns(NamedTuple):
    """The configuration's plain model, closed over its config:
    client_grad(weights_flat, client_data, client_mask) ->
    (the gradient of the client's loss times its count of valid
    examples [d] (a device array), the client's loss)."""
    client_grad: Callable
    to_host: Callable
    add: Callable


# coordinates of a D-vector brought off the device at a time
TRANSFER = 1 << 26


def to_host(x) -> np.ndarray:
    """A D-vector off the device into a float32 array of the caller's
    own, TRANSFER coordinates at a time: the runtime keeps a host
    staging buffer of each transfer's size, and `np.asarray` of the
    whole vector would make that one more D-vector."""
    n = x.shape[0]
    out = np.empty(n, np.float32)
    for i in range(0, n, TRANSFER):
        out[i:i + TRANSFER] = np.asarray(
            x if n <= TRANSFER else x[i:i + TRANSFER])
    return out


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
    return ModelFns(client_grad, to_host, add)


def reference_rounds(job: Job, fns: ModelFns, weights0: np.ndarray,
                     feeds: list, lrs: list
                     ) -> Iterator[Tuple[StepReadings, np.ndarray]]:
    """Follow the rounds in `feeds` (each (client_ids, data, mask) as
    the program was fed) from `weights0`; after each round, its
    StepReadings and the weights.

    The server's vectors (`w`, `V`, the gradient off the device) are
    float32 and updated in place, so that a round holds no spare
    D-vector: the gradient's buffer takes the step `lr * update` once
    the momentum has taken the gradient. What is yielded are those
    vectors themselves (the weights; after the first round the rows
    of the momentum state): the next round writes to them, so read
    them before asking for it."""
    import jax.numpy as jnp

    w = np.array(weights0, np.float32)
    decay = job.weight_decay / job.num_workers
    sketch = E = None
    if job.mode == "sketch":
        sketch = Sketch(job.d, job.num_cols, job.num_rows, job.hash_seed)
        V = np.zeros((job.num_rows, job.num_cols), np.float32)
        if job.error_type == "virtual":
            E = np.zeros_like(V)
    else:
        V = np.zeros(job.d, np.float32)
    # per-client rows, held for the clients that took part
    c_err: Dict[int, np.ndarray] = {}
    c_vel: Dict[int, np.ndarray] = {}
    for n, ((client_ids, data, mask), lr) in enumerate(zip(feeds, lrs)):
        first = n == 0
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
                g = fns.to_host(g)
                g /= max(float(counts[i]), 1.0)
                if decay != 0:
                    g += decay * w
                g *= float(counts[i])
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
                if first:
                    if job.local_momentum > 0:
                        state[f"velocity[{i}]"] = c_vel[int(cid)]
                    if job.error_type == "local":
                        state[f"error[{i}]"] = c_err[int(cid)]
            gradient = (agg / max(total, 1.0)).astype(np.float32)
            del agg
        else:
            acc = None
            for i in range(len(client_ids)):
                g, loss = fns.client_grad(
                    wdev, tuple(x[i] for x in data), mask[i])
                losses.append(float(loss))
                acc = g if acc is None else fns.add(acc, g)
            gradient = fns.to_host(acc)
            del acc, g
            if decay != 0:
                gradient += decay * w * total
            gradient /= max(total, 1.0)
        del wdev
        if job.mode == "sketch":
            V *= job.virtual_momentum
            V += sketch.encode(gradient)
            if job.error_type == "virtual":
                E += V
                table = E
            else:
                table = V
            update = top_k_dense(sketch.estimates(table), job.k)
            keep = (sketch.encode(update) == 0)
            if job.error_type == "virtual":
                E *= keep
            V *= keep
            if first:
                for j in range(job.num_rows):
                    state[f"momentum[{j}]"] = V[j]
        else:
            V *= job.virtual_momentum
            V += gradient
            update = V
            if first and job.mode == "uncompressed":
                state["momentum"] = V
        # nothing reads the gradient once the momentum has taken it
        np.multiply(update, np.float32(lr), out=gradient)
        w -= gradient
        # (a suspended generator keeps its locals: the buffer goes now)
        del gradient, update
        yield StepReadings(
            loss=float(np.mean(losses)),
            upload_bytes=float(job.upload_bytes_per_client
                               * len(client_ids)),
            state=state), w


# --------------------------------------------------------------------------
# the comparison


def _gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _reading(value: float) -> float:
    """A number as it is printed and compared: one that is not
    finite reads as infinite, over every limit."""
    return float(value) if math.isfinite(value) else float("inf")


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


# cells of a row or of the weight vectors walked at a time: the
# comparison's transients are a few float64 blocks of this length,
# whatever D is (8 MB each, which still fits a host's caches: blocks
# of 2^22 and more walk at half the speed)
BLOCK = 1 << 20


class RowSums(NamedTuple):
    """One first-round state row against the reference's, over the
    cells that both still hold."""
    program_sq: float       # sum of squares of the program's cells there
    reference_sq: float     # of the reference's
    diff_sq: float          # of their difference
    shared: int             # cells both hold (non-zero on both sides)
    held: int               # cells the reference holds


class Sums(NamedTuple):
    """Everything `compare` and `diagnostics` read, taken by
    `take_sums` while the reference follows the rounds: float64
    scalars, none of them the length of a vector."""
    loss_gap: float
    upload_bytes_gap: float
    rows: Dict[str, RowSums]            # by the reference's row names
    program_change_sq: np.ndarray       # per leaf, the change over the
    reference_change_sq: np.ndarray     # checked rounds, squared and summed
    # coordinates the first round moved: (program, reference, both);
    # None where the program's side kept no weights after it
    updated: Optional[tuple]


def row_sums(row: np.ndarray, ref_row: np.ndarray,
             block: int = BLOCK) -> RowSums:
    program_sq = reference_sq = diff_sq = 0.0
    shared = held = 0
    for lo in range(0, ref_row.shape[0], block):
        p = np.asarray(row[lo:lo + block], np.float32)
        r = ref_row[lo:lo + block]
        both = r != 0
        held += int(np.count_nonzero(both))
        both &= p != 0
        n = int(np.count_nonzero(both))
        if n == 0:
            continue
        shared += n
        if n < p.shape[0]:
            p, r = p[both], r[both]
        p64 = p.astype(np.float64)
        r64 = r.astype(np.float64)
        program_sq += float(p64 @ p64)
        reference_sq += float(r64 @ r64)
        p64 -= r64
        diff_sq += float(p64 @ p64)
    return RowSums(program_sq, reference_sq, diff_sq, shared, held)


def change_sums(weights: np.ndarray, weights0: np.ndarray, slices: list,
                block: int = BLOCK) -> np.ndarray:
    """Per leaf, the sum of squares of `weights - weights0`. A block
    gives each leaf it touches that leaf's part of it, so a leaf may
    span blocks and a block leaves."""
    out = np.zeros(len(slices))
    leaf = 0
    for lo in range(0, weights0.shape[0], block):
        hi = min(lo + block, weights0.shape[0])
        change = np.asarray(weights[lo:hi], np.float64) - weights0[lo:hi]
        while leaf < len(slices) and slices[leaf][1] < hi:
            _, a, b = slices[leaf]
            part = change[max(a, lo) - lo:min(b, hi) - lo]
            out[leaf] += float(part @ part)
            if b > hi:
                break           # the next block has the leaf's rest
            leaf += 1
    return out


def updated_counts(program_w: np.ndarray, reference_w: np.ndarray,
                   weights0: np.ndarray, block: int = BLOCK) -> tuple:
    """Coordinates that differ from `weights0`: in the program's
    weights, in the reference's, in both."""
    program = reference = both = 0
    for lo in range(0, weights0.shape[0], block):
        w0 = weights0[lo:lo + block]
        sp = np.asarray(program_w[lo:lo + block]) != w0
        sr = reference_w[lo:lo + block] != w0
        program += int(np.count_nonzero(sp))
        reference += int(np.count_nonzero(sr))
        sp &= sr
        both += int(np.count_nonzero(sp))
    return program, reference, both


def take_sums(program: Readings, rounds: Iterator, weights0: np.ndarray,
              slices: list, block: int = BLOCK) -> Sums:
    """Follow the reference (`rounds`, a `reference_rounds`) and take
    each sum as soon as both sides' vectors for it exist, `block`
    cells at a time; each of the program's vectors is let go once
    read (see Readings). Beside `weights0`, what is held at any time
    is what the reference itself holds and, until its first round is
    done, the program's first-round rows and weights."""
    program_sq = change_sums(program.weights, weights0, slices, block)
    program.weights = None
    rows, updated = {}, None
    loss_gaps, upload_gaps = [], []
    for mine, (step, w) in zip(program.steps, rounds):
        for name, ref_row in step.state.items():
            rows[name] = row_sums(mine.state.pop(name), ref_row, block)
        if program.weights_first is not None:
            updated = updated_counts(program.weights_first, w, weights0,
                                     block)
            program.weights_first = None
        loss_gaps.append(_gap(mine.loss, step.loss))
        upload_gaps.append(abs(mine.upload_bytes - step.upload_bytes))
    return Sums(max(loss_gaps), max(upload_gaps), rows, program_sq,
                change_sums(w, weights0, slices, block), updated)


def compare(sums: Sums, limits: dict) -> dict:
    """Every number compared, each with its limit, from the sums
    `take_sums` made of the two sides. Returns
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
    gap, diff = first_grad(sums)
    out = {"loss_gap": sums.loss_gap, "first_grad_gap": gap,
           "first_grad_diff": diff, "change_gap": change_gap(sums),
           "upload_bytes_gap": sums.upload_bytes_gap}
    result = {}
    for name, value in out.items():
        if name not in limits:
            continue          # read by `diagnostics`, not compared here
        result[name] = {"value": _reading(value),
                        "limit": float(limits[name])}
    return result


def first_grad(sums: Sums) -> tuple:
    """(`first_grad_gap`, `first_grad_diff`) of `compare`."""
    worst = worst_diff = 0.0
    for s in sums.rows.values():
        if s.shared == 0:
            worst = worst_diff = 1.0
            continue
        b = math.sqrt(s.reference_sq)
        # rows that share few cells were not built from one gradient
        few = 0.0 if s.shared / max(s.held, 1) > 0.5 else 1.0
        worst = max(worst, _gap(math.sqrt(s.program_sq), b), few)
        worst_diff = max(worst_diff,
                         math.sqrt(s.diff_sq) / max(b, 1e-30), few)
    return worst, worst_diff


def change_gap(sums: Sums, floor: str = "rms") -> float:
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
    ref_norms = np.sqrt(sums.reference_change_sq)
    base = float(np.median(ref_norms))
    if floor == "rms":
        base = max(base, float(np.sqrt(np.mean(ref_norms ** 2))))
    worst = 0.0
    for pn, rn in zip(np.sqrt(sums.program_change_sq), ref_norms):
        worst = max(worst, abs(float(pn) - rn)
                    / max(float(rn), base, 1e-30))
    return worst


def diagnostics(sums: Sums) -> dict:
    """Not compared: how the two sides' first updates overlap. The
    program selects with the chip's approximate top-k (recall about
    0.95) or a sampled threshold, the reference with an exact top-k,
    so `support_overlap` (the share of the reference's updated
    coordinates that the program updated too) says how much of
    `change_gap` is selection."""
    out = {"first_grad_diff": _reading(first_grad(sums)[1]),
           "change_gap_median_floor": change_gap(sums, floor="median")}
    if sums.updated is None:
        return out
    program, reference, both = sums.updated
    return {**out, "program_updated": float(program),
            "reference_updated": float(reference),
            "support_overlap": float(both) / max(float(reference), 1.0)}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
