"""The comparison in blocks against the plain expressions it replaced.

    JAX_PLATFORMS=cpu python -m pytest fedbench/tests/test_blocks.py -q

`reference.take_sums` follows the reference's rounds, walks the
first-round state rows and the weight vectors a block at a time and
keeps float64 scalars; `compare`, `change_gap` and `diagnostics` read
those. What the numbers MEAN is
written out below as whole-vector expressions (the code as it stood
before PR 38: float64 copies, boolean-indexed copies, difference
vectors); the block walk has to give the same numbers up to the order
of float64 additions, on rows with sent cells zeroed on either side,
with leaves that span blocks and blocks that span leaves, and hold no
D-vector of its own while it does. `reference_rounds` updates its
vectors in place and has to give the float32 values of the function
as it stood (`run_reference`), kept frozen here.
"""
from __future__ import annotations

import math
import os
import sys
import tracemalloc

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import reference as ref  # noqa: E402


# --------------------------------------------------------------------------
# what is meant: the whole-vector expressions


def _gap(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def plain_change_gap(program_w, reference_w, weights0, slices,
                     floor="rms"):
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


def plain_compare(program, reference, weights0, slices):
    out = {"loss_gap": max(_gap(p.loss, r.loss) for p, r in
                           zip(program.steps, reference.steps))}
    worst = worst_diff = 0.0
    for name, ref_row in reference.steps[0].state.items():
        row = np.asarray(program.steps[0].state[name], np.float32)
        both = (row != 0) & (ref_row != 0)
        if not both.any():
            worst = worst_diff = 1.0
            continue
        p64 = row[both].astype(np.float64)
        r64 = ref_row[both].astype(np.float64)
        b = float(np.linalg.norm(r64))
        shared = both.sum() / max(int((ref_row != 0).sum()), 1)
        few = 0.0 if shared > 0.5 else 1.0
        worst = max(worst, _gap(float(np.linalg.norm(p64)), b), few)
        worst_diff = max(worst_diff, float(np.linalg.norm(p64 - r64))
                         / max(b, 1e-30), few)
    out["first_grad_gap"] = worst
    out["first_grad_diff"] = worst_diff
    out["change_gap"] = plain_change_gap(
        program.weights, reference.weights, weights0, slices)
    out["upload_bytes_gap"] = max(
        abs(p.upload_bytes - r.upload_bytes)
        for p, r in zip(program.steps, reference.steps))
    return {k: float(v) if math.isfinite(v) else float("inf")
            for k, v in out.items()}


def plain_diagnostics(program, reference, weights0, slices):
    out = {"first_grad_diff": plain_compare(
               program, reference, weights0, slices)["first_grad_diff"],
           "change_gap_median_floor": plain_change_gap(
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


# --------------------------------------------------------------------------
# seeded readings

# leaves smaller than a block, one of several blocks, one cell long
LEAVES = (3, 200, 5, 7, 500, 1, 284)
D = sum(LEAVES)
ALL_LIMITS = {name: 0.0 for name in (
    "loss_gap", "first_grad_gap", "first_grad_diff", "change_gap",
    "upload_bytes_gap")}
CASES = ("dense", "zeroed_program", "zeroed_reference", "zeroed_both",
         "few", "disjoint", "nonfinite")


def slices_of(leaves):
    out, at = [], 0
    for i, n in enumerate(leaves):
        out.append((f"leaf{i}", at, at + n))
        at += n
    return out


def readings(case: str, d: int = D, seed: int = 38, first: bool = True):
    """(program, reference, weights0): two rows a side, the reference's
    a noisy copy of the program's, with the case's cells zeroed."""
    rng = np.random.RandomState(seed)

    def f32(x):
        return np.asarray(x, np.float32)

    weights0 = f32(rng.randn(d))
    rows_r = {f"momentum[{j}]": f32(rng.randn(d)) for j in range(2)}
    rows_p = {k: f32(v * (1 + 1e-3 * rng.randn(d)))
              for k, v in rows_r.items()}
    sent_p = rng.rand(d) < 0.3
    sent_r = sent_p ^ (rng.rand(d) < 0.05)
    row_p, row_r = rows_p["momentum[1]"], rows_r["momentum[1]"]
    if case in ("zeroed_program", "zeroed_both"):
        row_p[sent_p] = 0
    if case in ("zeroed_reference", "zeroed_both"):
        row_r[sent_r] = 0
    if case == "few":           # the program holds a third of the cells
        row_p[rng.rand(d) < 0.67] = 0
    if case == "disjoint":
        row_p[::2] = 0
        row_r[1::2] = 0
    if case == "nonfinite":
        row_p[d // 2] = np.inf
    # the first round moves a tenth of the coordinates, not the same
    # ones on the two sides; three rounds move them all a little
    moved_r = rng.rand(d) < 0.1
    moved_p = moved_r ^ (rng.rand(d) < 0.02)
    step = f32(1e-2 * rng.randn(d))
    first_r = f32(weights0 + step * moved_r)
    first_p = f32(weights0 + step * moved_p * 1.01)
    w_r = f32(first_r + 1e-3 * rng.randn(d))
    w_p = f32(w_r + 1e-5 * rng.randn(d))

    def side(rows, w, w_first, losses, up):
        steps = [ref.StepReadings(loss, up, rows if i == 0 else {})
                 for i, loss in enumerate(losses)]
        return ref.Readings(steps, w, w_first)

    program = side(rows_p, w_p, first_p if first else None,
                   (2.31, 2.2, 2.1001), 4000.0)
    reference = side(rows_r, w_r, first_r, (2.3, 2.2, 2.1), 4000.0)
    return program, reference, weights0


def rounds_of(reference):
    """The reference's side as `reference_rounds` hands it on: after
    each round its readings and its weights."""
    middle = (reference.weights_first + reference.weights) / 2
    return zip(reference.steps,
               (reference.weights_first, middle, reference.weights))


def sums_of(case: str, slices: list, block: int, **kw):
    """`take_sums` consumes the program's readings, so it gets a set
    of its own."""
    program, reference, weights0 = readings(case, **kw)
    return ref.take_sums(program, rounds_of(reference), weights0, slices,
                         block=block)


def values(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def assert_same(got: dict, want: dict) -> None:
    """To 1e-12 relative; a gap of norms is the difference of two
    nearly equal norms over one of them, so where it is small the
    norms' last digits show in it: to 1e-13 absolute (each is a share
    of the reference's norm)."""
    assert set(got) == set(want)
    for name, w in want.items():
        if math.isfinite(w):
            assert got[name] == pytest.approx(w, rel=1e-12, abs=1e-13), \
                name
        else:
            assert got[name] == w, name


def assert_sums_are_the_plain_sums(sums, program, reference, weights0,
                                   slices) -> None:
    """The float64 sums themselves, to 1e-12 relative."""
    close = dict(rel=1e-12, abs=0)
    for name, ref_row in reference.steps[0].state.items():
        row = program.steps[0].state[name]
        both = (row != 0) & (ref_row != 0)
        p64, r64 = row[both].astype(np.float64), ref_row[both].astype(
            np.float64)
        s = sums.rows[name]
        assert (s.shared, s.held) == (both.sum(), (ref_row != 0).sum())
        if not np.isfinite(p64).all():
            assert s.program_sq == float("inf")
            continue
        assert s.program_sq == pytest.approx(float(p64 @ p64), **close)
        assert s.reference_sq == pytest.approx(float(r64 @ r64), **close)
        assert s.diff_sq == pytest.approx(
            float((p64 - r64) @ (p64 - r64)), **close)
    dp = np.asarray(program.weights, np.float64) - weights0
    dr = np.asarray(reference.weights, np.float64) - weights0
    for i, (_, a, b) in enumerate(slices):
        assert sums.program_change_sq[i] == pytest.approx(
            float(dp[a:b] @ dp[a:b]), **close)
        assert sums.reference_change_sq[i] == pytest.approx(
            float(dr[a:b] @ dr[a:b]), **close)


@pytest.mark.parametrize("block", [7, 64, 4096])
@pytest.mark.parametrize("case", CASES)
def test_block_walk_gives_the_plain_numbers(case, block):
    """Every compared number and every diagnostic, for blocks shorter
    than most leaves, blocks that hold several leaves, and one block
    over everything."""
    program, reference, weights0 = readings(case)
    slices = slices_of(LEAVES)
    sums = sums_of(case, slices, block)
    assert_sums_are_the_plain_sums(sums, program, reference, weights0,
                                   slices)
    assert_same(values(ref.compare(sums, ALL_LIMITS)),
                plain_compare(program, reference, weights0, slices))
    assert_same(ref.diagnostics(sums),
                plain_diagnostics(program, reference, weights0, slices))
    assert ref.change_gap(sums, floor="median") == pytest.approx(
        plain_change_gap(program.weights, reference.weights, weights0,
                         slices, floor="median"), rel=1e-12)


def test_what_the_cases_read():
    """The cases are what their names say: rows that share no cell
    read 1.0, rows that share under half of the reference's cells read
    1.0, a cell that is not finite reads infinite, and rows with the
    sent cells zeroed on both sides are compared over the rest."""
    slices = slices_of(LEAVES)

    def read(case):
        return values(ref.compare(sums_of(case, slices, 64), ALL_LIMITS))

    assert read("disjoint")["first_grad_gap"] == 1.0
    assert read("disjoint")["first_grad_diff"] == 1.0
    assert read("few")["first_grad_gap"] == 1.0
    assert read("nonfinite")["first_grad_gap"] == float("inf")
    both = read("zeroed_both")
    assert 0 < both["first_grad_gap"] < both["first_grad_diff"] < 0.01
    assert both["loss_gap"] == pytest.approx(0.01 / 2.3)
    assert both["upload_bytes_gap"] == 0.0


def test_limits_choose_what_is_compared():
    sums = sums_of("dense", slices_of(LEAVES), ref.BLOCK, first=False)
    checks = ref.compare(sums, {"loss_gap": 0.1, "change_gap": 1e-9})
    assert set(checks) == {"loss_gap", "change_gap"}
    assert checks["loss_gap"]["limit"] == 0.1
    assert not ref.is_correct(checks)
    assert ref.is_correct({"loss_gap": checks["loss_gap"]})
    # the program's side kept no weights after the first round
    assert set(ref.diagnostics(sums)) == {"first_grad_diff",
                                          "change_gap_median_floor"}


def test_row_sums_of_a_row_shorter_than_the_weights():
    """A sketch's momentum rows are `num_cols` long, not D."""
    rng = np.random.RandomState(3)
    row = rng.randn(333).astype(np.float32)
    other = (row * 1.001).astype(np.float32)
    other[:50] = 0
    s = ref.row_sums(row, other, block=100)
    assert (s.shared, s.held) == (283, 283)
    assert s.reference_sq == pytest.approx(
        float(np.sum(other[50:].astype(np.float64) ** 2)), rel=1e-12)


# --------------------------------------------------------------------------
# memory: no D-vector of the comparison's own


def traced_peak(fn) -> int:
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        fn()
        return tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def test_comparison_allocates_less_than_one_vector():
    """At D = 2^22 in blocks of 2^16 the whole comparison allocates
    under one float32 D-vector; the whole-vector expressions take
    over eight (which also shows that the instrument sees numpy)."""
    d = 1 << 22
    program, reference, weights0 = readings("zeroed_both", d=d)
    mine = readings("zeroed_both", d=d)[0]
    rounds = list(rounds_of(reference))
    slices = slices_of((d // 2, 1 << 10, d // 2 - (1 << 10)))
    vector = 4 * d

    def blocks():
        sums = ref.take_sums(mine, iter(rounds), weights0, slices,
                             block=1 << 16)
        ref.compare(sums, ALL_LIMITS)
        ref.diagnostics(sums)

    def plain():
        plain_compare(program, reference, weights0, slices)
        plain_diagnostics(program, reference, weights0, slices)

    assert traced_peak(blocks) < vector
    assert traced_peak(plain) > 8 * vector


# --------------------------------------------------------------------------
# reference_rounds against the function as it stood


def frozen_run_reference(job, fns, weights0, feeds, lrs):
    """`reference.run_reference` before PR 38, line for line."""
    import jax.numpy as jnp

    w = np.array(weights0, np.float32)
    sketch = (ref.Sketch(job.d, job.num_cols, job.num_rows, job.hash_seed)
              if job.mode == "sketch" else None)
    if job.mode == "sketch":
        V = np.zeros((job.num_rows, job.num_cols), np.float32)
        E = np.zeros_like(V)
    else:
        V = np.zeros(job.d, np.float32)
        E = np.zeros(job.d, np.float32)
    c_err, c_vel = {}, {}
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
                sent = ref.top_k_dense(np.asarray(acc, np.float32), job.k)
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
                update = ref.top_k_dense(sketch.estimates(table), job.k)
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
        steps.append(ref.StepReadings(
            loss=float(np.mean(losses)),
            upload_bytes=float(job.upload_bytes_per_client
                               * len(client_ids)),
            state=state))
    return ref.Readings(steps, w, w_first)


def model_fns(d: int, seed: int = 7, whole_transfer: bool = False):
    """A plain model small enough to write here: each example pulls
    the weights towards its own target through a fixed random matrix,
    so clients, rounds and weights all change the gradient."""
    import jax
    import jax.numpy as jnp

    mix = jnp.asarray(np.random.RandomState(seed).randn(d, 16) / 4.0,
                      jnp.float32)

    @jax.jit
    def client_grad(vec, data, mask):
        (targets,) = data

        def loss_of(v):
            per = jnp.sum((jnp.tanh(v @ mix)[None, :] - targets) ** 2, -1)
            return jnp.sum(per * mask) / jnp.maximum(mask.sum(), 1.0)

        loss, g = jax.value_and_grad(loss_of)(vec)
        return g * mask.sum(), loss

    return ref.ModelFns(
        client_grad,
        (lambda x: np.asarray(x)) if whole_transfer else ref.to_host,
        jax.jit(lambda a, b: a + b))


def job_of(mode: str, d: int, weight_decay: float) -> ref.Job:
    return ref.Job(
        mode=mode, d=d, k=max(d // 20, 1), num_rows=5, num_cols=97,
        hash_seed=42, num_workers=3, num_clients=8,
        weight_decay=weight_decay, virtual_momentum=0.9,
        local_momentum=0.9 if mode == "local_topk" else 0.0,
        error_type={"sketch": "virtual", "local_topk": "local",
                    "uncompressed": "none"}[mode],
        upload_bytes_per_client=4 * d)


def feeds_of(d: int, rounds: int = 3, seed: int = 11):
    """Three clients a round of eight, so per-client rows come back;
    ragged masks, one client of the second round with one example."""
    rng = np.random.RandomState(seed)
    feeds = []
    for r in range(rounds):
        ids = rng.choice(8, 3, replace=False)
        data = (rng.randn(3, 4, 16).astype(np.float32),)
        mask = (rng.rand(3, 4) < 0.8).astype(np.float32)
        mask[:, 0] = 1.0
        if r == 1:
            mask[1, 1:] = 0.0
        feeds.append((ids, data, mask))
    return feeds, [0.1, 0.08, 0.05][:rounds]


def followed(rounds) -> ref.Readings:
    """All of `reference_rounds`, each round's vectors copied before
    the next is asked for (they are the reference's own)."""
    steps, weights = [], []
    for step, w in rounds:
        steps.append(step._replace(
            state={k: v.copy() for k, v in step.state.items()}))
        weights.append(w.copy())
    return ref.Readings(steps, weights[-1], weights[0])


@pytest.mark.parametrize("weight_decay", [0.0, 5e-4])
@pytest.mark.parametrize("mode", ["sketch", "local_topk", "uncompressed"])
def test_reference_rounds_give_the_frozen_functions_values(mode,
                                                           weight_decay):
    """Every float32 the reference hands on (losses, state rows, the
    weights after the first and the last round) equals the frozen
    function's. Equal as values: with a weight decay of 0 the frozen
    function adds a zero vector, which turns a negative zero into a
    positive one, and the comparison reads no zero's sign."""
    d = 401
    job = job_of(mode, d, weight_decay)
    feeds, lrs = feeds_of(d)
    weights0 = np.random.RandomState(5).randn(d).astype(np.float32) / 3.0
    start = weights0.copy()
    got = followed(ref.reference_rounds(job, model_fns(d), weights0,
                                        feeds, lrs))
    want = frozen_run_reference(job, model_fns(d, whole_transfer=True),
                                weights0, feeds, lrs)
    np.testing.assert_array_equal(weights0, start)    # not written to
    assert len(got.steps) == len(want.steps) == 3
    for g, w in zip(got.steps, want.steps):
        assert (g.loss, g.upload_bytes) == (w.loss, w.upload_bytes)
        assert list(g.state) == list(w.state)
        for name in w.state:
            assert g.state[name].dtype == np.float32
            np.testing.assert_array_equal(g.state[name], w.state[name])
    assert want.steps[0].state and not want.steps[1].state
    for g, w in ((got.weights, want.weights),
                 (got.weights_first, want.weights_first)):
        assert g.dtype == np.float32
        np.testing.assert_array_equal(g, w)
    assert np.count_nonzero(got.weights != weights0) >= job.k


@pytest.mark.parametrize("mode", ["sketch", "local_topk", "uncompressed"])
def test_following_the_reference_gives_the_plain_numbers(mode):
    """`take_sums` over the live reference, whose vectors change under
    it from round to round, against the whole-vector expressions over
    the frozen function's copies; the program's side is the frozen
    function with its rows and weights a little off."""
    d = 401
    job = job_of(mode, d, 5e-4)
    feeds, lrs = feeds_of(d)
    weights0 = np.random.RandomState(5).randn(d).astype(np.float32) / 3.0
    slices = slices_of((100, 1, 250, 50))
    rng = np.random.RandomState(9)

    def off(x):
        return (x * (1 + 1e-3 * rng.randn(*x.shape))).astype(np.float32)

    want = frozen_run_reference(job, model_fns(d, whole_transfer=True),
                                weights0, feeds, lrs)
    state = {k: off(v) for k, v in want.steps[0].state.items()}
    weights, first = off(want.weights), off(want.weights_first)

    def program():
        return ref.Readings(
            [ref.StepReadings(s.loss * 1.001, s.upload_bytes,
                              dict(state) if i == 0 else {})
             for i, s in enumerate(want.steps)], weights, first)

    mine = program()
    sums = ref.take_sums(mine, ref.reference_rounds(
        job, model_fns(d), weights0, feeds, lrs), weights0, slices,
        block=64)
    assert mine.weights is None and mine.weights_first is None
    assert mine.steps[0].state == {}          # let go, every one
    assert_same(values(ref.compare(sums, ALL_LIMITS)),
                plain_compare(program(), want, weights0, slices))
    assert_same(ref.diagnostics(sums),
                plain_diagnostics(program(), want, weights0, slices))


def test_to_host_in_pieces(monkeypatch):
    import jax.numpy as jnp

    x = jnp.arange(1000, dtype=jnp.float32) * 0.5
    whole = ref.to_host(x)
    monkeypatch.setattr(ref, "TRANSFER", 300)
    pieces = ref.to_host(x)
    np.testing.assert_array_equal(pieces, whole)
    np.testing.assert_array_equal(whole, np.asarray(x))
    assert pieces.flags.writeable and pieces.flags.owndata


def test_reference_rounds_hold_no_spare_vector():
    """Uncompressed at D = 2^20: beside its weights and its momentum
    the reference allocates the gradient's buffer and no more, and
    following it to the sums adds under a tenth of a vector."""
    d = 1 << 20
    job = job_of("uncompressed", d, 0.0)
    feeds, lrs = feeds_of(d, rounds=2)
    weights0 = np.zeros(d, np.float32)
    slices = slices_of((d // 2, d // 2))
    fns = model_fns(d)
    fns.client_grad(weights0, tuple(x[0] for x in feeds[0][1]),
                    feeds[0][2][0])      # compiled outside the count
    rows = {"momentum": np.ones(d, np.float32)}

    def follow():
        mine = ref.Readings(
            [ref.StepReadings(1.0, 0.0, dict(rows)),
             ref.StepReadings(1.0, 0.0, {})],
            np.ones(d, np.float32), np.ones(d, np.float32))
        ref.take_sums(mine, ref.reference_rounds(
            job, fns, weights0, feeds, lrs), weights0, slices,
            block=1 << 14)

    # the program's two vectors made in `follow`, then w, V, gradient
    assert traced_peak(follow) < (2 + 3.1) * 4 * d
