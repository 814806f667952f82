"""Count-sketch for gradient compression, TPU-native.

Re-designs the capability the reference gets from the external `csvec`
package (CSVec: github.com/nikitaivkin/csh; used at reference
CommEfficient/fed_worker.py:312-320 and fed_aggregator.py:464-467,
584-595): an r x c count-sketch of a length-d vector supporting
linear accumulation, top-k heavy-hitter recovery, and L2 estimation.

TPU-first design. csvec hashes every coordinate independently, which
on an accelerator means r*d-element scatter (encode) and gather
(decode) through HBM — measured at ~600 ms per op for d=6.6M on a
v5e. Both are eliminated by choosing a hash family that vector
hardware can evaluate with contiguous memory ops only (~2-5 ms, i.e.
memory-bound optimal):

  * View the vector as B = ceil(d/c) contiguous chunks of length c.
    Row j's bucket hash is a random cyclic rotation per chunk:
        bucket_j(i) = ((i mod c) + offset[j, i // c]) mod c
    Encode row j = sum over chunks of rotate(sign * chunk): pure
    slices and adds. Decode-estimate inverts the rotations.
  * Signs factor as sign_j(i) = eps_j[i mod c] * delta_j[i // c] with
    eps ([r, c]) and delta ([r, B]) i.i.d. Rademacher drawn once from
    the seed. TPUs multiply floats far faster than they evaluate
    integer hash mixers (int multiplies are emulated), and the eps
    table is 4rc bytes regardless of d.
  * Validity: two coords in the same chunk never collide (same
    rotation — strictly better than the classic family). Coords in
    different chunks b != b' collide with probability exactly 1/c over
    the independent uniform offsets, and their sign product
    eps(p)eps(p')delta(b)delta(b') (or delta(b)delta(b') when p = p')
    has zero mean, so estimates are unbiased with variance
    <= ||v||^2/c per row; median-of-rows and heavy-hitter recovery
    guarantees carry over unchanged.
  * Everything is a pure function of (table, static geometry), so
    sketches are linear by construction: psum of worker tables over
    the client mesh axis == the sketch of the summed gradient. That
    linearity is the whole point of FetchSGD, and it is what lets the
    reference's lone NCCL reduce (fed_worker.py:138) become a single
    `lax.psum` here.
  * `num_blocks` (csvec's GPU-memory workaround) is accepted for API
    parity but cannot change results; chunking here is intrinsic
    (B = ceil(d/c)).

The sketch state is just a jnp array [r, c]; this class is a frozen
bundle of static geometry + sign/offset tables, safe to close over
under jit.

Performance notes (measured on TPU v5e, d=6.6M, c=500k, r=5 — see
PERF.md):
  * The rotation offsets are STATIC (numpy, fixed at construction), so
    encode/decode unroll into `jnp.roll` with compile-time shifts (two
    contiguous slices + concat each, fully fusible) instead of a
    `lax.scan` carrying traced offsets whose `dynamic_slice` of a
    doubled row defeats fusion. Measured: encode 4.3 ms -> 0.7 ms,
    full estimate 17 ms -> 4 ms. The scan path is kept as a fallback
    for very large r * n_chunks where unrolling would bloat compile
    time.
  * Heavy-hitter selection uses `jax.lax.approx_max_k` — the TPU-native
    partial-reduce top-k. On TPU it recovers ~95% (default
    recall_target) of the true top-k; missed coordinates are caught by
    error feedback on later rounds, the regime FetchSGD already
    operates in (sketch estimates are themselves approximate). On CPU
    (the test mesh) approx_max_k is exact, so golden tests see exact
    semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Unroll encode/decode over (row, chunk) pairs with static shifts up to
# this many pairs; beyond it, fall back to a lax.scan over chunks
# (bounded compile time, ~4x slower per element on TPU).
STATIC_UNROLL_LIMIT = 2048

# decode_topk_sparse may materialize the full [n_chunks, c] estimate
# (fast single approx_max_k select) only below this element count.
# The estimate is ~padded-d floats, so 256M elements = 1 GiB f32 (x2
# transient for the squared copy): GPT2-small's D=124M decodes on the
# fast path, while d = O(1e9) — where several d-sized f32 temporaries
# would crowd a 16 GiB HBM — falls back to the blockwise scan that
# keeps live memory at O(c) (SURVEY.md §7.3 hard part #1).
DECODE_MATERIALIZE_LIMIT = 256 * 1024 * 1024

# Above this d, decode_topk_dense selects heavy hitters by SAMPLED
# THRESHOLD instead of index top-k. Motivation (measured via
# _jax.approx_top_k_reduction_output_size): at GPT2-small geometry
# (d=124M, k=952k) the TPU ApproxTopK partial reduce only shrinks the
# input 4x before its exact sort — a 31M-element sort per decode. The
# threshold route estimates the k-th largest |estimate| from a ~1M
# strided sample (a cheap approx_max_k), then selects every coordinate
# >= that threshold with one elementwise mask: no large sort, no
# gather, no scatter. The selected count is k +- sampling noise (~1-2%
# at a 1M sample) rather than exactly k — the FetchSGD regime already
# treats k as a budget on approximate sketch estimates, and error
# feedback re-transmits anything a high threshold briefly excludes.
# Small geometries (all golden tests, the flagship CV bench) keep
# index top-k and its exact-k semantics. The gate is d-based, not
# backend-based, so a given geometry has one semantics everywhere
# (multihost bitwise-equality proofs compare like with like). The
# selection algorithm itself is ops/flat.py's sampled_threshold_mask
# (one shared implementation).
THRESHOLD_DECODE_MIN_D = 32 * 1024 * 1024


@dataclasses.dataclass(frozen=True, eq=False)
class CSVec:
    """Count-sketch geometry: d-dim vectors into an [r, c] table.

    API parity map with the reference's csvec.CSVec:
      encode(v)                  ~ CSVec(...).accumulateVec(v); .table
      (table arithmetic is just +)~ accumulateTable / zero()
      decode_topk(table, k)      ~ unSketch(k=k)
      l2estimate(table)          ~ l2estimate()
    """
    d: int
    c: int
    r: int
    num_blocks: int = 1   # accepted for parity; results are invariant
    seed: int = 42

    def __post_init__(self):
        rng = np.random.RandomState(self.seed)
        B = self.n_chunks
        object.__setattr__(
            self, "_offsets", rng.randint(0, self.c, size=(self.r, B))
            .astype(np.int32))
        object.__setattr__(
            self, "_eps",
            rng.choice([-1.0, 1.0], size=(self.r, self.c))
            .astype(np.float32))
        object.__setattr__(
            self, "_delta",
            rng.choice([-1.0, 1.0], size=(self.r, B)).astype(np.float32))

    # --- geometry helpers ------------------------------------------------
    @property
    def n_chunks(self) -> int:
        return -(-self.d // self.c)

    @property
    def _static_path(self) -> bool:
        return self.r * self.n_chunks <= STATIC_UNROLL_LIMIT

    @property
    def table_shape(self) -> Tuple[int, int]:
        return (self.r, self.c)

    def zeros(self) -> jax.Array:
        return jnp.zeros(self.table_shape, jnp.float32)

    def _rotate(self, row: jax.Array, shift) -> jax.Array:
        """out[p] = row[(p - shift) mod c]: two contiguous slices."""
        doubled = jnp.concatenate([row, row], axis=-1)
        return jax.lax.dynamic_slice_in_dim(
            doubled, self.c - shift, self.c, axis=-1)

    def _unrotate(self, row: jax.Array, shift) -> jax.Array:
        """out[p] = row[(p + shift) mod c] (inverse of _rotate)."""
        doubled = jnp.concatenate([row, row], axis=-1)
        return jax.lax.dynamic_slice_in_dim(doubled, shift, self.c, axis=-1)

    def _padded_chunks(self, vec: jax.Array) -> jax.Array:
        B = self.n_chunks
        pad = B * self.c - self.d
        if pad:
            vec = jnp.pad(vec, (0, pad))
        return vec.reshape(B, self.c)

    # --- hashing (for sparse / per-coordinate paths) ---------------------
    def hash_indices(self, idx: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Buckets [r, n] (int32 in [0, c)) and signs [r, n] (+-1 f32)
        for an int32 index array [n]. Out-of-range indices get an
        arbitrary valid bucket (callers mask their values)."""
        safe = jnp.clip(idx, 0, self.d - 1)
        b = (safe // self.c).astype(jnp.int32)             # chunk [n]
        p = (safe % self.c).astype(jnp.int32)              # position [n]
        off = jnp.asarray(self._offsets)[:, b]             # [r, n]
        buckets = (p[None, :] + off) % self.c
        signs = jnp.asarray(self._eps)[:, p] * jnp.asarray(self._delta)[:, b]
        return buckets.astype(jnp.int32), signs

    # --- encode ----------------------------------------------------------
    def encode(self, vec: jax.Array) -> jax.Array:
        """Sketch a dense [d] vector into an [r, c] table: one
        multiply + rotate + add per (row, chunk), all contiguous.

        Static-offset unroll (shifts known at trace time -> `jnp.roll`
        lowers to fusible static slices; see module perf notes); scan
        fallback above STATIC_UNROLL_LIMIT."""
        chunks = self._padded_chunks(vec)                  # [B, c]
        eps = jnp.asarray(self._eps)                       # [r, c]

        if self._static_path:
            rows = []
            for j in range(self.r):
                acc = jnp.zeros_like(vec, shape=(self.c,))
                for b in range(self.n_chunks):
                    acc = acc + (jnp.roll(eps[j] * chunks[b],
                                          int(self._offsets[j, b]))
                                 * float(self._delta[j, b]))
                rows.append(acc)
            return jnp.stack(rows)

        def body(table, xs):
            chunk, off_b, delta_b = xs                     # [c], [r], [r]
            signed = eps * chunk[None, :] * delta_b[:, None]   # [r, c]
            rows = [self._rotate(signed[j], off_b[j]) for j in range(self.r)]
            return table + jnp.stack(rows), None

        init = jnp.zeros_like(vec, shape=self.table_shape)
        table, _ = jax.lax.scan(
            body, init,
            (chunks, jnp.asarray(self._offsets).T,
             jnp.asarray(self._delta).T))
        return table

    def encode_sparse(self, indices: jax.Array, values: jax.Array) -> jax.Array:
        """Sketch a sparse vector given as (indices [n], values [n]).
        Out-of-range indices (e.g. i >= d padding) are dropped. Used by
        the server's sketched error-feedback step, which re-sketches the
        k-sparse recovered update (reference fed_aggregator.py:593-595)
        — an O(k) scatter instead of an O(d) re-encode."""
        buckets, signs = self.hash_indices(indices.astype(jnp.int32))
        valid = ((indices >= 0) & (indices < self.d)).astype(jnp.float32)
        vals = values * valid
        row_ids = jnp.repeat(
            jnp.arange(self.r, dtype=jnp.int32), indices.shape[0])
        return self.zeros().at[
            row_ids, buckets.reshape(-1)
        ].add((signs * vals[None, :]).reshape(-1))

    def encode_k_sparse(self, indices: jax.Array, values: jax.Array,
                        dense: Optional[jax.Array] = None) -> jax.Array:
        """Sketch a k-sparse vector, choosing the faster of the two
        mathematically identical routes (linearity — their equality is
        asserted by tests/test_sketch.py):

          * `encode_sparse`: O(r*k) scatter-add. Cheap everywhere when
            k is small, and on CPU backends at any k.
          * dense `encode(dense)`: O(r*d) contiguous rotations. TPU
            scatter throughput is orders of magnitude below streaming
            bandwidth, so past ~1M scattered elements (GPT2-small's
            server re-sketch: r*k = 4.8M) the dense route wins.

        `dense` is the already-materialized dense form of the sparse
        vector, if the caller has one in hand (the server's
        error-feedback step does); without it the dense route pays one
        extra O(k) scatter to build it.

        BACKEND-DISPATCH CAVEAT: unlike this module's other route
        gates (THRESHOLD_DECODE_MIN_D, DECODE_MATERIALIZE_LIMIT),
        which are d-based so a geometry has ONE semantics everywhere,
        this gate consults `jax.default_backend()` at TRACE time. The
        two routes are mathematically identical by sketch linearity,
        but floating-point summation ORDER differs (scatter-add
        accumulation vs. dense rotation reduction), so at large r*k a
        CPU trace and a TPU trace of the same geometry can produce
        sketch tables differing in final-ulp rounding. Cross-backend
        bitwise-equality comparisons (e.g. a CPU golden against a TPU
        run) must therefore pin the route — pass `dense` explicitly or
        compare within one backend; same-backend runs (all tests, all
        multihost bit-equality proofs) are unaffected because the
        dispatch is deterministic per backend."""
        use_dense = (self.r * int(indices.shape[0]) > 1_000_000
                     and jax.default_backend() != "cpu")
        if not use_dense:
            return self.encode_sparse(indices, values)
        if dense is None:
            dense = jnp.zeros(self.d, jnp.float32).at[indices].set(
                values, mode="drop")
        return self.encode(dense)

    # --- decode ----------------------------------------------------------
    def estimate(self, table: jax.Array, idx: jax.Array) -> jax.Array:
        """Median-of-rows unbiased estimates of coordinates `idx` [n]."""
        buckets, signs = self.hash_indices(idx.astype(jnp.int32))
        ests = signs * table[jnp.arange(self.r)[:, None], buckets]  # [r, n]
        return jnp.median(ests, axis=0)

    def estimate_all(self, table: jax.Array) -> jax.Array:
        """[B, c] median-of-rows estimates for every coordinate
        (flattened [: d] is the full estimate vector): r inverse
        rotations + sign correction per chunk, no gathers. Static
        unroll when small enough (module perf notes), a scan over
        chunks above STATIC_UNROLL_LIMIT. The padding tail (coords
        >= d) is NOT zeroed here: callers do (_flat_estimates)."""
        eps = jnp.asarray(self._eps)

        if self._static_path:
            delta = jnp.asarray(self._delta)
            ests = []
            for b in range(self.n_chunks):
                rows = jnp.stack(
                    [jnp.roll(table[j], -int(self._offsets[j, b]))
                     for j in range(self.r)])
                ests.append(jnp.median(
                    rows * eps * delta[:, b][:, None], axis=0))
            return jnp.stack(ests)                            # [B, c]

        def body(_, xs):
            off_b, delta_b = xs
            rows = [self._unrotate(table[j], off_b[j])
                    for j in range(self.r)]
            ests = jnp.stack(rows) * eps * delta_b[:, None]     # [r, c]
            return None, jnp.median(ests, axis=0)

        _, est = jax.lax.scan(
            body, None,
            (jnp.asarray(self._offsets).T, jnp.asarray(self._delta).T))
        return est                                            # [B, c]

    def _flat_estimates(self, table: jax.Array) -> jax.Array:
        """Materialized [padded] estimate vector with the padding tail
        (coords >= d) zeroed — the shared prologue of both
        materialize-path decode routes."""
        flat = self.estimate_all(table).reshape(-1)
        if self.n_chunks * self.c != self.d:
            iota = jnp.arange(flat.shape[0], dtype=jnp.int32)
            flat = jnp.where(iota < self.d, flat, 0.0)
        return flat

    def decode_topk(self, table: jax.Array, k: int) -> jax.Array:
        """Dense [d] vector holding the k largest-magnitude estimated
        coordinates (reference csvec unSketch(k))."""
        sparse_idx, sparse_vals = self.decode_topk_sparse(table, k)
        dense = jnp.zeros(self.d, jnp.float32)
        return dense.at[sparse_idx].set(sparse_vals, mode="drop")

    @property
    def _threshold_decode(self) -> bool:
        """Whether decode_topk_dense uses the sampled-threshold route
        (see THRESHOLD_DECODE_MIN_D). Requires the materialized-
        estimate path; beyond DECODE_MATERIALIZE_LIMIT the blockwise
        sparse decode stays the only option."""
        padded = self.n_chunks * self.c
        return (self.d > THRESHOLD_DECODE_MIN_D
                and padded <= DECODE_MATERIALIZE_LIMIT)

    def decode_topk_dense(self, table: jax.Array, k: int) -> jax.Array:
        """decode_topk for callers that only need the DENSE update
        (the server's error-feedback step): at large d takes the
        sampled-threshold route — one approx_max_k over a ~1M sample
        plus one elementwise mask, instead of an index top-k whose TPU
        partial-reduce sort grows with k*d — otherwise identical to
        decode_topk."""
        if not self._threshold_decode:
            return self.decode_topk(table, k)

        from commefficient_tpu.ops.flat import sampled_threshold_mask
        # the padding tail of _flat_estimates is already zeroed, which
        # is exactly the contract sampled_threshold_mask needs
        flat = self._flat_estimates(table)
        return sampled_threshold_mask(flat, min(k, self.d))[: self.d]

    def decode_topk_sparse(
        self, table: jax.Array, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        """(indices [k], values [k]) of the top-k estimates. Unfilled
        slots carry index d (out of range; dropped by `mode='drop'`
        scatters downstream)."""
        k = min(k, self.d)
        kc = min(k, self.c)
        eps = jnp.asarray(self._eps)

        if self._static_path and self.n_chunks * self.c <= DECODE_MATERIALIZE_LIMIT:
            # materialize the full [B, c] estimate (28 MB at the
            # flagship geometry) and select once with the TPU-native
            # approx_max_k partial reduce (module perf notes).
            flat = self._flat_estimates(table)
            _, idx = jax.lax.approx_max_k(flat * flat, k)
            vals = flat[idx]
            idx = jnp.where(vals == 0.0, self.d, idx)
            return idx.astype(jnp.int32), vals

        # blockwise fallback: per chunk keep the top-min(k, c)
        # candidates (a chunk holds at most c coords, so this loses
        # nothing), then one final top-k over the B * kc survivors.
        # Never materializes all d estimates at once (SURVEY.md §7.3
        # hard part #1: d = O(1e8) must stay bounded).
        def body(_, xs):
            off_b, delta_b, b = xs
            rows = [self._unrotate(table[j], off_b[j])
                    for j in range(self.r)]
            est = jnp.median(jnp.stack(rows) * eps * delta_b[:, None],
                             axis=0)                          # [c]
            i_global = b * self.c + jnp.arange(self.c, dtype=jnp.int32)
            est = jnp.where(i_global < self.d, est, 0.0)
            _, sel = jax.lax.approx_max_k(est * est, kc)
            return None, (i_global[sel], est[sel])

        _, (cand_idx, cand_vals) = jax.lax.scan(
            body, None,
            (jnp.asarray(self._offsets).T, jnp.asarray(self._delta).T,
             jnp.arange(self.n_chunks, dtype=jnp.int32)))
        cand_idx = cand_idx.reshape(-1)                       # [B * kc]
        cand_vals = cand_vals.reshape(-1)
        _, sel = jax.lax.approx_max_k(cand_vals * cand_vals, k)
        idx, vals = cand_idx[sel], cand_vals[sel]
        # slots holding a zero estimate are "unfilled": report index d
        # so downstream drop-mode scatters ignore them
        idx = jnp.where(vals == 0.0, self.d, idx)
        return idx.astype(jnp.int32), vals

    # --- norms -----------------------------------------------------------
    def l2estimate(self, table: jax.Array) -> jax.Array:
        """Estimated L2 norm of the sketched vector: median over rows of
        per-row L2 (csvec l2estimate; used for clipping sketches at
        reference utils.py:307-309)."""
        return jnp.sqrt(jnp.median(jnp.sum(table * table, axis=1)))
