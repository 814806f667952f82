"""Varying-axes bookkeeping for `pallas_call` inside `shard_map`.

Under `shard_map`'s `check_vma` every value carries the set of manual
mesh axes it varies over. A `pallas_call` does not infer that set for
its outputs (a bare `ShapeDtypeStruct` is rejected), and its body —
traced op by op under the interpreter — refuses to mix operands whose
sets differ (a replicated hash table times a per-client chunk). Both
are settled before the call: lift every operand to the union of the
sets and stamp that union on the outputs. Outside `shard_map` the
union is empty and nothing changes.
"""
from __future__ import annotations

import jax


def vary_together(*operands):
    """(vma, operands) with every operand pcast to the union `vma` of
    their varying-axes sets."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))

    def lift(x):
        missing = tuple(sorted(vma - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return vma, tuple(lift(x) for x in operands)
