#!/usr/bin/env python3
"""How the chip's compiler lays out the client state motion, asked
without a chip: the cohort-gather and scatter-back programs of
`federated/round.make_train_fn`, compiled for a described `v5e:2x2`
at the shapes of the benchmark's `resnet9_localtopk_state` cell.

    JAX_PLATFORMS=cpu python3 scripts/state_motion_layout.py \
        [--d 6568640] [--clients 100] [--workers 16] [--devices 1|4]

Per program it prints the bytes the compiler counts as accessed, its
temporary and argument sizes per device, what is aliased (the donated
block), the layout of the block operand, and how many `gather`,
`while` and collective instructions the program holds: what a reader
of a `state_motion_ms` line needs to know whether rows move as whole
tiles (PERF.md, section 6, PR 32 has this for parent and change).
A `while` is counted at one trip by the compiler's bytes, so a row
loop under one reads low by its trip count. Nothing runs: times come
from the chip only.
"""
from __future__ import annotations

import argparse
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

COUNTED = ("gather", "scatter", "while", "all-gather", "all-reduce",
           "reduce-scatter", "collective-permute", "all-to-all")


def describe(name: str, compiled) -> None:
    text = compiled.as_text()
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    ops = {k: len(re.findall(rf" {re.escape(k)}(?:-start)?\(", text))
           for k in COUNTED}
    # the block is the program's largest parameter
    params = re.findall(r"= (f32\[([\d,]+)\]\{[^}]*\}) parameter\(", text)
    block = max(params, default=("none", "0"), key=lambda p: math.prod(
        int(n) for n in p[1].split(",")))[0]
    print(f"{name}: bytes accessed {cost.get('bytes accessed', 0) / 1e9:.3f}"
          f" GB, temporary {mem.temp_size_in_bytes / 1e9:.3f} GB,"
          f" arguments {mem.argument_size_in_bytes / 1e9:.3f} GB,"
          f" aliased {mem.alias_size_in_bytes / 1e9:.3f} GB a device")
    print(f"  block operand {block}")
    print("  " + (", ".join(f"{k} {v}" for k, v in ops.items() if v)
                  or "no gather, scatter, while or collective"))


def compile_state_motion(devices, d: int, clients: int, workers: int):
    """(compiled gather, compiled scatter, the abstract ClientState)
    of the real round factory on a `clients` mesh of `devices`
    (described ones will do: nothing runs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated import round as fround

    mesh = Mesh(np.array(devices), ("clients",))
    cfg = Config(mode="local_topk", error_type="local",
                 local_momentum=0.9, k=50_000, weight_decay=0.0,
                 num_workers=workers, microbatch_size=-1,
                 grad_size=d, num_clients=clients, seed=0).validate()
    handle = fround.make_train_fn(lambda *a: None, lambda v: v, cfg, mesh)

    def shaped(tree, shardings):
        return jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                 sharding=s),
            tree, shardings)

    state = jax.eval_shape(lambda: fround.init_client_state(cfg, clients))
    state = shaped(state, jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        fround.client_state_specs(state),
        is_leaf=lambda x: isinstance(x, P)))
    ids = jax.ShapeDtypeStruct((workers,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    cohort = shaped(jax.eval_shape(handle.gather_fn, state, ids),
                    handle.cohort_shardings)
    return (handle.gather.lower(state, ids).compile(),
            handle.scatter.lower(state, ids, cohort).compile(), state)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--d", type=int, default=6_568_640)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    # an entry compiled for a described chip cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    gather, scatter, state = compile_state_motion(
        topo.devices[:args.devices], args.d, args.clients, args.workers)
    block = max(jax.tree.leaves(state), key=lambda l: l.size)
    print(f"D={args.d}, {args.clients} clients, {args.workers} a round,"
          f" {args.devices} device(s); a tracked block is"
          f" f32{list(block.shape)}, {block.size * 4 / 1e9:.3f} GB")
    describe("gather_cohort", gather)
    describe("scatter_back", scatter)
    return 0


if __name__ == "__main__":
    sys.exit(main())
