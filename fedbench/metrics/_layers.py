"""Device seconds of a layer the MODEL names inside the round's
`fwdbwd` scope (`attention_full`, `attention_window`, `moe_route`,
`expert_ffn`: `commefficient_tpu/scopes.py`).

`_scopes.scope_seconds` gives an op to the outermost `fed_` name on
its path, which for these is `fwdbwd`; here an op belongs to a layer
if that layer's name is a component anywhere on its path. A layer's
seconds are the union of its ops' intervals on the line "XLA Ops"
(a `while` op's event covers its body's), averaged over the devices.
A program that lays no such name (an older commit, another model)
gives None from every reader, never an error.
"""
from __future__ import annotations

import re

from fedbench import reduce as reducer
from fedbench.metrics import _scopes


def _layer_seconds(ctx, name: str):
    path = _scopes._xplane(ctx)
    if path is None:
        return None
    key = ("layer", path, name)
    if key not in _scopes._cache:
        inner = re.compile(r"(?:^|[/(])fed_" + re.escape(name)
                           + r"(?=[/):]|$)")
        planes = {k: p for k, p in _scopes._device_planes(path).items()
                  if p["lines"].get(reducer.OP_LINE)}
        total, found = 0.0, False
        for plane in planes.values():
            metas = {m for m, t in plane["tf_op"].items()
                     if inner.search(t)}
            found = found or bool(metas)
            total += reducer.union_seconds(
                [(t0, t1) for m, t0, t1 in plane["lines"][reducer.OP_LINE]
                 if m in metas]) / len(planes)
        _scopes._cache[key] = total if found else None
    return _scopes._cache[key]


def layer_ms(ctx, name: str):
    """ms a round of the ops under the model's layer `name`; None
    where the program lays no such name."""
    seconds = _layer_seconds(ctx, name)
    if seconds is None or not ctx["rounds"]:
        return None
    return seconds / ctx["rounds"] * 1e3
