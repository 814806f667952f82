"""Device ms a round of the client forward/backward that no inner
name covers: the ops whose outermost layer scope is `fwdbwd`
(`client_fwdbwd_ms`'s ops) and whose path carries none of the ten
names in `INNER`, the union of their intervals on the line "XLA Ops",
averaged over the devices.

A container op (a `while` or a `conditional`) is left out: its event
covers its body's ops, which count under their own names. The names
are fixed here, not read from the program, so that what the metric
means does not move with a later program. A program that lays none of
`NEW` (one from before they were laid) gives None.

Besides the number, the ten longest instructions that it counts are
printed to standard error, ms a round each: what the next name would
have to cover.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict

from fedbench import reduce as reducer
from fedbench.metrics import _scopes

INNER = ("attention_full", "attention_window", "moe_route", "expert_ffn",
         "shared_expert", "attn_proj", "dense_mlp", "norm", "head_loss",
         "unravel")
NEW = ("attn_proj", "dense_mlp", "norm", "head_loss", "unravel")
CONTAINERS = ("while", "conditional")


def _component(names) -> re.Pattern:
    return re.compile(r"(?:^|[/(])fed_(?:" + "|".join(names)
                      + r")(?=[/):]|$)")


_INNER = _component(INNER)
_NEW = _component(NEW)
# the opcode of an HLO line: the first word after " = " that opens a
# parenthesis (a tuple's type opens one after a space, a layout's
# tiling `T(8,128)` after a capital)
_OPCODE = re.compile(r"(?<![\w.-])([a-z][\w-]*)\(")


def is_container(hlo: str) -> bool:
    """Whether a TPU op event's name, its HLO line ('%while.3 = (...)
    while(...), body=...'), is a `while` or a `conditional`."""
    m = _OPCODE.search(hlo.partition(" = ")[2])
    return bool(m) and m.group(1) in CONTAINERS


def _other(planes: dict) -> dict | None:
    planes = {k: p for k, p in planes.items()
              if p["lines"].get(reducer.OP_LINE)}
    if not any(_NEW.search(t) for p in planes.values()
               for t in p["tf_op"].values()):
        return None
    total = 0.0
    by_op = defaultdict(float)
    for plane in planes.values():
        metas = {m for m, t in plane["tf_op"].items()
                 if _scopes.scope_of(t) == "fwdbwd"
                 and not _INNER.search(t)
                 and not is_container(plane["names"].get(m, ""))}
        iv = []
        for m, t0, t1 in plane["lines"][reducer.OP_LINE]:
            if m in metas:
                iv.append((t0, t1))
                by_op[reducer.short_op(plane["names"].get(m, str(m)))] \
                    += (t1 - t0) / len(planes)
        total += reducer.union_seconds(iv) / len(planes)
    return {"seconds": total, "by_op": dict(by_op)}


def read(ctx):
    path = _scopes._xplane(ctx)
    if path is None or not ctx["rounds"]:
        return None
    key = ("fwdbwd_other", path)
    if key not in _scopes._cache:
        found = _other(_scopes._device_planes(path))
        _scopes._cache[key] = found
        if found is not None:
            m = ctx["rounds"]
            top = sorted(found["by_op"].items(), key=lambda kv: -kv[1])[:10]
            _scopes.say("[fedbench] fwdbwd under no inner name, longest "
                        "instructions, ms a round: " + json.dumps(
                            [[k, round(v / m * 1e3, 3)] for k, v in top]))
    found = _scopes._cache[key]
    if found is None:
        return None
    return found["seconds"] / ctx["rounds"] * 1e3
