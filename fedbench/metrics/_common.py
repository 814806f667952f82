"""Arithmetic shared by the per-layer readers. A reader is
`read(ctx) -> float | None`; `ctx` is what the harness's traced
window hands over: `rounds`, `window_s` (host clock), `stage_s` and
`api_s` per round (the benchmark's own spans), `valid_examples` per
round, `program_spans` (the program's TRACE records), `trace` (see
reduce.reduce_events), `peaks`, `chips`, `config`, `traffic`, `work`.
A reader that finds nothing to read returns None."""
from __future__ import annotations


def per_round_ms(seconds, ctx):
    if seconds is None or not ctx["rounds"]:
        return None
    return seconds / ctx["rounds"] * 1e3


def module_seconds(ctx, *needles):
    """Device seconds of the jitted modules whose name holds one of
    `needles`; None where the trace has none of them."""
    found = [s for name, s in ctx["trace"]["modules"].items()
             if any(n in name for n in needles)]
    return sum(found) if found else None
