"""Mean host time per round inside FedModel.__call__, from the
program's own TRACE spans plan + stage + tier_motion + dispatch +
collect (journaled under --trace)."""
from fedbench.metrics._common import per_round_ms

SPANS = ("plan", "stage", "tier_motion", "dispatch", "collect")


def read(ctx):
    durs = [float(s["dur"]) for s in ctx["program_spans"]
            if s.get("name") in SPANS and "dur" in s]
    if not durs:
        return None
    rounds = {s.get("round") for s in ctx["program_spans"]
              if s.get("name") == "dispatch"}
    if not rounds:
        return None
    return sum(durs) / len(rounds) * 1e3
