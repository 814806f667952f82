"""Share of its roofline the state-space scan reaches: the least time
the chip could take for what the scope `ssm_scan` has to do in a round
(`work/<config>.ssm_scan_work`: the larger of its FLOPs over the bf16
peak and its bytes over the memory bandwidth), over the scope's
measured device time, in percent."""
from fedbench.metrics._layers import layer_ms


def read(ctx):
    ms = layer_ms(ctx, "ssm_scan")
    work_of = getattr(ctx["work"], "ssm_scan_work", None)
    if not ms or work_of is None:
        return None
    t, c = ctx["traffic"], ctx["config"]
    positions = (t["num_workers"] * t["local_batch_size"]
                 * c["num_candidates"] * t["corpus"]["max_tokens"])
    work = work_of(c, positions)
    least_s = max(work["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                  work["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return least_s / (ms * 1e-3) * 100.0
