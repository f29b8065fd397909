"""xfa_flash_attention (the training forward): least time each call's
causal work allows over the kernel's traced time."""
from bench import flops


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("xfa_flash_attention")
    if not k or not k["seconds"]:
        return None
    t, _ = flops.min_time(flops.flash_attn_cost(run.dims, run.batch,
                                                run.seq_len), run.peaks)
    return 100.0 * k["count"] * t / k["seconds"]
