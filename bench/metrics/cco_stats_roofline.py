"""The phase-1 statistics kernel's (kernels/cco_stats.py) share of its
roofline: for each call, the larger of its FLOPs over the peak FLOP/s and
its HBM bytes over the peak bandwidth, summed over the calls in the
traced window, over the kernel's device time there."""
from bench import costs

KERNEL = "cco_stats_pallas"   # the kernel's op name in the trace


def is_kernel(name):
    return name.startswith(KERNEL)


def read(ctx):
    red = ctx["reduction"]
    secs = red.op_seconds(is_kernel)
    calls = red.op_calls(is_kernel)
    if secs <= 0 or not calls or not ctx["peaks"]:
        return None
    spec = ctx["spec"]
    tr, model = spec["traffic"], spec["model"]
    n = tr["clients_per_round"] * tr["partition"]["samples_per_client"]
    flops, hbm = costs.cco_stats_cost(n, model["proj_dims"][-1])
    # the kernel computes the d x d moment only; the four vector moments
    # are jnp reductions outside it
    d = model["proj_dims"][-1]
    flops -= 6.0 * n * d
    hbm -= 4 * d * costs.F32
    peaks = ctx["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                hbm / peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / secs
