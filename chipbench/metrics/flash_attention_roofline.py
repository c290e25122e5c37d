"""The flash attention kernel's share of its roofline, in %
(``kernels/attention/flash_attention.py``): the least time the window's
needed attention work could take, the larger of its operations over the
bf16 peak and its bytes over HBM bandwidth, over the summed device time
of the trace's ``flash_attention`` ops. The work comes from the
request-steps the window completed, real segments only."""


def read(run):
    tr = run["trace"]
    if tr is None or run["peak"] is None:
        return None
    t = tr["kernel_s"].get("flash_attention", 0.0)
    w = run["work"]
    if t <= 0 or not w["attn_flops"]:
        return None
    least = max(w["attn_flops"] / run["peak"]["bf16_flops"],
                w["attn_bytes"] / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / t
