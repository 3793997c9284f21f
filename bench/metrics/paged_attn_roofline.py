"""Paged-attention kernel time against its roofline.

Per kernel call (one layer, one decode step) the bound is the larger of
bytes / HBM peak and FLOPs / bf16 peak, both counted by the architecture
module's ``paged_attn_cost`` (the K/V bytes of the live rows' true context
lengths, plus q and out); the context lengths are tracked host-side per
tick.  Summed over the traced ticks, divided by the summed device time of
the kernel's events in the programs that run the decode scan."""

from bench.lib import trace as T
from bench.lib.peaks import peaks_for

KERNEL = r"^_paged_decode_attention"
PROGRAMS = r"^jit_(tick|mixed)_paged"


def read(ctx):
    if ctx.trace is None or not ctx.ticks:
        return None
    itemsize = 2 if ctx.spec.dtype == "bfloat16" else 4
    steps = ctx.serve.tick_steps
    pk = None
    bound = kern = 0.0
    for t in ctx.ticks:
        if not t.decode:
            continue
        evs = T.select(ctx.trace.ops, KERNEL, PROGRAMS, t.a, t.b)
        if not evs:
            continue
        kern += T.total(evs) / ctx.trace.n_devices
        pk = pk or peaks_for(ctx.device_kind)
        for j in range(steps):
            ctxs = [plen + done + j + 1 for plen, done, _ in t.decode]
            fl, by = ctx.arch.paged_attn_cost(ctx.spec, ctxs, itemsize,
                                              itemsize)
            bound += ctx.spec.n_layers * max(fl / pk.bf16_flops,
                                             by / pk.hbm_bw)
    return 100.0 * bound / kern if kern > 0 else None
