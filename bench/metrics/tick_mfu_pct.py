"""Model FLOPs of the tokens the traced ticks processed, over the device's
busy time inside their ``step_tick`` spans times the chip's bf16 peak.

Counted per token by the configuration's architecture module
(``decode_flops``, ``prefill_flops``, ``head_params``), whatever implements
it: the LM head only for each token that yields logits (every decoded
token, and a prompt's last token).  Padded slab rows and discarded decode
steps are not model work."""

from bench.lib import trace as T
from bench.lib.peaks import peaks_for


def tick_flops(arch, spec, t):
    f = 0.0
    for plen, done, kept in t.decode:
        ctx0 = plen + done          # the cache before this tick's steps
        ctx_sum = kept * ctx0 + kept * (kept + 1) // 2
        f += arch.decode_flops(spec, ctx_sum, kept)
    for start, n in t.chunk:
        f += arch.prefill_flops(spec, start, n, 0)
    for n in t.prefill:
        f += arch.prefill_flops(spec, 0, n, 0)
    # the first logits of every prompt that finished ingesting this tick
    # are the ones its first decoded token was read from
    firsts = sum(1 for _, done, _ in t.decode if done == 0)
    return f + 2.0 * firsts * arch.head_params(spec)


def read(ctx):
    if ctx.trace is None or not ctx.ticks or not ctx.trace.ops:
        return None
    busy = sum(T.busy(ctx.trace, t.a, t.b) for t in ctx.ticks)
    if busy <= 0:
        return None
    work = sum(tick_flops(ctx.arch, ctx.spec, t) for t in ctx.ticks)
    return 100.0 * work / (busy * peaks_for(ctx.device_kind).bf16_flops)
