"""Model FLOPs of every token the traced window computed (decode tokens and
prefill chunks, attention over their real contexts), over the window's
length times the chip's peak, in percent."""
from pb import work


def read(run):
    if run.reduced is None or not run.ticks:
        return None
    flops = 0.0
    for t in run.ticks:
        flops += work.decode_flops(run.shapes, t.decode_lens)
        flops += sum(work.prefill_flops(run.shapes, a, b, f)
                     for a, b, f in t.chunks)
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops"])
