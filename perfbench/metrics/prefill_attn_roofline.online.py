"""Share of its roofline that the paged prefill attention kernel reached:
the least time the chip needs for each chunk's causal attention against
its committed context, over the device time of every
``flash_paged_prefill`` op in the traced window, in percent."""
from pb import work


def read(run):
    if run.reduced is None:
        return None
    kernel_s = run.reduced.op_seconds(r"flash_paged_prefill")
    if kernel_s <= 0:
        return None
    need = sum(work.roofline_s(*work.prefill_attn(run.shapes, [(a, b)]),
                               run.peak)
               for t in run.ticks for a, b, _ in t.chunks)
    return 100.0 * need / kernel_s
