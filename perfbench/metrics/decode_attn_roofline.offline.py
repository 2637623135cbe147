"""Share of its roofline that the paged decode attention kernel reached:
the least time the chip needs for the keys and values of the decoding
lanes' real contexts (and their FLOPs), over the device time of every
``flash_paged_decode`` op in the traced window, in percent."""
from pb import work


def read(run):
    if run.reduced is None:
        return None
    kernel_s = run.reduced.op_seconds(r"flash_paged_decode")
    if kernel_s <= 0:
        return None
    need = sum(work.roofline_s(*work.decode_attn(run.shapes, t.decode_lens),
                               run.peak) for t in run.ticks if t.decode_lens)
    return 100.0 * need / kernel_s
