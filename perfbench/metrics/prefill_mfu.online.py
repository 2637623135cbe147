"""Model FLOPs of the prefill chunks in the traced window over the device
time of their ``jit_paged_prefill_step`` calls times the chip's peak, in
percent."""
from pb import work


def read(run):
    calls = (run.reduced.programs if run.reduced else {}).get(
        "jit_paged_prefill_step")
    if not calls:
        return None
    flops = sum(work.prefill_flops(run.shapes, a, b, f)
                for t in run.ticks for a, b, f in t.chunks)
    return 100.0 * flops / (sum(calls) * run.peak["bf16_flops"])
