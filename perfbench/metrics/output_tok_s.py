"""Output tokens per second of an offline cell: the tokens that the
window's ticks emitted, over the time from the start of its first tick to
the end of its last one (the host clock; each tick ends when its logits
reached the host)."""


def read(run):
    if not run.ticks:
        return None
    span = run.ticks[-1].t_end - run.ticks[0].t_start
    return sum(t.tokens for t in run.ticks) / span
