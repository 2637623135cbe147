"""Device idle milliseconds per tick while the host is in the engine's
``emit``: the logits' copy to the host and sampling there, after the
step's device work has ended (the traced window's idle time that the
reduction charges to the ``emit`` span, over the window's ticks)."""


def read(run):
    if run.reduced is None or not run.ticks:
        return None
    return run.reduced.idle_by_phase.get("emit", 0.0) / len(run.ticks) * 1e3
