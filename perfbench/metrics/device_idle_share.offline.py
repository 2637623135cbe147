"""Share of the traced window in which no operation ran on the device, in
percent; the breakdown charges each idle stretch to the host phase
(``schedule``, ``dispatch``, ``emit``) that covered it."""


def read(run):
    return None if run.reduced is None else 100.0 * run.reduced.idle_share
