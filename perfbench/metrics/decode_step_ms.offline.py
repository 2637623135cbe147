"""Device milliseconds per call of the decode step program
(``jit_paged_decode_step``) in the traced window."""


def read(run):
    calls = (run.reduced.programs if run.reduced else {}).get(
        "jit_paged_decode_step")
    return sum(calls) / len(calls) * 1e3 if calls else None
