"""Share of the prompt tokens admitted in the window that the prefix cache
served (the engine's per-request ``cached_tokens`` count), in percent."""


def read(run):
    adm = [s.req for s in run.sent if s.req.admit_t is not None
           and run.t_open <= s.req.admit_t <= run.t_close]
    total = sum(len(r.prompt) for r in adm)
    return 100.0 * sum(r.cached_tokens for r in adm) / total if total \
        else None
