"""Cache manager: prompt tokens served from the radix prefix cache
(``Request.prefix_hit_tokens``) over the prompt tokens admitted in the
window."""


def read(run):
    adm = [r for r in run.records
           if r.admitted is not None and run.t0 <= r.admitted < run.t1]
    total = sum(r.prompt_len for r in adm)
    return 100.0 * sum(r.prefix_hit_tokens for r in adm) / total \
        if total else None
