"""Model: FLOPs of the work the window completed over what the chips
could do in it at their bf16 peak. The work is every output token read
back in the window (its decode step: 2 x matrix parameters plus attention
at its position) and, for each first token, the prefill of its prompt.
Counted from the configuration's widths, never from the program."""


def read(run):
    if run.peak is None:
        return None
    s = run.shape
    flops = 0
    for r in run.records:
        for k, t in enumerate(r.token_times):
            if not run.t0 <= t < run.t1:
                continue
            if k == 0:
                flops += s.prefill_flops(r.prompt_len)
            else:
                flops += 2 * s.matmul_params + s.attn_flops(r.prompt_len + k)
    if not flops:
        return None
    return 100.0 * flops / ((run.t1 - run.t0) * run.chips
                            * run.peak["bf16_flop_s"])
