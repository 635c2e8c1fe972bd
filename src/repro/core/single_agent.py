"""Single-agent baseline (paper §5.2, Table 3).

One agent, one shared context, same round budget R and the same tools —
but none of the role specialization. The paper diagnoses why this loses:

  "the slowdown of Kernel 1 under the single-agent setting was due to
   unrepresentative test inputs generated during test construction, which
   biased the profiling results."

We reproduce that failure *structurally*, not by nerfing the model:

  * Test construction: the single agent whips up ONE quick test case with
    whatever dims it reaches for first (a big power-of-two head_dim /
    hidden), instead of the testing agent's production-shape suite.
  * Profiling: reps=1, no warm-up discipline -> ~4% noise (the dedicated
    profiling agent runs the paper's 20 warm-ups + 100 reps -> ~0.4%).
  * Planning: no per-term roofline breakdown — it greedily walks a fixed
    transformation checklist and keeps any change that doesn't look worse
    than its own noisy single-rep measurement.

On simple kernels (K3) this is fine — matching the paper's observation
that SA ≈ MA there. On K1 the unrepresentative head_dim hides the cost of
a harmful 'neutral-looking' change, which the real evaluation suite then
exposes — the paper's 0.73×.
"""

from __future__ import annotations

import dataclasses
from collections import deque

from repro.core.agents import ProfilingAgent, Suggestion, TestingAgent
from repro.core.oplog import Log, LogEntry
from repro.kernels.registry import KernelSpace, get_space, make_inputs

# The single agent's quick-test dims: it grabs round numbers it has seen in
# model cards — unrepresentative of the serving shapes the kernels actually
# run on (paper Table 4). For Kernel 1 it confuses head_dim with a model's
# *hidden size* (4096) — production head dims are 128/256. At d=4096 the
# narrow-score side traffic is relatively tiny, so a harmful unfused-S_out
# change looks "within noise"; at real head dims it costs ~50% more HBM
# traffic. This is the paper's observed failure ("unrepresentative test
# inputs ... biased the profiling results"), reproduced mechanistically.
_QUICK_SHAPES = {
    "silu_and_mul": {"batch": 8, "hidden": 4096},
    "fused_add_rmsnorm": {"batch": 8, "hidden": 4096},
    "merge_attn_states_lse": {"seq": 256, "heads": 4, "head_dim": 4096},
    "flash_decode": {"batch": 1, "q_heads": 8, "kv_heads": 8,
                     "head_dim": 128, "seq": 1024},
}

# Fixed transformation checklist (no profile-driven targeting): intrinsics
# first (they're the famous tricks), then structure, then tiles.
_CHECKLIST = ("use_reciprocal", "use_rsqrt", "fast_exp", "fuse_s_out",
              "two_pass", "fused_split", "hoist", "mask_oob",
              "block_rows", "block_cols", "chunk")


def optimize_single_agent(kernel: str | KernelSpace, *, rounds: int = 5,
                          verbose: bool = False) -> Log:
    """Run the single-agent loop. Returns a Log comparable to Alg. 1's."""
    space = get_space(kernel) if isinstance(kernel, str) else kernel

    # The agent does its own test construction: one quick case.
    quick = [make_inputs(space.name, _QUICK_SHAPES[space.name], seed=7)]
    tester = TestingAgent()           # same tool access (validate only)
    profiler = ProfilingAgent(reps=1)  # sloppy single-rep measurements

    s_prev = space.baseline
    perf_prev = profiler.profile(space, s_prev, quick)
    log = Log()
    log.append(LogEntry(0, s_prev, True, perf_prev, rationale="baseline"))
    accepted_lat = perf_prev.geomean_latency_us

    knob_by_name = {k.name: k for k in space.knobs}
    todo = deque(n for n in _CHECKLIST if n in knob_by_name)
    for r in range(1, rounds + 1):
        if not todo:
            log.append(LogEntry(r, s_prev, True, perf_prev,
                                rationale="checklist exhausted; hold"))
            continue
        name = todo.popleft()
        knob = knob_by_name[name]
        if knob.kind == "bool":
            # the generalist just flips switches to see what happens — it
            # has no transformation catalog telling it the good direction
            value = not getattr(s_prev, name)
        else:
            value = min(knob.hi, knob.value(s_prev) * 2)
        sugg = Suggestion(name, value, f"checklist: try {name}={value}")
        s_new = space.mutate(s_prev, knob, value)
        pass_new, max_err = tester.validate(space, s_new, quick)
        perf_new = profiler.profile(space, s_new, quick)
        log.append(LogEntry(r, s_new, pass_new, perf_new,
                            rationale=sugg.rationale, max_err=max_err))
        # accept unless it looks clearly worse on the (noisy) quick test
        if pass_new and perf_new.geomean_latency_us <= accepted_lat * 1.05:
            s_prev, perf_prev = s_new, perf_new
            accepted_lat = perf_new.geomean_latency_us
        if verbose:
            print(f"[SA {space.name}] r{r} {sugg.rationale} -> "
                  f"{'kept' if s_prev is s_new else 'rejected'} "
                  f"({perf_new.geomean_latency_us:.2f}us)")

    # The single agent SHIPS its last accepted kernel — it has no
    # independent log review (that's the planning agent's job in MA).
    final = dataclasses.replace(s_prev, name=f"{space.name}_single_agent")
    log.entries[-1].code = final
    log.final_variant = final
    return log
