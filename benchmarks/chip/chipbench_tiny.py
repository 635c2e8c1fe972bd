"""Tiny cells for the CPU tests: the benchmark's code paths at sizes a
test run holds, with the program on the CPU."""

import json
from pathlib import Path

from chipbench import harness

HERE = Path(__file__).resolve().parent
# readings at these sizes (CPU, 4 seeds): the program's widest gap
# 0.0 - 0.019, the fp8 control's 0.095 - 0.130
LIMIT = 0.05

CHAT = {"loop": "open", "rate_per_s": 6.0,
        "prompt_len": {"median": 24, "sigma": 1.0, "min": 8, "max": 64},
        "output_len": {"median": 12, "sigma": 0.7, "min": 4, "max": 32},
        "temperature": 0.8, "greedy_share": 0.5}


def cell(mesh=None) -> harness.Cell:
    """The ``qwen2-0.5b`` configuration cut to a test's size, on one
    device or on a ``mesh`` of four."""
    cfg = json.loads((HERE / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
               vocab_size=512, num_attention_heads=4,
               num_key_value_heads=4 if mesh else 2)
    cfg["serving"] = dict(cfg["serving"], slots=4, max_seq=256, mesh=mesh,
                          chips=4 if mesh else 1)
    cfg["correct"] = {name: {"limit": LIMIT, "min_tokens": 60,
                             "max_requests": 8}
                      for name in ("greedy_gap", "sampled_gap")}
    e2e = ["output_tok_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"]
    return harness.Cell(name="tiny-qwen2-0.5b.chat", chips=4 if mesh else 1,
                        cfg=cfg, mix=dict(CHAT), end_to_end=e2e,
                        per_layer=["slot_occupancy", "prefix_hit_share",
                                   "mfu"],
                        units={n: "u" for n in e2e + [
                            "slot_occupancy", "prefix_hit_share", "mfu"]})
