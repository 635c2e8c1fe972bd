"""The ``qwen3-8b-tp4`` configuration and the ``batch`` mix on the CPU:
the configuration cut to a test's size on a (1, 4) mesh of four forced
host devices, where a sound run passes and the fp8 control and a program
with its QK-norm left out fail; the cell as ``BENCHMARK.json`` resolves
it; and the ``collective_share`` reader on a hand-built trace."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, load_file, traffic
from chipbench.tracereduce import Span, Trace

HERE = Path(__file__).resolve().parent
SEED = 2**31 + 1501
# readings at these sizes (CPU, (1, 4) mesh, 4 seeds, 64 greedy tokens
# each): the program's widest gap 0.0 - 0.016, the fp8 control's
# 0.051 - 0.139, the program without its QK-norm 0.120 - 0.331
LIMIT = 0.03

BATCH = {"loop": "closed", "outstanding": 8, "requests": 64, "in_flight": 4,
         "prompt_len": {"median": 64, "sigma": 0.5, "min": 16, "max": 160},
         "output_len": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
         "temperature": 0.0}


def tiny_cell() -> harness.Cell:
    """``qwen3-8b-tp4`` cut to a test's size: 2 layers, d_model 128, 8
    query and 4 KV heads of 16, QK-norm on, vocab 512, 4 slots x 256."""
    cfg = json.loads((HERE / "configs" / "qwen3-8b-tp4.json").read_text())
    cfg.update(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
               num_attention_heads=8, num_key_value_heads=4, head_dim=16,
               vocab_size=512)
    cfg["serving"] = dict(cfg["serving"], slots=4, max_seq=256)
    cfg["correct"] = {"greedy_gap": {"limit": LIMIT, "min_tokens": 60,
                                     "max_requests": 8}}
    e2e = ["output_tok_s", "itl_p95_ms", "setup_s"]
    return harness.Cell(name="tiny-qwen3-8b-tp4.batch", chips=4, cfg=cfg,
                        mix=dict(BATCH), end_to_end=e2e, per_layer=[],
                        units={n: "u" for n in e2e})


RUN = """
import dataclasses, json, sys
sys.path[:0] = [{here!r}, {src!r}]
import test_chipbench_qwen3 as t
from chipbench import harness
from repro.models import layers
if {fault!r} == "no_qk_norm":
    qkv_proj = layers.qkv_proj
    def without(p, x, cfg):
        return qkv_proj(p, x, dataclasses.replace(cfg, qk_norm=False))
    layers.qkv_proj = without
r = harness.run(t.tiny_cell(), {seed}, 2.0, False, require_chip=False,
                control={fault!r} == "control")
print(json.dumps(r))
"""


@pytest.mark.parametrize("fault", ["sound", "control", "no_qk_norm"])
def test_a_sound_run_passes_and_the_control_and_a_missing_qk_norm_fail(
        fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    code = RUN.format(here=str(HERE), src=str(HERE.parents[1] / "src"),
                      fault=fault, seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert '"heads_tp": true' in out.stdout      # the plan shards heads
    assert set(result["compared"]) == {"greedy_gap", "greedy_tokens",
                                       "failed_requests"}
    assert result["compared"]["greedy_tokens"]["value"] >= 60
    assert result["correct"] is (fault == "sound")
    gap = result["compared"]["greedy_gap"]["value"]
    assert (gap <= LIMIT) is (fault == "sound")


def test_the_cell_as_the_benchmark_resolves_it():
    cell = harness.load_cell("qwen3-8b-tp4.batch")
    assert cell.chips == 4 == cell.cfg["serving"]["chips"]
    assert cell.end_to_end == ["output_tok_s", "itl_p95_ms", "setup_s"]
    assert "collective_share" in cell.per_layer
    assert "prefix_hit_share" not in cell.per_layer
    assert cell.mix["loop"] == "closed" and cell.mix["temperature"] == 0.0
    assert cell.cfg["reduced"] == [] and set(cell.cfg["correct"]) == {
        "greedy_gap"}
    # the longest prompt and output fill a slot exactly
    assert traffic.prompt_bounds(cell.mix)[1] \
        + traffic.output_bounds(cell.mix)[1] == cell.cfg["serving"]["max_seq"]
    plan = traffic.generate(cell.mix, 2**33 + 5, 51.0, cell.cfg["vocab_size"])
    assert len(plan.items) == 384 and len(plan.in_flight) == 16
    assert plan.outstanding == 2 * cell.cfg["serving"]["slots"]
    assert all(it.greedy for it in plan.items + plan.in_flight)
    assert all(len(it.prompt) + it.max_new <= 2048
               for it in plan.items + plan.in_flight)


def _trace():
    """Two chips, window [0, 3]: chip 0 has an all-gather that overlaps
    compute and an operation that reads an all-gather's result; chip 1 a
    collective-permute and an all-reduce cut by the window's end; both
    have collectives outside the window."""
    def op(name, kind, lo, hi, operand="%param.0"):
        return Span(f"%{name} = bf16[8]{{0}} {kind}(bf16[8]{{0}} {operand})",
                    lo, hi)
    chip0 = [op("fusion.1", "fusion", 0.0, 1.0),
             op("all-gather-start.1", "all-gather-start", 0.8, 1.2),
             op("fusion.2", "fusion", 1.5, 2.0),
             op("fusion.9", "fusion", 2.0, 2.2, "%all-gather-done.1"),
             op("all-gather-start.2", "all-gather-start", 5.0, 6.0)]
    chip1 = [op("fusion.1", "fusion", 0.0, 2.0),
             op("collective-permute-done.2", "collective-permute-done",
                2.5, 2.6),
             op("all-reduce.1", "all-reduce", 2.9, 3.5),
             op("all-gather-start.2", "all-gather-start", -1.0, -0.5)]
    return Trace(ops={0: chip0, 1: chip1}, modules={}, host=[])


def test_collective_share_on_a_hand_built_trace():
    read = load_file("metrics", "collective_share").read
    run = SimpleNamespace(trace=_trace(), trace_window=(0.0, 3.0))
    # chip 0: collectives 0.4 s of 1.9 s busy; chip 1: 0.2 of 2.2
    assert read(run) == pytest.approx(100 * (0.4 / 1.9 + 0.2 / 2.2) / 2)
    only = Trace(ops={0: [Span("%all-gather.3 = bf16[8]{0} all-gather()",
                               0.5, 1.0)]}, modules={}, host=[])
    assert read(SimpleNamespace(trace=only, trace_window=(0.0, 3.0))) \
        == pytest.approx(100.0)
    none = Trace(ops={0: [Span("%fusion.1 = bf16[8]{0} fusion("
                               "bf16[8]{0} %all-gather.3)", 0.0, 1.0)]},
                 modules={}, host=[])
    assert read(SimpleNamespace(trace=none, trace_window=(0.0, 3.0))) == 0.0
    assert read(SimpleNamespace(trace=None, trace_window=None)) is None
    idle = SimpleNamespace(trace=_trace(), trace_window=(10.0, 11.0))
    assert read(idle) is None
