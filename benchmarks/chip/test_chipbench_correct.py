"""The comparison that decides ``correct``, on the CPU at a tiny size:
a sound run passes, the control (the fp8 reference in the program's
place) and each fault the cells can have fail: in greedy and in sampled
tokens, and on a mesh the exchange between chips left out."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import harness

HERE = Path(__file__).resolve().parent
SEED = 2**31 + 77


def test_sound_run_passes_and_the_fp8_control_fails():
    cell = tiny.cell()
    seen = harness.serve_once(cell, SEED, 2.0, jax.devices())
    r = harness.check(cell.cfg, SEED, seen["records"], control=True)
    assert r["greedy_gap"]["tokens"] >= 60
    assert r["sampled_gap"]["tokens"] >= 60
    compared, ok = harness.judge(cell.cfg, r, 0)
    assert ok and set(compared) == {"greedy_gap", "greedy_tokens",
                                    "sampled_gap", "sampled_tokens",
                                    "failed_requests"}
    assert not harness.judge(cell.cfg, r, 0, "control")[1]


def test_the_control_in_the_programs_place_is_not_correct():
    result = harness.run(tiny.cell(), SEED, 2.0, False, require_chip=False,
                         control=True)
    assert result["correct"] is False
    assert max(result["compared"]["greedy_gap"]["value"],
               result["compared"]["sampled_gap"]["value"]) > tiny.LIMIT


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.serving.engine import Engine
    apply = Engine._apply

    def altered(self, pending):
        (tok, done), reqs = pending
        tok = np.array(tok)
        for i, req in enumerate(reqs):
            if req is not None and tok[i] >= 0 and req.sampling.greedy:
                tok[i] = (tok[i] + 1) % self.cfg.vocab
                break
        return apply(self, ((tok, done), reqs))

    monkeypatch.setattr(Engine, "_apply", altered)
    result = harness.run(tiny.cell(), SEED, 2.0, False, require_chip=False)
    assert result["correct"] is False
    assert result["compared"]["greedy_gap"]["value"] > tiny.LIMIT


def _sampling_fault(kind):
    from repro.serving import engine
    draw = engine.sample_tokens

    def temperature(logits, keys, index, temp, top_k, top_p):
        return draw(logits, keys, index, temp * 1.25, top_k, top_p)

    def fold_in(logits, keys, index, temp, top_k, top_p):
        # every token of a request drawn with its first token's noise
        return draw(logits, keys, index * 0, temp, top_k, top_p)

    return {"temperature": temperature, "fold_in": fold_in}[kind]


@pytest.mark.parametrize("kind", ["temperature", "fold_in", "altered"])
def test_a_sampling_fault_fails(kind, monkeypatch):
    """A wrong temperature, a draw that leaves out the token's index, or a
    sampled token altered where it is produced."""
    from repro.serving import engine
    if kind == "altered":
        apply = engine.Engine._apply

        def altered(self, pending):
            (tok, done), reqs = pending
            tok = np.array(tok)
            for i, req in enumerate(reqs):
                if req is not None and tok[i] >= 0 \
                        and not req.sampling.greedy:
                    tok[i] = (tok[i] + 1) % self.cfg.vocab
                    break
            return apply(self, ((tok, done), reqs))

        monkeypatch.setattr(engine.Engine, "_apply", altered)
    else:
        monkeypatch.setattr(engine, "sample_tokens", _sampling_fault(kind))
    result = harness.run(tiny.cell(), SEED, 2.0, False, require_chip=False)
    assert result["correct"] is False
    assert result["compared"]["sampled_gap"]["value"] > tiny.LIMIT
    assert result["compared"]["greedy_gap"]["value"] <= tiny.LIMIT


MESH_RUN = """
import json, sys
sys.path[:0] = [{here!r}, {src!r}]
import jax.numpy as jnp
import chipbench_tiny as tiny
from chipbench import harness
from repro.sharding import tp
if {broken}:
    def local_only(o):
        # the all-gather of the heads left out: each chip's own heads
        # stand in for every chip's
        p = tp.current()
        return o if p is None or not p.heads else jnp.tile(o, (1, 1, p.model, 1))
    tp.gather_heads = local_only
r = harness.run(tiny.cell(mesh=[1, 4]), {seed}, 2.0, False,
                require_chip=False)
print(json.dumps(r))
"""


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "no_exchange"])
def test_the_exchange_between_chips_left_out_fails(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    code = MESH_RUN.format(here=str(HERE), src=str(HERE.parents[1] / "src"),
                           broken=broken, seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (not broken)
