"""One run of one cell: set-up, the measured window, the check of what
the window produced, and the result line.

Set-up makes the weights on the device from the seed, builds the engine,
and warms every program the cell's traffic will use (each prefill bucket
its prompts span, the decode step, and the sampling programs where the
cell samples). The window then drives ``Engine.submit`` / ``Engine.step``
for ``seconds`` with the traffic's schedule and reads the engine from the
outside: each request's tokens as the host reads them back, and the
slots after each step. After the window the engine is freed and the
plain reference checks a sample of the served tokens.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from statistics import median

import numpy as np

from chipbench import correct, cost, load_file, traffic, tracereduce
from chipbench.window import Record

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench" / "trace"
DRAIN_CAP_S = 60.0


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """What one entry of ``BENCHMARK.json``'s workloads resolves to."""
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list      # metric names reported with --trace 0
    per_layer: list       # metric names reported with --trace 1
    units: dict


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in {bench_file.name}")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = traffic.load(BENCH_DIR / "traffic" / f"{wl['traffic']}.json")

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m["name"] for m in bench["end_to_end"] if applies(m)]
    per_layer = [m["name"] for m in bench["per_layer"] if applies(m)]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    return Cell(name=name, chips=wl["chips"], cfg=cfg, mix=mix,
                end_to_end=e2e, per_layer=per_layer, units=units)


def chip_devices(n: int):
    """The first ``n`` TPU devices, or ``NoChip``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r} "
                     f"({devs[0].device_kind}), not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX finds {len(devs)}")
    return devs[:n]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` in the checkout (a fixed path:
    it is part of what an entry is found by). Every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs JAX compiles or loads (from the cache or not)."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, t0, t1) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- the engine under load ---------------------------------------------------

class Load:
    """Drives an engine with a traffic plan and records what the host sees."""

    def __init__(self, engine, plan: traffic.Plan):
        from repro.serving import Request, SamplingParams
        self.engine, self.plan = engine, plan
        self._Request, self._Sampling = Request, SamplingParams
        self.records: list[Record] = []
        self.live: dict = {}
        self.steps: list = []      # (time, occupied slots, live kv lengths)
        self.next = 0

    def submit(self, item: traffic.Item, due: float) -> None:
        rec = Record(index=item.index, due=due, prompt_len=len(item.prompt),
                     max_new=item.max_new, greedy=item.greedy,
                     prompt=item.prompt, temperature=item.temperature,
                     sample_seed=item.sample_seed)
        req = self._Request(
            rid=item.index, prompt=item.prompt, max_new_tokens=item.max_new,
            sampling=self._Sampling(temperature=item.temperature,
                                    seed=item.sample_seed))
        with annotate("bench.submit"):
            rec.submitted = time.perf_counter()
            self.engine.submit(req)
        self.records.append(rec)
        self.live[item.index] = (req, rec)

    def send_next(self, due: float) -> None:
        """Send the schedule's next request, due at ``due``."""
        self.submit(self.plan.items[self.next], due)
        self.next += 1

    def step(self) -> None:
        eng = self.engine
        with annotate("bench.step"):
            stepped = eng.step()
        now = time.perf_counter()
        with annotate("bench.observe"):
            occ = [s for s in eng.slots if s.req is not None]
            if stepped and occ:
                self.steps.append((now, len(occ), [s.dpos for s in occ]))
            finished = 0
            for idx, (req, rec) in list(self.live.items()):
                n = len(req.out_tokens)
                if n > len(rec.token_times):
                    if not rec.token_times:
                        rec.admitted = now
                        rec.prefix_hit_tokens = req.prefix_hit_tokens
                    rec.token_times.extend([now] * (n - len(rec.token_times)))
                if req.done:
                    rec.finish_reason = req.finish_reason
                    rec.tokens = list(req.out_tokens)
                    rec.prefix_hit_tokens = req.prefix_hit_tokens
                    del self.live[idx]
                    finished += 1
        if self.plan.loop == "closed":
            for _ in range(finished):
                if self.next < len(self.plan.items):
                    self.send_next(now)

    def start(self) -> None:
        """Send the requests already in flight when the window opens and
        step until every one is admitted (part of set-up)."""
        now = time.perf_counter()
        for item in self.plan.in_flight:
            self.submit(item, now)
        while len(self.engine.scheduler) and self.engine.has_work():
            self.step()

    def window(self, seconds: float) -> tuple[float, float]:
        """Run the schedule for ``seconds``; returns the window's bounds."""
        items = self.plan.items
        t0 = time.perf_counter()
        end = t0 + seconds
        if self.plan.loop == "closed":
            for _ in range(max(0, self.plan.outstanding - len(self.live))):
                self.send_next(t0)
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if self.plan.loop == "open":
                while self.next < len(items) and \
                        t0 + items[self.next].due_s <= now:
                    self.send_next(t0 + items[self.next].due_s)
            if self.engine.has_work():
                self.step()
            elif self.plan.loop == "open" and self.next < len(items):
                with annotate("bench.wait"):
                    time.sleep(max(0.0, min(end, t0 + items[self.next].due_s)
                                   - time.perf_counter()))
            else:
                with annotate("bench.wait"):
                    time.sleep(max(0.0, end - time.perf_counter()))
        t1 = time.perf_counter()
        if self.plan.loop == "open":       # due in the window, not yet sent
            while self.next < len(items) and items[self.next].due_s < seconds:
                self.send_next(t0 + items[self.next].due_s)
        return t0, t1

    def snapshot(self) -> None:
        """Record the tokens served so far to requests still running."""
        for req, rec in self.live.values():
            rec.tokens = list(req.out_tokens)

    def drain(self, t0: float, t1: float, cap_s: float = DRAIN_CAP_S) -> float:
        """Step on until every request due in the window has its first
        token or has ended, for at most ``cap_s``; returns the time."""
        stop = time.perf_counter() + cap_s
        while time.perf_counter() < stop and self.engine.has_work() and any(
                not r.token_times and r.finish_reason is None
                and t0 <= r.due < t1 for r in self.records):
            self.step()
        return time.perf_counter()


def warm_up(engine, cfg: dict, mix: dict, rng) -> int:
    """Serve one short request per prefill bucket the mix's prompts span
    (the sampling programs first where the mix samples); returns the
    number of requests."""
    from repro.serving import Request, SamplingParams
    lo, hi = traffic.prompt_bounds(mix)
    max_seq = cfg["serving"]["max_seq"]
    lengths, b = [], 1
    while b < lo:
        b *= 2
    while True:
        lengths.append(max(lo, min(b, hi)))
        if b >= min(hi, max_seq):
            break
        b *= 2
    temp = float(mix.get("temperature", 0.0))
    vocab = cfg["vocab_size"]
    for i, n in enumerate(lengths):
        engine.submit(Request(
            rid=-1 - i, prompt=rng.integers(0, vocab, n, dtype=np.int32),
            max_new_tokens=2,
            sampling=SamplingParams(temperature=temp if i == 0 else 0.0,
                                    seed=i)))
    while engine.has_work():
        engine.step()
    engine.flush()
    return len(lengths)


# -- one run -----------------------------------------------------------------

@dataclasses.dataclass
class RunView:
    """What a metric reader reads."""
    cell: Cell
    records: list
    steps: list
    t0: float
    t1: float
    drain_end: float
    setup_s: float
    chips: int
    shape: cost.Shape
    peak: dict | None             # None off the chip: no share of a peak
    slots: int
    trace: object = None          # tracereduce.Trace, --trace 1 only
    trace_window: tuple = None    # (lo, hi) on the trace's clock


def read_metric(name: str, view: RunView):
    return load_file("metrics", name).read(view)


def model_module(cfg: dict):
    return load_file("models", cfg["model"])


def reference_module(cfg: dict):
    return load_file("models", f"{cfg['model']}_reference")


def make_mesh(cfg: dict, devices):
    shape = cfg["serving"].get("mesh")
    if not shape:
        return None
    from repro.launch.mesh import make_mesh as program_mesh
    n = int(np.prod(shape))
    return program_mesh(tuple(shape), ("data", "model"), devices=devices[:n])


def memory_peak(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return max(peaks) if peaks else 0


def serve_once(cell: Cell, seed: int, seconds: float, devices, *,
               trace: bool = False, t_start: float | None = None,
               counter: CompileCounter | None = None) -> dict:
    """Set up, run the window, drain, free; returns what was seen."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    cfg, mix = cell.cfg, cell.mix
    model = model_module(cfg)
    mesh = make_mesh(cfg, devices)
    params = model.make_weights(cfg, seed, mesh,
                                slots=cfg["serving"]["slots"])
    engine = model.make_engine(cfg, params, mesh)
    del params           # the engine holds what it serves
    rng = np.random.default_rng([seed, 1])
    n_warm = warm_up(engine, cfg, mix, rng)
    jax.block_until_ready(engine.cache)
    plan = traffic.generate(mix, seed, seconds, cfg["vocab_size"])
    load = Load(engine, plan)
    load.start()
    jax.block_until_ready(engine.cache)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with annotate("bench.window"):
        t0, t1 = load.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    drain_end = load.drain(t0, t1)
    load.snapshot()
    out = dict(records=load.records, steps=load.steps, t0=t0, t1=t1,
               drain_end=drain_end, setup_s=setup_s, n_warm=n_warm,
               slots=engine.n_slots,
               compiles=counter.between(t0, t1) if counter else None,
               memory_peak=memory_peak(devices),
               stats=engine.stats())
    del engine, load
    gc.collect()
    return out


GAPS = {"greedy_gap": True, "sampled_gap": False}   # name -> greedy


def check(cfg: dict, seed: int, records, *, control: bool = False) -> dict:
    """Readings of the comparison, for the greedy tokens and, where the
    run sampled, for the sampled ones: the served tokens' widest gap and,
    when asked, the control's."""
    ref_mod = reference_module(cfg)
    t = time.perf_counter()
    ref = ref_mod.Reference(cfg, seed)
    low = ref_mod.Reference(cfg, seed, "fp8") if control else None
    out = {}
    for i, (name, greedy) in enumerate(GAPS.items()):
        if not any(r.greedy == greedy for r in records):
            continue
        spec = cfg["correct"][name]
        sample = correct.select(records, np.random.default_rng([seed, 2 + i]),
                                spec["min_tokens"], spec["max_requests"],
                                greedy=greedy)
        gap, cgap, n = correct.widest_gap(sample, ref, low)
        out[name] = {"served": gap, "control": cgap, "tokens": n,
                     "requests": len(sample),
                     "longest": max((len(r.tokens) for r in sample),
                                    default=0)}
    out["seconds"] = time.perf_counter() - t
    return out


def judge(cfg: dict, readings: dict, failed: int,
          chooser: str = "served") -> tuple[dict, bool]:
    """The numbers compared, each with its limit, and whether all hold.
    ``chooser`` is ``"served"`` (the program's tokens) or ``"control"``
    (the lower-precision reference's first choices in their place)."""
    compared = {}
    for name in GAPS:
        if name in readings:
            spec = cfg["correct"][name]
            compared[name] = {"value": readings[name][chooser],
                              "limit": spec["limit"]}
            compared[name.replace("_gap", "_tokens")] = {
                "value": readings[name]["tokens"],
                "limit": spec["min_tokens"], "at_least": True}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    ok = all((c["value"] >= c["limit"]) if c.get("at_least")
             else (c["value"] <= c["limit"]) for c in compared.values())
    return compared, ok


def live_kv(steps, t0: float, t1: float) -> float:
    """Mean live KV rows (tokens) per decode dispatch in the window."""
    live = [sum(kv) for t, _, kv in steps if t0 <= t < t1]
    return sum(live) / len(live) if live else 0.0


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, t_start: float | None = None,
        control: bool = False) -> dict:
    """One run of ``cell``: returns the result line's object. Prints the
    run's other numbers on standard output and the compared numbers as the
    last lines of standard error. With ``control`` the lower-precision
    reference's choices stand in for the served tokens in the comparison
    (the control, which has to come out not correct)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    devices = chip_devices(cell.chips) if require_chip \
        else jax.devices()[:cell.chips]
    use_compile_cache()
    counter = CompileCounter()
    seen = serve_once(cell, seed, seconds, devices, trace=trace,
                      t_start=t_start, counter=counter)
    recs, t0, t1 = seen["records"], seen["t0"], seen["t1"]
    due = [r for r in recs if t0 <= r.due < t1]
    failed = sum(r.failed for r in due)
    readings = check(cell.cfg, seed, recs, control=control)
    compared, ok = judge(cell.cfg, readings, failed,
                         "control" if control else "served")

    kind = devices[0].device_kind
    view = RunView(cell=cell, records=recs, steps=seen["steps"], t0=t0, t1=t1,
                   drain_end=seen["drain_end"], setup_s=seen["setup_s"],
                   chips=cell.chips, shape=cost.Shape.of(cell.cfg),
                   peak=cost.peaks(kind) if require_chip else None,
                   slots=seen["slots"])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": seen["memory_peak"]}
    breakdown = None
    if trace:
        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        if files:
            view.trace = tracereduce.load(files[-1])
            lo, hi = view.trace.window()
            view.trace_window = (lo, hi)
            if view.trace.ops:
                busy = [tracereduce.busy_s(s, lo, hi)
                        for s in view.trace.ops.values()]
                device["busy_s"] = sum(busy) / len(busy)
                device["window_s"] = hi - lo
                breakdown = tracereduce.breakdown(view.trace, lo, hi)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        v = read_metric(name, view)
        if v is not None:
            metrics[name] = {"value": v, "unit": cell.units[name]}

    lateness = [r.submitted - r.due for r in due if r.submitted is not None]
    print(f"cell {cell.name} seed {seed}: {len(devices)} x {kind}; set-up "
          f"{seen['setup_s']:.3f} s ({seen['n_warm']} warm-up requests); "
          f"window {t1 - t0:.3f} s; {len(due)} requests due, "
          f"{sum(r.finish_reason == 'done' for r in due)} finished, "
          f"{failed} failed; {len(seen['steps'])} decode dispatches; "
          f"compiles in the window: {seen['compiles']}")
    if lateness:
        print(f"generator lateness: median {1e3 * median(lateness):.3f} ms, "
              f"max {1e3 * max(lateness):.3f} ms")
    live = live_kv(seen["steps"], t0, t1)
    pool = cell.cfg["serving"]["slots"] * cell.cfg["serving"]["max_seq"]
    per_token = view.shape.kv_bytes_per_token
    print(f"live KV: mean {live:.1f} tokens a decode step, "
          f"{live * per_token / 2**30:.4f} GiB of a pool of {pool} tokens, "
          f"{pool * per_token / 2**30:.4f} GiB")
    print(f"engine: {json.dumps(seen['stats'], default=str)}")
    print(f"check{' (control)' if control else ''}: {readings}")
    for name, c in compared.items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"compare {name}: {c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr)
    result = {"correct": bool(ok), "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in compared.items()}
    return result


def calibrate(cell: Cell, seeds, seconds: float, out_path=None) -> list:
    """Readings for the limits: for each seed, a full set-up and window,
    then the program's and the control's readings of the same window, each
    judged as ``run`` judges it, in one process."""
    import jax
    devices = chip_devices(cell.chips)
    use_compile_cache()
    rows = []
    for seed in seeds:
        seen = serve_once(cell, seed, seconds, devices)
        failed = sum(x.failed for x in seen["records"])
        r = check(cell.cfg, seed, seen["records"], control=True)
        r.update(seed=seed, setup_s=seen["setup_s"], failed=failed,
                 correct=judge(cell.cfg, r, failed)[1],
                 control_correct=judge(cell.cfg, r, failed, "control")[1])
        print(json.dumps(r), flush=True)
        rows.append(r)
        jax.clear_caches()
        gc.collect()
    if out_path:
        Path(out_path).write_text(json.dumps(rows, indent=1))
    return rows


def sweep(cell: Cell, seed: int, rates, seconds: float,
          lifetime_s: float, out_path=None) -> list:
    """The open-loop mix at each of ``rates`` on one set of weights, with
    ``rate x lifetime_s`` requests (at most the slots) in flight when the
    window opens: output rate, TTFT tail and the queue left at the
    window's end. Run once, to find the knee that a cell's rate is set
    from."""
    import jax
    from chipbench import window as w
    devices = chip_devices(cell.chips)
    use_compile_cache()
    cfg = cell.cfg
    model = model_module(cfg)
    params = model.make_weights(cfg, seed, make_mesh(cfg, devices),
                                slots=cfg["serving"]["slots"])
    out = []
    for rate in rates:
        engine = model.make_engine(cfg, params, make_mesh(cfg, devices))
        warm_up(engine, cfg, cell.mix, np.random.default_rng(seed))
        mix = dict(cell.mix, rate_per_s=rate, in_flight=min(
            cfg["serving"]["slots"], round(rate * lifetime_s)))
        load = Load(engine, traffic.generate(mix, seed, seconds,
                                             cfg["vocab_size"]))
        load.start()
        t0, t1 = load.window(seconds)
        waiting = len(engine.scheduler)
        end = load.drain(t0, t1)
        ttft = w.ttft_values(load.records, t0, t1)
        row = {"rate": rate, "output_tok_s": w.output_tok_s(load.records,
                                                            t0, t1),
               "ttft_p50_ms": w.tail_ms(ttft, 0.5, end),
               "ttft_p90_ms": w.tail_ms(ttft, 0.9, end),
               "itl_p95_ms": w.tail_ms(w.itl_values(load.records, t0, t1),
                                       0.95),
               "waiting_at_end": waiting, "due": len(ttft),
               "steps": len(load.steps),
               "occupancy": float(np.mean([s[1] for s in load.steps]))
               if load.steps else 0.0}
        print(json.dumps(row), flush=True)
        out.append(row)
        if out_path:
            Path(out_path).write_text(json.dumps(out, indent=1))
        del engine, load
        gc.collect()
    return out
