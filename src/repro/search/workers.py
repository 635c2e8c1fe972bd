"""Sandboxed evaluation workers — crash isolation for the search hot path.

Candidate kernels are exactly the code you must assume will hang, segfault,
or OOM: the coding agent writes them, the testing agent runs them. In the
thread-pool path one pathological genome wedges or kills the whole search.
This module moves the expensive tier-1/2 work (profile + oracle +
interpret-mode validation) into **spawn-mode worker processes** so the
blast radius of a broken candidate is one child process:

  deadline    ``conn.poll(deadline_s)`` in the parent; an over-deadline
              worker is shot (``kill``) and respawned — a wedged
              evaluation can never hang the search.
  retry       Infra faults (worker died, deadline, corrupt payload) are
              retried with exponential backoff. A fault is *never* raised
              to the caller.
  quarantine  A genome that faults ``quarantine_after`` times is written
              off: the pool reports it and the evaluator records a final
              ``finish_reason="crashed"`` verdict in the cache — mirroring
              the serving layer's request lifecycle — so it is never
              re-run, not even by a later process.
  integrity   The child ships ``(payload, sha256(payload))``; the parent
              recomputes the checksum before unpickling, so a corrupted
              result is an infra fault, not a wrong verdict.
  recycling   Workers retire after ``recycle_after`` tasks (leak hygiene
              on long searches) and are respawned transparently.

Determinism: the worker runs the same ``TieredEvaluator`` cascade as the
thread path, against batch-frozen thresholds shipped with each task, on a
suite regenerated from the (seeded, deterministic) testing agent. For a
well-behaved genome the returned ``EvalResult`` is bit-identical to the
in-process one. Tasks therefore ship the kernel *name* plus
``suite_shapes`` — not the ``KernelSpace`` (whose oracle/run callables
don't pickle) — so process isolation requires registered kernels.

Chaos: a ``reliability.SearchChaosInjector`` attached to the pool arms
per-attempt directives (``kill_worker`` / ``hang_eval`` /
``corrupt_result``) that the child executes against itself, drilling every
fault path above deterministically.
"""

from __future__ import annotations

import dataclasses
import hashlib
import multiprocessing as mp
import os
import pickle
import queue
import threading
import time
import traceback
from typing import Any, Callable, Optional

from repro.search.types import EvalResult

_SENTINEL = None                    # shutdown message to a worker


# -- the child ---------------------------------------------------------------

class _TimeoutTesting:
    """Delegating wrapper that applies the pool's cooperative per-task
    budget to every ``validate`` call inside the worker (the parent's
    join-timeout kill remains the hard guarantee)."""

    def __init__(self, testing, timeout_s):
        self._testing = testing
        self._timeout_s = timeout_s

    def validate(self, space, variant, tests, *, oracle=None):
        return self._testing.validate(space, variant, tests, oracle=oracle,
                                      timeout_s=self._timeout_s)

    def __getattr__(self, name):
        return getattr(self._testing, name)


def _run_task(task: dict) -> tuple[EvalResult, dict]:
    """Evaluate one genome exactly as the thread path would: fresh local
    evaluator + cache, frozen thresholds from the parent's batch."""
    from repro.kernels.registry import get_space
    from repro.search.cache import EvalCache
    from repro.search.evaluator import _UNSET, TieredEvaluator

    space = get_space(task["kernel"])
    if tuple(task["suite_shapes"]) != tuple(space.suite_shapes):
        space = dataclasses.replace(
            space, suite_shapes=tuple(task["suite_shapes"]))
    testing = task["testing"]
    tests = testing.generate_tests(space)
    if task.get("soft_timeout_s"):
        testing = _TimeoutTesting(testing, task["soft_timeout_s"])
    cfg = task["config"]
    ev = TieredEvaluator(screen=cfg["screen"], smoke=cfg["smoke"],
                         share_oracle=cfg["share_oracle"],
                         dominate_factor=cfg["dominate_factor"])
    frozen = task["frozen"]
    result = ev.evaluate(
        space, task["variant"], tests, testing=testing,
        profiling=task["profiling"], cache=EvalCache(),
        validate=task["validate"], tests_digest=task["tests_digest"],
        _frozen=_UNSET if frozen is None else tuple(frozen))
    # delivery-time flags are the parent's business
    result = dataclasses.replace(result, cached=False, replayed=False)
    return result, ev.stats.as_dict()


def _worker_main(conn) -> None:
    """Child process loop: recv task -> evaluate -> send checksummed
    payload. Runs until the sentinel (or until the parent shoots it)."""
    while True:
        try:
            task = conn.recv()
        except (EOFError, OSError):
            return
        if task is _SENTINEL:
            conn.close()
            return
        chaos = task.get("chaos")
        if chaos and chaos["kind"] == "kill_worker":
            os._exit(17)            # simulated segfault/OOM kill
        if chaos and chaos["kind"] == "hang_eval":
            time.sleep(chaos.get("seconds") or 3600.0)
        try:
            payload = pickle.dumps(("ok",) + _run_task(task))
        except BaseException:       # noqa: BLE001 — child must not die here
            payload = pickle.dumps(("error", traceback.format_exc(limit=8)))
        digest = hashlib.sha256(payload).hexdigest()
        if chaos and chaos["kind"] == "corrupt_result":
            # bit-rot in transit: the digest describes the true payload
            payload = bytes([payload[0] ^ 0xFF]) + payload[1:]
        try:
            conn.send((payload, digest))
        except (BrokenPipeError, OSError):
            return


# -- the parent --------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """What ``EvalWorkerPool.submit`` learned about one task. ``ok=False``
    means the genome exhausted its fault budget and must be quarantined —
    infra faults never raise."""
    ok: bool
    result: Optional[EvalResult] = None
    stats: Optional[dict] = None    # worker-side EvalStats deltas
    error: Optional[str] = None     # last fault detail when not ok
    attempts: int = 1


class _Worker:
    """One spawned child plus its parent-side pipe end."""

    def __init__(self, ctx, env_path: str):
        parent, child = ctx.Pipe()
        self.conn = parent
        self.tasks_done = 0
        # the spawned interpreter must be able to import repro; tests often
        # run with sys.path tweaks that children don't inherit, so splice
        # the package root into PYTHONPATH around start(). Workers only
        # interpret kernels, so they are pinned to the CPU: a chip belongs
        # to one process, and the parent may hold it.
        old = {k: os.environ.get(k) for k in ("PYTHONPATH", "JAX_PLATFORMS")}
        parts = [env_path] + ([old["PYTHONPATH"]] if old["PYTHONPATH"]
                              else [])
        os.environ["PYTHONPATH"] = os.pathsep.join(parts)
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            self.proc = ctx.Process(target=_worker_main, args=(child,),
                                    daemon=True)
            self.proc.start()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        child.close()

    def alive(self) -> bool:
        return self.proc.is_alive()

    def shoot(self) -> None:
        if self.proc.is_alive():
            self.proc.kill()
        self.proc.join(timeout=5.0)
        self.conn.close()

    def retire(self) -> None:
        try:
            self.conn.send(_SENTINEL)
        except (BrokenPipeError, OSError):
            pass
        self.proc.join(timeout=5.0)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=5.0)
        self.conn.close()


class EvalWorkerPool:
    """Pool of spawn-mode evaluation workers with deadlines, bounded
    retries, quarantine, and recycling. Thread-safe: ``submit`` may be
    called concurrently (``evaluate_many`` does, one thread per genome);
    each submit checks a worker out of the pool for the task's duration.

    ``on_stat(name, n)`` reports infra events (``worker_crashes``,
    ``eval_timeouts``, ``corrupt_results``, ``retries``, ``recoveries``,
    ``quarantined`` is the evaluator's to count, ``workers_recycled``) —
    wire it to ``TieredEvaluator.bump``.
    """

    def __init__(self, *, workers: int = 1, deadline_s: float = 60.0,
                 max_retries: int = 2, quarantine_after: int = 2,
                 recycle_after: int = 50, backoff_s: float = 0.05,
                 chaos=None,
                 on_stat: Optional[Callable[..., Any]] = None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        if quarantine_after < 1:
            raise ValueError("quarantine_after must be >= 1")
        self.workers = workers
        self.deadline_s = deadline_s
        self.max_retries = max_retries
        self.quarantine_after = quarantine_after
        self.recycle_after = recycle_after
        self.backoff_s = backoff_s
        self.chaos = chaos
        self._on_stat = on_stat or (lambda name, n=1: None)
        self._ctx = mp.get_context("spawn")
        from repro.core import costmodel
        self._env_path = os.path.dirname(os.path.dirname(
            os.path.dirname(costmodel.__file__)))
        self._idle: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._dispatched = 0        # global attempt counter (chaos step)
        self._strikes: dict[str, int] = {}
        self._strike_errors: dict[str, str] = {}
        self._closed = False
        for _ in range(workers):
            self._idle.put(self._spawn())

    # -- lifecycle -----------------------------------------------------------

    def _spawn(self) -> _Worker:
        return _Worker(self._ctx, self._env_path)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        drained = []
        while True:
            try:
                drained.append(self._idle.get_nowait())
            except queue.Empty:
                break
        for w in drained:
            w.retire()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- the submit path -----------------------------------------------------

    def submit(self, task: dict, *, digest: str) -> Outcome:
        """Run one task to an outcome: a verdict, or quarantine after the
        genome's fault budget is spent. Blocks while all workers are busy.
        """
        with self._lock:
            strikes = self._strikes.get(digest, 0)
            if strikes >= self.quarantine_after:
                return Outcome(ok=False, attempts=0,
                               error=self._strike_errors.get(
                                   digest, "previously quarantined"))
        attempts = 0
        faults = 0
        last_error = "unknown fault"
        while True:
            attempts += 1
            status, value = self._attempt(task, digest)
            if status == "ok":
                result, stats = value
                if faults:
                    self._on_stat("recoveries")
                return Outcome(ok=True, result=result, stats=stats,
                               attempts=attempts)
            faults += 1
            last_error = value
            with self._lock:
                self._strikes[digest] = self._strikes.get(digest, 0) + 1
                self._strike_errors[digest] = last_error
                quarantine = self._strikes[digest] >= self.quarantine_after
            if quarantine or attempts > self.max_retries:
                return Outcome(ok=False, error=last_error, attempts=attempts)
            self._on_stat("retries")
            time.sleep(self.backoff_s * (2 ** (attempts - 1)))

    def _attempt(self, task: dict, digest: str) -> tuple[str, Any]:
        """One dispatch to one worker. Returns ("ok", (result, stats)) or
        ("fault", error-string); the faulted worker is already replaced."""
        with self._lock:
            index = self._dispatched
            self._dispatched += 1
        shipped = dict(task, soft_timeout_s=self.deadline_s)
        if self.chaos is not None:
            fault = self.chaos.directive_for(digest, index)
            if fault is not None:
                shipped["chaos"] = {"kind": fault.kind,
                                    "seconds": fault.seconds}
        worker = self._idle.get()
        try:
            try:
                worker.conn.send(shipped)
            except (BrokenPipeError, OSError):
                self._on_stat("worker_crashes")
                worker.shoot()
                worker = None
                return "fault", "worker dead at dispatch"
            if not worker.conn.poll(self.deadline_s):
                self._on_stat("eval_timeouts")
                worker.shoot()
                worker = None
                return "fault", \
                    f"evaluation exceeded deadline ({self.deadline_s}s)"
            try:
                payload, sent_digest = worker.conn.recv()
            except (EOFError, OSError):
                self._on_stat("worker_crashes")
                worker.shoot()
                worker = None
                return "fault", "worker died mid-task"
            if hashlib.sha256(payload).hexdigest() != sent_digest:
                self._on_stat("corrupt_results")
                worker.shoot()          # don't trust its stream state
                worker = None
                return "fault", "result checksum mismatch"
            msg = pickle.loads(payload)
            if msg[0] == "error":
                # the evaluation itself raised in the child; the worker is
                # healthy — count the genome's strike, keep the worker
                self._on_stat("worker_crashes")
                return "fault", f"evaluation raised in worker:\n{msg[1]}"
            worker.tasks_done += 1
            return "ok", (msg[1], msg[2])
        finally:
            if worker is None:
                worker = self._spawn()
            elif worker.tasks_done >= self.recycle_after:
                self._on_stat("workers_recycled")
                worker.retire()
                worker = self._spawn()
            self._idle.put(worker)

    # -- introspection -------------------------------------------------------

    def strikes(self, digest: str) -> int:
        with self._lock:
            return self._strikes.get(digest, 0)
