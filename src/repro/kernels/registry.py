"""Decorator-based kernel registry — each kernel module declares its own
optimization space.

Historically ``repro.core.variants`` hand-maintained one ``SPACES`` dict
that knew every kernel's run/oracle/cost wiring.  That made adding a kernel
a two-file edit and coupled the agent core to every kernel module.  Now the
space definition lives next to the kernel it describes::

    from repro.kernels.registry import (KernelSpace, Knob,
                                        register_kernel_space)

    @register_kernel_space
    def _space() -> KernelSpace:
        return KernelSpace(name="my_kernel", baseline=BASELINE, ...)

``repro.kernels.__init__`` imports every kernel module, so importing the
package populates the registry; lookups (``get_space`` / the ``SPACES``
mapping view) trigger that import lazily so standalone consumers never see
an empty registry.  ``repro.core.variants`` re-exports everything here as a
back-compat shim.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Iterator, Mapping

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Knob:
    """One legal move in the optimization space."""
    name: str
    kind: str                       # "pow2" | "bool"
    lo: int = 8                     # pow2 bounds
    hi: int = 1024
    # which roofline terms this knob attacks; the planning agent matches
    # knobs against the dominant term of the profile. A knob that removes a
    # whole pass attacks both memory (traffic) and overhead (launch).
    attacks: tuple = ("memory",)    # of "memory" | "compute" | "overhead"
    # For bool knobs: the catalog-optimized direction (paper §5.3). The
    # planning agent only ever moves TOWARD the target; knobs whose baseline
    # already sits at the target (e.g. fuse_s_out) are ablation-only.
    target: Any = None
    note: str = ""

    def value(self, variant) -> Any:
        """This knob's setting in ``variant``. A pow2 knob left at
        ``None`` (the kernel sizes it from the input shape) reads as
        ``lo``, so tile moves have a value to start from."""
        v = getattr(variant, self.name)
        return self.lo if v is None and self.kind == "pow2" else v


@dataclasses.dataclass(frozen=True)
class TestCase:
    """One element of the test suite T (paper §3.1)."""
    name: str
    args: tuple                     # positional args to run_fn / oracle
    shape_info: dict                # kwargs for the cost function


TestCase.__test__ = False           # keep pytest from collecting it


@dataclasses.dataclass(frozen=True)
class KernelSpace:
    name: str
    baseline: Any
    run: Callable[..., Any]         # run(variant, *args, interpret=...)
    oracle: Callable[..., Any]
    cost: Callable[..., Any]        # cost(variant, **shape_info)
    knobs: tuple[Knob, ...]
    # shapes the TESTING agent draws the suite from (LLaMA-family dims per
    # paper §4); values are generator kwargs, see agents.TestingAgent.
    suite_shapes: tuple[dict, ...]
    # materializes one TestCase: make_inputs(shape, *, dtype, seed)
    make_inputs: Callable[..., TestCase] | None = None
    # the shipped tuned variant (``ops`` dispatch default); falls back to
    # ``baseline`` when a kernel has no pre-tuned genome.
    default: Any = None

    def mutate(self, variant, knob: Knob, value) -> Any:
        new = dataclasses.replace(variant, **{knob.name: value})
        # name = genome digest, not lineage (lineage lives in the Log)
        return dataclasses.replace(new, name=f"{self.name}@{knob.name}={value}")

    @property
    def shipped(self) -> Any:
        return self.default if self.default is not None else self.baseline


_REGISTRY: dict[str, KernelSpace] = {}


def register_kernel_space(obj):
    """Register a ``KernelSpace`` — usable as ``@register_kernel_space`` on
    a zero-arg factory function, or called directly on a space instance.

    Returns the registered ``KernelSpace`` (so a decorated factory's module
    attribute *is* the space). Duplicate names are an error: spaces register
    at module import, so a collision means two modules claim one kernel.
    """
    space = obj if isinstance(obj, KernelSpace) else obj()
    if not isinstance(space, KernelSpace):
        raise TypeError(f"register_kernel_space expected a KernelSpace or a "
                        f"factory returning one, got {type(space).__name__}")
    if space.name in _REGISTRY:
        raise ValueError(f"kernel space {space.name!r} is already registered")
    _REGISTRY[space.name] = space
    return space


def _populate() -> None:
    # Importing the package imports every kernel module, each of which
    # registers its space as a side effect.
    import repro.kernels  # noqa: F401


def get_space(name: str) -> KernelSpace:
    _populate()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel space named {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def registered_kernels() -> tuple[str, ...]:
    _populate()
    return tuple(sorted(_REGISTRY))


class _SpacesView(Mapping):
    """Read-only dict-compatible view of the registry (legacy ``SPACES``)."""

    def __getitem__(self, name: str) -> KernelSpace:
        return get_space(name)

    def __iter__(self) -> Iterator[str]:
        _populate()
        return iter(_REGISTRY)

    def __len__(self) -> int:
        _populate()
        return len(_REGISTRY)

    def __repr__(self) -> str:
        _populate()
        return f"SPACES({sorted(_REGISTRY)})"


SPACES: Mapping[str, KernelSpace] = _SpacesView()


def make_inputs(kernel: str, shape: dict, *, dtype=jnp.float32,
                seed: int = 0) -> TestCase:
    """Materialize one test case for a registered kernel from a shape spec."""
    space = get_space(kernel)
    if space.make_inputs is None:
        raise NotImplementedError(f"kernel {kernel!r} registered no "
                                  "make_inputs generator")
    return space.make_inputs(shape, dtype=dtype, seed=seed)


# -- suite / oracle memoization ----------------------------------------------
#
# Test suites and oracle outputs depend only on (kernel, suite shapes, data
# seed, dtypes) — never on the genome under evaluation — yet historically
# every search and every benchmark table regenerated both per call (per
# *genome* per test, for the oracle). These module-level memos make them
# once-per-suite; the tiered evaluator and ``benchmarks/run.py`` both read
# through them. ``clear_suite_memos()`` drops the (unbounded) memo arrays.

_SUITE_MEMO: dict[tuple, tuple] = {}
_ORACLE_MEMO: dict[tuple, tuple] = {}
_MEMO_LOCK = threading.Lock()          # guards the memo/lock dicts only
_ORACLE_KEY_LOCKS: dict[tuple, threading.Lock] = {}


def suite_key(space: KernelSpace, testing) -> tuple:
    """Identity of a generated suite: kernel + its shape spec + the testing
    agent's class (a subclass may override ``generate_tests``), data seed,
    and dtype roster (see ``TestingAgent.generate_tests``)."""
    cls = type(testing)
    return (space.name, repr(space.suite_shapes),
            f"{cls.__module__}.{cls.__qualname__}",
            getattr(testing, "seed", None),
            tuple(str(jnp.dtype(d)) for d in getattr(testing, "dtypes", ())))


def suite_tests(space: KernelSpace, testing) -> list[TestCase]:
    """Memoized ``testing.generate_tests(space)``; one generation per
    (kernel, shapes, seed, dtypes) process-wide."""
    key = suite_key(space, testing)
    with _MEMO_LOCK:
        hit = _SUITE_MEMO.get(key)
    if hit is not None:
        return list(hit)
    tests = testing.generate_tests(space)
    with _MEMO_LOCK:
        _SUITE_MEMO.setdefault(key, tuple(tests))
    return list(tests)


def oracle_outputs(space: KernelSpace, tests, *, digest: str) -> tuple[tuple, bool]:
    """Memoized oracle outputs aligned with ``tests``, keyed by (kernel,
    suite digest). Returns ``(outputs, computed)`` where ``computed`` is
    True when this call paid for the oracle run (callers meter oracle work
    with it).

    Locking is per key: racing evaluators of the SAME (kernel, suite)
    still compute the oracle exactly once, but evaluators of different
    kernels no longer serialize on one kernel's oracle run (historically
    the computation held the single global memo lock)."""
    key = (space.name, digest)
    with _MEMO_LOCK:
        hit = _ORACLE_MEMO.get(key)
        if hit is not None:
            return hit, False
        key_lock = _ORACLE_KEY_LOCKS.setdefault(key, threading.Lock())
    with key_lock:
        with _MEMO_LOCK:
            hit = _ORACLE_MEMO.get(key)
        if hit is not None:
            return hit, False
        outs = tuple(space.oracle(*t.args) for t in tests)
        with _MEMO_LOCK:
            _ORACLE_MEMO[key] = outs
        return outs, True


def clear_suite_memos() -> None:
    """Drop all memoized suites and oracle outputs (frees the arrays)."""
    with _MEMO_LOCK:
        _SUITE_MEMO.clear()
        _ORACLE_MEMO.clear()
        _ORACLE_KEY_LOCKS.clear()
