"""Mesh construction. A FUNCTION (not a module-level constant) so importing
this module never touches jax device state.

Every axis is ``Auto``: the programs place arrays with explicit
``NamedSharding``s and ``shard_map``, and let GSPMD propagate the rest.
(``jax.make_mesh`` defaults to ``Explicit`` axes, under which eager ops
on sharded arrays need a ``jax.set_mesh`` context.)"""

from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def force_host_devices(n: int) -> None:
    """Split the CPU backend into ``n`` devices for a mesh rehearsal, with
    single-threaded Eigen: multi-threaded contractions split the reduction
    axis by output width, so a head-sharded matmul would round differently
    from the unsharded one. Call it before JAX starts its backend (first
    device query or computation); flags already in ``XLA_FLAGS`` win."""
    flags = os.environ.get("XLA_FLAGS", "")
    for flag in (f"--xla_force_host_platform_device_count={n}",
                 "--xla_cpu_multi_thread_eigen=false"):
        if flag.split("=")[0] not in flags:
            flags += " " + flag
    os.environ["XLA_FLAGS"] = flags.strip()


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Production TPU v5e mesh: 16x16 (one pod, 256 chips) or
    2x16x16 (two pods, 512 chips). The ``pod`` axis is the DCN hop —
    gradient reduction composes (pod, data); see sharding/rules.py."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))
