"""Production meshes.

Single pod: (16, 16) over ("data", "model") — 256 chips; the 16-way model
axis is the paper's 4-D hypercube (16 = 2⁴) for the GCN path and TP/EP for
the LM archs.  Multi-pod: (2, 16, 16) over ("pod", "data", "model") — the
"pod" axis is an outer data-parallel axis whose collectives cross the
inter-pod links (DCN in a real deployment).

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init; smoke
tests and benches see the real 1-CPU backend).

Every mesh here types its axes ``AxisType.Auto``: the code places and
shards its arrays itself (``NamedSharding`` + ``shard_map``), and JAX
0.9's default ``Explicit`` axes would put shardings into array types.
"""
from __future__ import annotations

import os
from typing import Sequence

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh with ``Auto`` axes (tests: small meshes on the 16
    host devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_topology_mesh(n_cores: int, topology: str = "hypercube",
                       axis: str = "model"):
    """Core-axis mesh validated against a registered topology.

    The topology owns the core-count contract (``Topology.validate_cores``
    — every built-in wants a power of two), so a bad count dies here with
    the topology's own error instead of three layers down inside
    ``shard_map``.  The mesh itself stays one-dimensional: grid structure
    (e.g. the 2-D torus's R×C) lives in the topology's ``ppermute``
    schedules, not in the mesh shape, so every topology shares one mesh
    form and one ``PartitionSpec`` rule.
    """
    from repro.engine.registry import get_topology

    get_topology(topology).validate_cores(n_cores)
    if len(jax.devices()) < n_cores:
        raise RuntimeError(
            f"need {n_cores} devices for n_cores={n_cores}, have "
            f"{len(jax.devices())} — set XLA_FLAGS="
            "--xla_force_host_platform_device_count")
    return make_mesh((n_cores,), (axis,))


def forced_host_env(n_devices: int, src: str) -> dict:
    """Environment for a child process that runs on ``n_devices`` forced
    host CPU devices, with ``src`` on its ``PYTHONPATH``.

    The benchmarks' and planner's re-exec launchers use this.  They time
    XLA's CPU backend, and a child on a TPU host would wait on the chip
    that this (parent) process already holds, so on a TPU backend this
    raises instead of launching anything.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "this launcher re-executes itself on forced host CPU devices; "
            "on a TPU host the parent process holds the chip and a child "
            "would hang on it or time the CPU under the chip's name — "
            "run it with JAX_PLATFORMS=cpu, or measure the chip in one "
            "process (chip_smoke.py)")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env
