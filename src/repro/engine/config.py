"""Declarative Engine configuration — one validated object instead of the
old flag cloud (``overlap=``, ``ell=``, ``blocked=``, ``layout=``).

An :class:`EngineConfig` names a registered format, schedule and topology
plus the knobs every path shares (pipelining waves, ELL autotune caps,
mesh axis, learning rate, precision).  Validation happens at construction:
unknown names and unsupported combinations raise ``ValueError`` listing
the registered options, so a typo dies at config time, not three layers
down inside ``shard_map``.

Spec grammar: ``format[+schedule[+topology[+partition]]]`` — ``"ell"``,
``"ell+pipelined"``, ``"ell+pipelined+ring"``,
``"ell+pipelined+hypercube+mincom"``.  An omitted schedule takes
the format's default; an omitted topology takes ``hypercube`` (the
paper's NoC); an omitted partition takes ``naive`` (contiguous
striping).  ``.spec`` is the canonical spelling and keeps the legacy
two- and three-part forms whenever the trailing knobs are defaults, so
pre-topology/pre-partition spec strings, metric keys and checkpoints
round-trip unchanged.  The ``merge`` knob (``"dedup"`` | ``"redundancy"``,
the edge-plan merge level) is a config FIELD rather than a spec part: it
changes the plan the kernels walk, not which engine path runs.

``"auto"`` is the one spec that is not a format name: it defers the
format/schedule/topology choice to :mod:`repro.engine.planner`, which
resolves it to a concrete registered spec at build time (cost model →
persisted autotune record → static fallback).  An auto config carries the
shared knobs but no concrete parts; combining it with an explicit
schedule or topology is an error.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

from . import registry

Caps = Union[str, Sequence[int], None]

#: precisions the kernels implement today (bf16 messages are a future
#: format registration, not a silent cast)
PRECISIONS = ("fp32",)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Declarative spec of one aggregation engine.

    format:   registered edge layout — ``"coo"`` | ``"block"`` | ``"ell"``
    schedule: registered fold issue order — ``"serial"`` | ``"pipelined"``
              (``None`` → the format's default)
    topology: registered interconnect — ``"hypercube"`` | ``"allpairs"`` |
              ``"ring"`` | ``"torus2d"`` (``None`` → ``hypercube``, the
              paper's NoC and the oracle schedule)
    n_chunks: feature waves for the pipelined schedule (``None`` → the
              backend default, :func:`repro.distributed.aggregate.default_n_chunks`)
    caps:     ELL degree-bucket caps override (``None`` → the autotuned
              scheme from :mod:`repro.kernels.tune`)
    block_tiles: destination tiles for the block format's single-device
              layer (distributed paths always tile per core instead)
    partition: node→core partition quality — ``"naive"`` (contiguous
              striping, the paper's address decode) | ``"mincom"``
              (communication-volume-minimizing relabeling); fourth spec
              part, omitted from ``.spec`` when default
    merge:    edge-plan merge level — ``"dedup"`` (within-block sender
              merge) | ``"redundancy"`` (+ GraphACT cross-row virtual
              vertices); a field, not a spec part
    axis:     mesh axis name that plays the paper's 16-core hypercube
    lr:       SGD learning rate baked into ``train_step``
    precision: accumulation precision (``"fp32"`` only today: every matmul
              of the step, combination and kernels, at
              ``repro.kernels.spmm.F32_MATMUL``)
    """

    format: str = "coo"
    schedule: Optional[str] = None
    topology: Optional[str] = None
    partition: str = "naive"
    merge: str = "dedup"
    n_chunks: Optional[int] = None
    caps: Caps = None
    block_tiles: int = 4
    axis: str = "model"
    lr: float = 0.05
    precision: str = "fp32"

    def __post_init__(self):
        from repro.graph.partition import validate_partition
        from repro.kernels.edgeplan import validate_merge
        validate_partition(self.partition)
        validate_merge(self.merge)
        if self.format == registry.AUTO_SPEC:
            if self.schedule is not None or self.topology is not None:
                raise ValueError(
                    f"{registry.AUTO_SPEC!r} is a complete spec — the "
                    f"planner picks the format, schedule AND topology; "
                    f"drop the explicit "
                    f"{'schedule' if self.schedule else 'topology'} or name "
                    f"a concrete spec from "
                    f"{registry.supported_specs(three_part=True)}")
        else:
            fmt = registry.get_format(self.format)
            if self.schedule is None:
                object.__setattr__(self, "schedule", fmt.default_schedule)
            if self.topology is None:
                object.__setattr__(self, "topology",
                                   registry.DEFAULT_TOPOLOGY)
            registry.validate_combo(self.format, self.schedule,
                                    self.topology)
        if self.n_chunks is not None and int(self.n_chunks) < 1:
            raise ValueError(f"n_chunks must be >= 1, got {self.n_chunks}")
        if self.block_tiles < 1:
            raise ValueError(
                f"block_tiles must be >= 1, got {self.block_tiles}")
        if self.precision not in PRECISIONS:
            raise ValueError(f"unknown precision {self.precision!r}; "
                             f"supported: {list(PRECISIONS)}")
        if self.caps is not None and not isinstance(self.caps, str):
            object.__setattr__(self, "caps", tuple(int(c) for c in self.caps))

    @classmethod
    def from_spec(cls, spec: str, **overrides) -> "EngineConfig":
        """Parse ``"ell+pipelined+ring"`` / ``"ell+pipelined"`` / ``"coo"``
        / ``"ell+pipelined+hypercube+mincom"`` into a validated config.

        The spec is ``format[+schedule[+topology[+partition]]]``; a bare
        format takes its default schedule, an omitted topology defaults to
        ``hypercube``, an omitted partition to ``naive``.  ``overrides``
        set the remaining knobs (``n_chunks=4``, ``lr=0.1``, ...).
        """
        parts = [p.strip() for p in spec.split("+")]
        if not 1 <= len(parts) <= 4 or not all(parts):
            raise ValueError(
                f"bad engine spec {spec!r}: expected 'format', "
                f"'format+schedule', 'format+schedule+topology' or "
                f"'format+schedule+topology+partition'; valid "
                f"specs: {registry.supported_specs()} (+ optionally one of "
                f"{registry.available_topologies()}, then one of "
                f"{registry.available_partitions()})")
        kw = dict(overrides)
        kw["format"] = parts[0]
        if len(parts) >= 2:
            kw["schedule"] = parts[1]
        if len(parts) >= 3:
            kw["topology"] = parts[2]
        if len(parts) == 4:
            kw["partition"] = parts[3]
        return cls(**kw)

    @property
    def is_auto(self) -> bool:
        """True for the planner-deferred ``"auto"`` spec (no concrete
        format/schedule/topology until :meth:`Engine.resolve` runs)."""
        return self.format == registry.AUTO_SPEC

    @property
    def spec(self) -> str:
        """The canonical spec string of this config.

        Two-part ``"format+schedule"`` when the topology is the default
        ``hypercube`` (pre-topology specs, metric keys and checkpoints
        round-trip unchanged); ``"format+schedule+topology"`` with a
        non-default topology; a fourth ``+partition`` part only when the
        partition is not ``naive`` (the topology is then always spelled
        out, default or not, so the parts stay positional); ``"auto"``
        for the planner-deferred config.
        """
        if self.is_auto:
            return registry.AUTO_SPEC
        base = f"{self.format}+{self.schedule}"
        if self.partition != "naive":
            return f"{base}+{self.topology}+{self.partition}"
        if self.topology == registry.DEFAULT_TOPOLOGY:
            return base
        return f"{base}+{self.topology}"

    def with_spec(self, spec: str) -> "EngineConfig":
        """This config's knobs (waves, caps, axis, lr, ...) re-bound to a
        different spec — how the planner turns an auto config concrete."""
        return EngineConfig.from_spec(
            spec, partition=self.partition, merge=self.merge,
            n_chunks=self.n_chunks, caps=self.caps,
            block_tiles=self.block_tiles, axis=self.axis, lr=self.lr,
            precision=self.precision)
