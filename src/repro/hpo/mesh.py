"""Device-mesh execution layer for the HPO stack (DESIGN.md §8).

The paper's final scaling claim is a further speedup from running the
lazy-GP optimizer "in a parallel environment": one suggest round is
embarrassingly parallel over both the **study** axis (S independent
posteriors, PR 2's batch dimension) and the **restart** axis (R
independent EI ascents per study).  This module owns the mapping from
those logical axes onto a physical `jax.sharding.Mesh`:

  * axis ``"study"`` — shards the leading S axis of the stacked
    `LazyGPState` (every leaf: `x_buf (S, n_max, d)`, `li_buf
    (S, n_max, n_max)`, per-study scalars `(S,)`, params leaves `(S,)`).
    No collective ever crosses this axis: studies are independent, so the
    sharded suggest/absorb programs are pure SPMD with zero communication.
  * axis ``"restart"`` — when S is smaller than the device count, the
    spare factor shards each study's R-restart EI ascent (the dominant
    per-round cost).  The state is *replicated* across this axis
    (including `li_buf` — see DESIGN.md §8 for why the maintained inverse
    must ride along), each shard ascends its restart slice, and one
    `all_gather` per suggest reassembles the (R,) candidate set so the
    basin dedup sees every restart.

`build(spec, n_studies, restarts)` turns the `SchedulerConfig.mesh` knob
into an `HPOMesh` (or None for the unsharded degenerate case):

  * ``"none"``  — no mesh; the plain single-program path (the default).
  * ``"auto"``  — factor the available devices into study x restart
    shards that divide S and R; collapses to None on a single device.
  * ``"SxR"``   — explicit shard counts, e.g. ``"4x2"`` = 4-way study
    sharding x 2-way restart sharding (must divide S and R and fit the
    device count).  ``"8"`` is shorthand for ``"8x1"``.

Validated on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the CI recipe); on a TPU slice the same specs place shards on real chips.
`benchmarks/bench_shard.py` measures the scaling curve this enables.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STUDY_AXIS = "study"
RESTART_AXIS = "restart"


def _largest_divisor_leq(n: int, cap: int) -> int:
    for c in range(min(n, cap), 0, -1):
        if n % c == 0:
            return c
    return 1


@dataclasses.dataclass(frozen=True)
class HPOMesh:
    """A (study x restart) device mesh plus the placement helpers.

    `study_shards * restart_shards` devices participate; the leading study
    axis of every stacked array is split `study_shards` ways and replicated
    across the restart axis.
    """

    mesh: Mesh
    study_shards: int
    restart_shards: int

    @property
    def n_devices(self) -> int:
        return self.study_shards * self.restart_shards

    def study_sharding(self) -> NamedSharding:
        """Sharding for stacked `(S, ...)` arrays: split S, replicate rest."""
        return NamedSharding(self.mesh, P(STUDY_AXIS))

    def place(self, tree):
        """Put a pytree of stacked `(S, ...)` leaves onto the mesh."""
        return jax.device_put(tree, self.study_sharding())

    def shard(self, body, n_in: int):
        """`shard_map` a stacked-state transition over the mesh.

        `body` maps `n_in` leading-S-axis pytrees to leading-S-axis pytrees
        (out_specs is a pytree prefix, so one spec covers any output
        arity); each shard sees the local `(S/study_shards, ...)` slice.
        Outputs must be replicated across the restart axis (each restart
        shard computes them identically after its `all_gather`), which
        `check_vma=False` asserts by fiat rather than proof.
        """
        return jax.shard_map(body, mesh=self.mesh,
                             in_specs=(P(STUDY_AXIS),) * n_in,
                             out_specs=P(STUDY_AXIS), check_vma=False)

    def replicated(self, body):
        """Run a single-study computation replicated on every device.

        The routed paths index one study out of the sharded stack; a Pallas
        kernel on that sub-state cannot be partitioned by GSPMD, so the
        sub-state is replicated and each device computes it under
        `shard_map` (identical results, written back by the caller)."""
        return jax.shard_map(body, mesh=self.mesh, in_specs=P(),
                             out_specs=P(), check_vma=False)


def parse_spec(spec: str) -> tuple[int, int] | str | None:
    """``"none"`` -> None, ``"auto"`` -> "auto", ``"SxR"``/``"S"`` -> ints."""
    s = (spec or "none").strip().lower()
    if s in ("none", ""):
        return None
    if s == "auto":
        return "auto"
    parts = s.split("x")
    try:
        if len(parts) == 1:
            return int(parts[0]), 1
        if len(parts) == 2:
            return int(parts[0]), int(parts[1])
    except ValueError:
        pass
    raise ValueError(
        f"bad mesh spec {spec!r}: expected 'none', 'auto', 'S' or 'SxR' "
        "(study shards x restart shards, e.g. '4x2')")


def build(spec: str, n_studies: int, restarts: int,
          devices=None) -> HPOMesh | None:
    """Resolve a mesh spec against the study/restart extents and devices.

    Shard counts must divide their axis extents exactly: a study shard owns
    `S / study_shards` whole studies and a restart shard ascends
    `R / restart_shards` whole seeds, so non-divisible specs are rejected
    rather than padded (GSPMD padding would silently waste lanes).
    """
    parsed = parse_spec(spec)
    if parsed is None:
        return None
    devices = list(devices if devices is not None else jax.devices())
    if parsed == "auto":
        if len(devices) == 1:
            return None  # the unsharded path IS the one-device case
        s = _largest_divisor_leq(n_studies, len(devices))
        r = _largest_divisor_leq(restarts, len(devices) // s)
        parsed = (s, r)
    s, r = parsed
    if s < 1 or r < 1:
        raise ValueError(f"mesh shards must be >= 1, got {s}x{r}")
    if s * r > len(devices):
        raise ValueError(
            f"mesh {s}x{r} needs {s * r} devices, have {len(devices)} "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before importing jax)")
    if n_studies % s:
        raise ValueError(
            f"study shards ({s}) must divide n_studies ({n_studies})")
    if restarts % r:
        raise ValueError(
            f"restart shards ({r}) must divide acq.restarts ({restarts})")
    mesh = Mesh(np.asarray(devices[:s * r]).reshape(s, r),
                (STUDY_AXIS, RESTART_AXIS))
    return HPOMesh(mesh=mesh, study_shards=s, restart_shards=r)
