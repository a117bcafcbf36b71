"""ICI-path stat aggregation — the on-device collective backend
(SURVEY.md §2.5: "per-chip stat vectors all-gathered with
jax.lax.all_gather over ICI so rank-skew diagnostics can be computed
on-device without a TCP round trip").

Each participant contributes one fixed-layout ``StatVector`` (step
duration, phase sums, memory) per aggregation; a single jitted
``shard_map`` all-gather moves every chip's vector over ICI and hands
rank 0's host the full ``(n_devices, n_fields)`` matrix in one transfer.
This is the latency-critical path for live cross-rank skew diagnosis on
a pod: one small collective instead of world_size TCP messages over DCN.

Works identically on the CI mesh (8 virtual CPU devices) and a real
slice; multi-host, every process sees the global result (all_gather is
global over the mesh axis).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

# fixed field layout (order matters — it IS the wire format on ICI)
STAT_FIELDS = (
    "step",
    "step_ms",
    "input_ms",
    "h2d_ms",
    "compute_ms",
    "optimizer_ms",
    "compile_ms",
    "collective_ms",
    "checkpoint_ms",
    "residual_ms",
    "memory_current_bytes",
    "memory_peak_bytes",
)
N_FIELDS = len(STAT_FIELDS)


@dataclasses.dataclass
class StatVector:
    values: Dict[str, float]

    def to_array(self) -> np.ndarray:
        return np.asarray(
            [float(self.values.get(f, 0.0)) for f in STAT_FIELDS],
            dtype=np.float32,
        )

    @classmethod
    def from_array(cls, arr: Sequence[float]) -> "StatVector":
        return cls({f: float(v) for f, v in zip(STAT_FIELDS, arr)})


class IciStatAggregator:
    """All-gather per-device stat vectors over a mesh axis."""

    def __init__(self, mesh=None, axis: Optional[str] = None) -> None:
        import jax

        if mesh is None:
            from traceml_tpu.parallel.mesh import make_mesh

            mesh = make_mesh({"fsdp": len(jax.devices())})
        self.mesh = mesh
        # default: gather over ALL mesh axes (every chip contributes)
        self.axes = (axis,) if axis else tuple(mesh.axis_names)
        self._gather = self._build()

    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axes = self.axes

        def gather(local: jnp.ndarray) -> jnp.ndarray:
            # local: (1, N_FIELDS) shard per device → (n_devices, N_FIELDS).
            # Gather over the LAST axis first: each all_gather makes the
            # gathered axis major in dim 0, so reversing the chain leaves
            # the result in mesh-linear (first-axis-major) order — row i
            # IS participant i of the P(axes) input placement.  Rank
            # attribution downstream depends on this.
            out = local
            for ax in reversed(axes):
                out = jax.lax.all_gather(out, ax, axis=0, tiled=True)
            return out

        # check_vma off: the output IS replicated over the gathered axes
        # (all_gather makes it so), but static replication inference
        # can't always prove it across multiple chained axes.
        return jax.jit(
            jax.shard_map(
                gather,
                mesh=self.mesh,
                in_specs=P(axes),
                out_specs=P(),
                check_vma=False,
            )
        )

    @property
    def n_participants(self) -> int:
        n = 1
        for ax in self.axes:
            n *= self.mesh.shape[ax]
        return n

    def aggregate(self, stats: StatVector) -> np.ndarray:
        """Contribute this process's vector; returns the gathered
        ``(n_participants, N_FIELDS)`` matrix (host numpy).

        Single-controller usage (one process drives the whole mesh, as
        in tests and single-host jobs): the same vector is contributed
        for every local device shard.
        """
        n = self.n_participants
        return self._place_and_gather(
            np.broadcast_to(stats.to_array()[None, :], (n, N_FIELDS))
        )

    def aggregate_many(self, stats: Sequence[StatVector]) -> np.ndarray:
        """Single-controller variant: place DISTINCT per-device vectors
        (len must equal n_participants) and gather.  Tests and
        single-host jobs use this to exercise the real collective with
        heterogeneous per-chip stats."""
        n = self.n_participants
        if len(stats) != n:
            raise ValueError(f"need {n} vectors, got {len(stats)}")
        return self._place_and_gather(np.stack([s.to_array() for s in stats]))

    def _place_and_gather(self, rows: np.ndarray) -> np.ndarray:
        """Host rows → one row per device (each device receives only
        its own row) → the gathered matrix on the host."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        local = jax.device_put(
            np.ascontiguousarray(rows, dtype=np.float32),
            NamedSharding(self.mesh, P(self.axes)),
        )
        with self.mesh:
            out = self._gather(local)
        return np.asarray(jax.device_get(out))

    def rank_skew(self, gathered: np.ndarray, field: str) -> Dict[str, float]:
        """Cross-chip skew for one field: (worst − median) / median."""
        idx = STAT_FIELDS.index(field)
        col = np.asarray(gathered)[:, idx]
        med = float(np.median(col))
        worst = int(np.argmax(col))
        skew = (float(col[worst]) - med) / med if med > 0 else 0.0
        return {
            "median": med,
            "worst": float(col[worst]),
            "worst_rank": worst,
            "skew_pct": skew,
        }


def gathered_to_stat_vectors(gathered: np.ndarray) -> List[StatVector]:
    return [StatVector.from_array(row) for row in np.asarray(gathered)]
