"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

  single-pod: (16, 16)    axes (data, model)  = 256 chips (one v5e pod)
  multi-pod : (2, 16, 16) axes (pod, data, model) = 512 chips

'model' carries TP / EP / the k²-triples predicate arena; 'data' carries DP
+ FSDP weight shards; 'pod' is pure DP across the (slow) cross-pod links —
gradient all-reduce over 'pod' is the int8-compression target.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """The one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` and ``shard_map``-inside-``jit`` reject
    the partition specs this repo writes; all of it assumes ``Auto``.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Every mesh axis that is not 'model' (DP/FSDP axes)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def serve_mesh_shape(n_devices: int, *, model_max: int = 4) -> tuple[int, int]:
    """Factor ``n_devices`` into a (data, model) serve-mesh shape that uses
    EVERY device: the model axis is the largest divisor of ``n_devices``
    not exceeding ``model_max``.

    This replaces the old ``mp = min(4, n)`` factorization, whose
    ``(n // mp, mp)`` mesh silently dropped devices whenever ``n % mp``
    was nonzero (6 devices became a 1x4 mesh serving on 4).  Here
    6 -> (2, 3), 8 -> (2, 4), 5 -> (5, 1); the product is always
    ``n_devices`` or the call fails loudly.
    """
    if n_devices < 1:
        raise ValueError(f"need at least one device, got {n_devices}")
    mp = max(
        d for d in range(1, min(model_max, n_devices) + 1)
        if n_devices % d == 0
    )
    shape = (n_devices // mp, mp)
    assert shape[0] * shape[1] == n_devices
    return shape


class Peaks(NamedTuple):
    """Published per-chip peaks: the roofline denominators."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None
