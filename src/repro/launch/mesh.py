"""Production mesh construction (deliverable e, MULTI-POD DRY-RUN §1).

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state. The dry-run launcher sets
``--xla_force_host_platform_device_count=512`` BEFORE importing jax;
smoke tests and benchmarks see the single real CPU device.

Hardware model (TPU v5e, used by the roofline analysis):
  197 TFLOP/s bf16 per chip · 819 GB/s HBM · ~50 GB/s/link ICI.
"""
from __future__ import annotations

import numpy as np

import jax

PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link


def _make_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def fold_mesh_shape(n: int, *, multi_pod: bool = False) -> tuple:
    """Fold ``n`` devices into the largest valid mesh shape.

    "model" takes the largest power-of-two divisor of ``n`` up to the
    canonical 16 (tensor parallelism wants a power of two; anything
    wider than 16 splits head dims); "data" absorbs the rest. multi_pod
    peels a leading pod=2, so it needs an even device count.
    """
    n = int(n)
    if n < 1:
        raise RuntimeError(f"cannot build a mesh from {n} devices")
    shape = ()
    if multi_pod:
        if n % 2:
            raise RuntimeError(
                f"multi_pod mesh needs an even device count, have {n} "
                f"devices — drop multi_pod or launch via "
                f"repro.launch.dryrun (forces 512 host devices)")
        shape, n = (2,), n // 2
        if n < 1:
            raise RuntimeError(
                "multi_pod mesh needs >= 2 devices, have 2·0")
    model = 1
    while model * 2 <= min(16, n) and n % (model * 2) == 0:
        model *= 2
    return shape + (n // model, model)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return _make_mesh(shape, axes)
    if len(devices) > n:
        # more devices than needed (e.g. 512 forced, single-pod 256)
        return _make_mesh(shape, axes, devices=devices[:n])
    # generic fallback: fold whatever this host provides into the
    # largest valid (data, model) shape (fold_mesh_shape raises with
    # the device count when no valid fold exists, e.g. multi_pod odd)
    return _make_mesh(fold_mesh_shape(len(devices), multi_pod=multi_pod),
                      axes, devices=devices)


def make_population_mesh(devices=None):
    """1-D population mesh: every device on the "data" axis (model=1).

    The population plane ((num_clients,) control/world arrays,
    core/population.py) shards over "data" only — it has no model axis
    to fill, so unlike the production grid ANY device count is a valid
    shape."""
    devices = list(jax.devices()) if devices is None else list(devices)
    return _make_mesh((len(devices), 1), ("data", "model"),
                      devices=devices)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Single-device mesh for smoke tests of the sharded code path."""
    return _make_mesh(shape, axes, devices=jax.devices()[:1])


def topology_pspec(mesh, min_pods: int = None):
    """PartitionSpec for a topology accumulator plane ``(pods, rows,
    lane)`` (repro.topology.engine.TopologyState.accum): shard the
    leading pod axis over "data" when the plane is tall enough to
    split evenly-ish (``min_pods`` defaults to the data-axis size),
    replicate otherwise — small upper-tier planes (often 1 root pod)
    don't benefit from sharding."""
    from jax.sharding import PartitionSpec
    if "data" not in mesh.axis_names:
        return PartitionSpec()
    if min_pods is not None and min_pods < mesh.shape["data"]:
        return PartitionSpec()
    return PartitionSpec("data")


def client_axes_in_mesh(cfg, mesh) -> tuple:
    """The subset of cfg.client_axes present in this mesh."""
    return tuple(a for a in cfg.client_axes if a in mesh.axis_names)


def num_clients(cfg, mesh) -> int:
    axes = client_axes_in_mesh(cfg, mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return max(n, 1)
