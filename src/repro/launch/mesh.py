"""Production mesh definition (functions only — importing this module
never touches jax device state; see MULTI-POD DRY-RUN spec)."""
from __future__ import annotations

import jax
import numpy as np


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which Explicit axes (the default
    since JAX 0.7) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU tests/benches (never 512 placeholders)."""
    return auto_mesh((1, 1), ("data", "model"))


def make_serving_mesh(n_devices: int = 0, devices=None,
                      model_parallel: int = 0):
    """Mesh for a ``ServingDeployment`` (serving/deployment.py): engine
    params are laid out by the launch/sharding.py param rules (SLM/LLM
    weight leaves sharded over "model" under RULES_INFERENCE, so
    per-device param bytes shrink with the model axis) and one decode
    lane spans a pod slice — batch rows over ("pod", "data"), wide
    cache dims over "model" (``lane_leaf_spec`` rules).

    Factors the device count as pod×data×model: "model" takes a factor
    of 2 when 4+ devices are available (enough left for batch
    parallelism), the remainder backs the ("pod", "data") batch axes —
    8 devices -> (2, 2, 2), 4 -> (1, 2, 2), 2 -> (1, 2, 1).
    ``model_parallel`` overrides the model-axis width (e.g. 4 on 8
    devices trades batch parallelism for a ~4x smaller per-device
    param footprint).  Works for real accelerators and for host meshes
    of fake CPU devices (``--xla_force_host_platform_device_count``)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if n_devices:
        if n_devices > len(devs):
            raise ValueError(
                f"make_serving_mesh: asked for {n_devices} devices but "
                f"only {len(devs)} exist (set "
                "--xla_force_host_platform_device_count before jax init)")
        devs = devs[:n_devices]
    n = len(devs)
    if model_parallel:
        if n % model_parallel:
            raise ValueError(
                f"make_serving_mesh: model_parallel={model_parallel} "
                f"does not divide {n} devices")
        model = model_parallel
    else:
        model = 2 if (n % 2 == 0 and n >= 4) else 1
    rest = n // model
    pod = 2 if rest % 4 == 0 else 1
    data = rest // pod
    arr = np.asarray(devs).reshape(pod, data, model)
    return jax.sharding.Mesh(arr, ("pod", "data", "model"))


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
