"""Production mesh construction (deliverable e).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. The single-pod mesh is 16x16
(256 chips, one v5e pod); multi-pod adds a leading "pod"=2 axis (512 chips).
"pod" behaves as an outer data axis: gradient reduction is hierarchical
(reduce-scatter intra-pod over "data", all-reduce inter-pod over "pod"),
which XLA derives from the combined ("pod","data") batch sharding.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(model_axis: int | None = None):
    """A mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    m = model_axis or 1
    assert n % m == 0, (n, m)
    return _mk((n // m, m), ("data", "model"))


def make_model_mesh(n_shards: int):
    """The tensor-parallel serving mesh: ``n_shards`` devices on the
    "model" axis (sharded page store / streamed TP serving). Raises a
    clear error instead of the bare assert when the host cannot supply
    the shards (CI forces virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    n = len(jax.devices())
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n % n_shards:
        raise ValueError(
            f"n_shards={n_shards} needs a device count it divides; "
            f"{n} device(s) visible (on CPU, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards})")
    return make_host_mesh(model_axis=n_shards)


def data_axis_names(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


MODEL_AXIS = "model"
