"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
init; smoke tests see the real single device.
"""

from __future__ import annotations

import re
import warnings

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    The installed JAX defaults to ``Explicit`` axes, under which every
    array carries its sharding in its type and GSPMD no longer propagates
    shardings through the serve programs (a dynamic-update-slice of a
    replicated cache with a data-sharded update then fails to trace).  The
    repo's sharding rules are written for propagation, so every mesh it
    builds goes through here.
    """
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``data`` (DP/FSDP), ``model`` (TP/EP/SP); ``pod`` composes with
    ``data`` for cross-pod data parallelism (DCN-friendly: only gradient
    all-reduces cross pods).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """``data x model`` mesh over whatever devices exist.

    A single-CPU-device process falls back to ``model_parallel=1`` (with a
    warning) so the same CLI invocation works on a laptop and under
    ``--xla_force_host_platform_device_count``.  On an accelerator a
    model-parallel request the devices cannot meet is a configuration
    error, and like any other indivisibility it raises (a ``ValueError``,
    not an assert — asserts vanish under ``python -O``).
    """
    n = len(jax.devices())
    if model_parallel != 1 and n == 1 and jax.default_backend() == "cpu":
        warnings.warn(f"make_host_mesh: only 1 device visible; falling back "
                      f"to model_parallel=1 (requested {model_parallel})",
                      stacklevel=2)
        model_parallel = 1
    if model_parallel < 1 or n % model_parallel != 0:
        raise ValueError(
            f"make_host_mesh: model_parallel={model_parallel} must be >= 1 "
            f"and divide the visible device count ({n} devices)")
    return auto_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


_MESH_SPEC_RE = re.compile(r"^(\d+)x(\d+)$")


def make_serve_mesh(spec: str, model_parallel: int = 1):
    """Mesh from a CLI spec: ``host`` (all devices / ``model_parallel``),
    ``pod`` / ``pod2`` (production v5e meshes), or an explicit ``DxM``
    (``2x2``, ``4x1``, ...) ``data x model`` grid over the visible devices.
    """
    if spec == "host":
        return make_host_mesh(model_parallel)
    if spec in ("pod", "pod2"):
        return make_production_mesh(multi_pod=spec == "pod2")
    m = _MESH_SPEC_RE.match(spec)
    if not m:
        raise ValueError(f"mesh spec {spec!r}: expected 'host', 'pod', "
                         f"'pod2', or 'DxM' (e.g. '2x2')")
    d, t = int(m.group(1)), int(m.group(2))
    n = len(jax.devices())
    if d * t > n:
        raise ValueError(f"mesh spec {spec!r} needs {d * t} devices but only "
                         f"{n} are visible (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={d * t} "
                         f"for a CPU smoke run)")
    return auto_mesh((d, t), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
