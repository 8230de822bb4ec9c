"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod),
axes (data, model).  Multi-pod: 2 x 16 x 16 = 512 chips with a leading
pure-DP 'pod' axis (DCN-connected pods; only gradient all-reduces cross
the pod boundary).
"""
from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    assert len(devices) >= n, (
        f"need {n} devices, have {len(devices)} — run under "
        f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dry run) "
        f"or on the full slice")
    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for CI-grade sharding tests (needs >= prod(shape) devices)."""
    n = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(dev, axes)


def _devices_for(n: int, devices=None) -> list:
    """The local device list, checked against the ``n`` devices a layout
    asks for.  Only the CPU backend may wrap a layout round-robin onto
    fewer devices (correctness is placement-independent, which is what
    lets a pipeline degenerate to one CPU device in tests); on an
    accelerator a short device list raises instead of silently stacking
    stages or replicas onto one chip."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) < n and devices[0].platform != "cpu":
        raise ValueError(
            f"layout needs {n} {devices[0].platform} devices, "
            f"{len(devices)} present")
    return devices


def pipeline_stage_devices(n_stages: int, devices=None) -> list:
    """Device list for the pipeline-parallel CNN serving path: one device
    per stage, in a 1-D 'stage' chain (the Fig 7 chip line re-expressed
    over local accelerators).  With fewer CPU devices than stages, stages
    wrap round-robin (see ``_devices_for``); fan a CPU host out to N
    devices with XLA_FLAGS=--xla_force_host_platform_device_count=N.
    """
    devices = _devices_for(n_stages, devices)
    return [devices[s % len(devices)] for s in range(n_stages)]


def replica_pipeline_devices(n_replicas: int, n_stages: int,
                             devices=None) -> list:
    """Disjoint per-replica device groups for the replicated serving
    front-end (serving/frontend.py): ``n_replicas`` independent stage
    chains of ``n_stages`` devices each, carved contiguously from the
    local device list — replica ``r`` owns devices
    ``[r*n_stages, (r+1)*n_stages)``, so no device (and no resident
    weight byte) is shared between replicas when ``n_replicas*n_stages``
    physical devices exist.  With fewer CPU devices the groups wrap
    round-robin, exactly like ``pipeline_stage_devices``, so the whole
    fleet degenerates to one CPU device in tests; an accelerator with
    too few devices raises.  Fan a CPU host out with
    XLA_FLAGS=--xla_force_host_platform_device_count=N.
    """
    assert n_replicas >= 1 and n_stages >= 1, (n_replicas, n_stages)
    devices = _devices_for(n_replicas * n_stages, devices)
    return [[devices[(r * n_stages + s) % len(devices)]
             for s in range(n_stages)] for r in range(n_replicas)]
