"""Production meshes.  Functions, not constants — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod meshes: single pod (16,16) data×model (256 chips);
    multi-pod (2,16,16) pod×data×model (512 chips).

    On the host-platform dry-run there are 512 placeholder devices; the
    single-pod mesh uses the first 256 (jax.make_mesh requires an exact
    device count, so we fall back to an explicit subset when needed).
    """
    import numpy as np
    from jax.sharding import Mesh
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes)
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    return Mesh(np.array(devs[:n]).reshape(shape), axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()[: data * model]
    return Mesh(np.array(devs).reshape(data, model), ("data", "model"))


def make_candidate_mesh(n_devices: int | None = None):
    """1-D mesh over local devices for BCD candidate-parallel evaluation.

    The candidate axis of a stacked mask tree shards over ``"cand"``
    (a meshed core.engine.PipelinedEvaluator); params/data replicate.
    Works on any device count including 1.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    assert n <= len(devs), f"need {n} devices, have {len(devs)}"
    return Mesh(np.array(devs[:n]), ("cand",))


def make_cand_batch_mesh(cand: int | None = None, batch: int | None = None):
    """2-D ``("cand", "batch")`` mesh for joint candidate×batch BCD eval.

    A pure candidate layout idles ``n_devices - RT`` devices whenever a trial
    chunk has fewer candidates than the mesh has devices; this mesh lets
    ``core.engine.PipelinedEvaluator`` shard small chunks over ``"cand"`` while
    a batch-sharded evaluator context splits each candidate's forward over
    ``"batch"`` (big chunks still shard jointly over both axes — the spec is
    chosen per call).  Give either factor; the other defaults to using every
    local device.  ``batch`` must divide the eval-batch leading dim.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    n = len(devs)
    if cand is None and batch is None:
        cand, batch = n, 1
    elif cand is None:
        cand = n // batch
    elif batch is None:
        batch = n // cand
    assert cand >= 1 and batch >= 1, (cand, batch)
    assert cand * batch <= n, \
        f"need {cand}x{batch} devices, have {n}"
    return Mesh(np.array(devs[:cand * batch]).reshape(cand, batch),
                ("cand", "batch"))


def dp_axes(mesh) -> tuple:
    """Mesh axes that carry data parallelism (batch sharding)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def process_info() -> tuple:
    """``(process_index, process_count)`` of this host in the jax job.

    The bridge between jax's multi-process runtime and
    :mod:`repro.launch.coordinator`: on a real cluster
    (``jax.distributed.initialize``) a launcher maps these onto
    ``REPRO_COORD_RANK``/``REPRO_COORD_WORLD``; single-process runs get
    ``(0, 1)``.  Calling this initializes jax's backend, so launch-time code
    should consult the coordinator env vars first (coordinator.from_env)
    and fall back here only when it actually needs device state.
    """
    return int(jax.process_index()), int(jax.process_count())
