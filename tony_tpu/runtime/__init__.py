"""Task-side runtime: consume the coordinator-exported environment.

The user-script-facing half of the runtime adapter. The reference exports
TF_CONFIG and the user script feeds it to ``tf.train.Server`` (reference:
tony-examples/mnist-tensorflow/mnist_distributed.py:190-227); here the
executor exports the ``TONY_JAX_*`` bootstrap (tony_tpu/cluster/executor.py)
and the user script calls :func:`initialize` + :func:`mesh`:

    import tony_tpu.runtime as rt
    rt.initialize()                 # jax.distributed bootstrap (no-op 1-proc)
    mesh = rt.mesh()                # Mesh over ALL devices, axes from config
    ...pjit/shard_map under `mesh`...

Works identically on a real TPU slice, on multi-process CPU (the fake-cluster
E2E path), and single-process (mesh over local devices).
"""

from __future__ import annotations

import json
import logging
import os
from dataclasses import dataclass

from tony_tpu import constants

log = logging.getLogger(__name__)

_initialized = False


@dataclass(frozen=True)
class TaskInfo:
    job_name: str
    task_index: int
    task_num: int
    session_id: int
    attempt: int
    process_id: int
    num_processes: int
    coordinator_address: str
    cluster_spec: dict

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def task_info() -> TaskInfo:
    """Parse the executor-exported environment (works outside tony too,
    defaulting to a single local process)."""
    spec = os.environ.get(constants.CLUSTER_SPEC, "")
    return TaskInfo(
        job_name=os.environ.get(constants.JOB_NAME, "worker"),
        task_index=int(os.environ.get(constants.TASK_INDEX, "0")),
        task_num=int(os.environ.get(constants.TASK_NUM, "1")),
        session_id=int(os.environ.get(constants.SESSION_ID, "0")),
        attempt=int(os.environ.get(constants.ATTEMPT_NUMBER, "0")),
        process_id=int(os.environ.get(constants.JAX_PROCESS_ID, "0")),
        num_processes=int(os.environ.get(constants.JAX_NUM_PROCESSES, "1")),
        coordinator_address=os.environ.get(constants.JAX_COORDINATOR_ADDRESS, ""),
        cluster_spec=json.loads(spec) if spec else {},
    )


def initialize() -> TaskInfo:
    """Bootstrap ``jax.distributed`` from the coordinator-assigned identity —
    the direct analog of the reference's TF_CONFIG consumption. Idempotent;
    no-op for single-process jobs and bare (non-tony) runs."""
    global _initialized
    info = task_info()
    if _initialized:
        return info
    if info.is_distributed and info.coordinator_address:
        import jax
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Multi-process CPU (the fake-cluster test path) needs an
            # explicit cross-process collectives implementation.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        log.info("jax.distributed.initialize(%s, num_processes=%d, process_id=%d)",
                 info.coordinator_address, info.num_processes, info.process_id)
        jax.distributed.initialize(
            coordinator_address=info.coordinator_address,
            num_processes=info.num_processes,
            process_id=info.process_id)
    from tony_tpu.runtime import compile_cache, profiler
    compile_cache.enable()
    profiler.maybe_start()
    _initialized = True
    return info


def platform_dtype():
    """The dtype the example entry points compute in: bfloat16 on a TPU
    (the MXU's native input), float32 elsewhere (the CPU tests compare
    against float32 references). One definition for all of them — and
    :func:`device_line` is how the choice reaches the log."""
    import jax
    import jax.numpy as jnp
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def device_line(dtype) -> str:
    """``platform tpu kind 'TPU v5 lite' devices 1 dtype bfloat16`` — what
    an entry point prints FIRST: dtype (and, in the ops, Mosaic or the
    interpreter) follows the platform, so a run that landed on the CPU
    must say so in its log rather than exit 0 looking like a chip run.
    Initialises the backend."""
    import jax
    import numpy as np
    d = jax.devices()
    return (f"platform {d[0].platform} kind {d[0].device_kind!r} "
            f"devices {len(d)} dtype {np.dtype(dtype).name}")


def mesh_axes() -> dict[str, int]:
    """The mesh layout shipped by the coordinator (tony.application.mesh),
    or {} when unset."""
    raw = os.environ.get(constants.MESH_SPEC, "")
    if not raw:
        return {}
    return json.loads(raw).get("axes", {})


def mesh_dcn_axes() -> dict[str, int]:
    """Cross-slice (DCN) mesh layout (tony.application.mesh.dcn), or {}
    for single-slice jobs."""
    raw = os.environ.get(constants.MESH_SPEC, "")
    if not raw:
        return {}
    return json.loads(raw).get("dcn_axes", {})


def slice_info() -> tuple[int, int]:
    """(slice_id, num_slices) of this host's gang — (0, 1) when the job
    type is single-slice (tony.{job}.slices unset or 1)."""
    return (int(os.environ.get(constants.SLICE_ID, "0")),
            int(os.environ.get(constants.NUM_SLICES, "1")))


def mesh(axes: dict[str, int] | None = None,
         axis_order: tuple[str, ...] | None = None,
         dcn_axes: dict[str, int] | None = None):
    """Build a ``jax.sharding.Mesh`` over ALL devices (all processes).

    ``axes`` defaults to the config-shipped layout; a single axis given as
    -1/0 is inferred from the global device count (so the layout scales with
    the slice). Returns a 1-axis ``("dp",)`` mesh when nothing is configured.
    When the job is multi-slice and DCN axes are configured
    (tony.application.mesh.dcn), the mesh is hybrid: dcn axes span slices,
    ici axes stay within a slice. Delegates to
    :mod:`tony_tpu.parallel.mesh` — one implementation of axis
    inference/ordering for the whole framework.
    """
    from tony_tpu.parallel.mesh import make_hybrid_mesh, make_mesh
    axes = axes if axes is not None else mesh_axes()
    dcn = dcn_axes if dcn_axes is not None else mesh_dcn_axes()
    if dcn:
        if axis_order is not None:
            # silently dropping the caller's order would remap their
            # PartitionSpecs onto the wrong axes
            raise ValueError("axis_order is not supported for hybrid "
                             "(multi-slice) meshes: the order is fixed to "
                             "dcn-major/ici-minor")
        return make_hybrid_mesh(axes, dcn)
    return make_mesh(axes, axis_order=axis_order)
