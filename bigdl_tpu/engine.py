"""Engine — runtime topology initialisation.

Parity: ``utils/Engine.scala`` (339: ``Engine.init(node, cores, onSpark)``,
``coreNumber()``, ``nodeNumber()``, the ``default``/``model`` thread pools,
``checkSingleton``).

TPU-native redesign (SURVEY.md section 7): thread pools disappear — XLA owns
intra-op parallelism — and ``Engine.init`` becomes **device mesh
construction**.  ``nodeNumber`` maps to the size of the data-parallel mesh
axis; ``coreNumber`` maps to per-device batch capacity (kept for API
compatibility; XLA decides actual core usage).  The mesh is 1-D ("data") by
default, with room for 2-D data x model axes — the forward-looking extension
point the reference lacks (SURVEY.md section 2.7).

``check_singleton`` survives as a per-process guard against double
initialisation with conflicting topologies (the analogue of the reference's
two-tasks-in-one-executor oversubscription check,
``utils/Engine.scala:219-230``).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

import jax
import numpy as np

logger = logging.getLogger("bigdl_tpu.engine")


class Engine:
    _mesh: Optional["jax.sharding.Mesh"] = None
    _lock = threading.Lock()
    _node_number = 1
    _core_number = 1

    DATA_AXIS = "data"
    MODEL_AXIS = "model"

    @classmethod
    def init(cls, node_number: Optional[int] = None,
             core_number: Optional[int] = None,
             model_parallel: int = 1,
             mesh_shape=None) -> "jax.sharding.Mesh":
        """Build the global device mesh.

        ``mesh_shape`` (or the ``BIGDL_TPU_MESH`` environment variable —
        see ``parallel/mesh.py`` for the spec syntax) builds the named
        3-axis ``(data, fsdp, tp)`` trainer mesh; without either, the
        legacy ``(data, model)`` layout is kept (node_number defaults to
        devices / model_parallel).  Re-initialising with a different
        topology raises (checkSingleton semantics).
        """
        import os

        from jax.sharding import Mesh

        devices = jax.devices()
        n_dev = len(devices)
        legacy_args = node_number is not None or model_parallel != 1
        if mesh_shape is not None and legacy_args:
            # two EXPLICIT topology sources disagreeing is the bug
            # checkSingleton exists to catch; the env variable alone is
            # only a deployment default and loses to API arguments below
            raise ValueError(
                "pass EITHER mesh_shape or node_number/model_parallel, "
                "not both")
        if mesh_shape is not None or \
                (os.environ.get("BIGDL_TPU_MESH") and not legacy_args):
            from bigdl_tpu.parallel import mesh as mesh_mod
            shape = mesh_mod.mesh_shape(mesh_shape, n_devices=n_dev)
            with cls._lock:
                if cls._mesh is not None:
                    have = dict(cls._mesh.shape)
                    if have != shape.as_dict():
                        raise RuntimeError(
                            f"Engine already initialised with topology "
                            f"{have}, requested {shape.as_dict()} "
                            "(checkSingleton)")
                    return cls._mesh
                cls._mesh = mesh_mod.build_mesh(shape, devices=devices)
                cls._node_number = shape.data * shape.fsdp
                cls._core_number = core_number or 1
                logger.info("Engine initialised: mesh %s over %d devices",
                            dict(cls._mesh.shape), n_dev)
                return cls._mesh
        if node_number is None:
            node_number = n_dev // model_parallel
        want = (node_number, model_parallel)
        with cls._lock:
            if cls._mesh is not None:
                have = (cls._mesh.shape[cls.DATA_AXIS],
                        cls._mesh.shape.get(cls.MODEL_AXIS, 1))
                if have != want:
                    raise RuntimeError(
                        f"Engine already initialised with topology {have}, "
                        f"requested {want} (checkSingleton)")
                return cls._mesh
            assert node_number * model_parallel <= n_dev, \
                f"requested {node_number}x{model_parallel} mesh but only " \
                f"{n_dev} devices are visible"
            grid = np.asarray(
                devices[:node_number * model_parallel]).reshape(
                node_number, model_parallel)
            cls._mesh = Mesh(grid, (cls.DATA_AXIS, cls.MODEL_AXIS))
            cls._node_number = node_number
            cls._core_number = core_number or 1
            logger.info("Engine initialised: mesh %s over %d devices",
                        dict(cls._mesh.shape), n_dev)
            return cls._mesh

    @classmethod
    def mesh(cls) -> "jax.sharding.Mesh":
        if cls._mesh is None:
            cls.init()
        return cls._mesh

    @classmethod
    def node_number(cls) -> int:
        return cls._node_number

    @classmethod
    def core_number(cls) -> int:
        return cls._core_number

    @classmethod
    def init_multihost(cls, coordinator_address: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None,
                       model_parallel: int = 1) -> "jax.sharding.Mesh":
        """Multi-host (pod / DCN) topology init.

        The reference's cluster bring-up is ``Engine.init(node, cores,
        onSpark=true)`` building a SparkContext over executors
        (``utils/Engine.scala:318-352``); the TPU-native equivalent is
        ``jax.distributed.initialize`` (controller discovery via TPU
        metadata when args are None) followed by a global mesh over ALL
        hosts' devices.  Per-host input sharding is
        ``dataset.seqfile.host_shard_paths`` /
        ``DistributedDataSet.shard_iterators`` — data is partitioned by
        host exactly like the reference's locality-pinned RDD partitions.

        On a single host this is a no-op wrapper around ``init()``.
        """
        if coordinator_address is not None or \
                (num_processes is not None and num_processes > 1):
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        else:
            # no-args case: let jax auto-discover the pod topology from
            # the TPU metadata; on plain single-host/CPU environments (or
            # when already initialised) this raises and we proceed local
            try:
                jax.distributed.initialize()
            except Exception as e:  # noqa: BLE001 — backend-specific types
                if cls._distributed_already_up():
                    # a prior initialize() (user-driven or a re-run of
                    # this method) is a fine state — keep going
                    logger.info("jax.distributed already initialised; "
                                "reusing the existing runtime")
                elif cls._env_says_multihost():
                    # fail CLOSED: on a real pod a silent single-host
                    # fallback trains N independent models (the failure
                    # mode the reference guards with
                    # minRegisteredResourcesRatio=1.0,
                    # ``utils/Engine.scala:331``)
                    raise RuntimeError(
                        "jax.distributed.initialize() failed but the "
                        "environment indicates a multi-host pod "
                        f"({cls._env_says_multihost()}). Refusing to "
                        "continue single-host — every host would train "
                        "an independent model. Pass coordinator_address/"
                        "num_processes/process_id explicitly or fix the "
                        "pod metadata.") from e
                else:
                    logger.warning(
                        "jax.distributed.initialize() failed (%s); "
                        "continuing SINGLE-HOST. If this is a multi-host "
                        "pod this is wrong — every host would train "
                        "independently; pass coordinator_address/"
                        "num_processes/process_id explicitly.", e)
        return cls.init(model_parallel=model_parallel)

    @staticmethod
    def _distributed_already_up() -> bool:
        return bool(jax.distributed.is_initialized())

    @staticmethod
    def _env_says_multihost() -> Optional[str]:
        """Name of the first env signal indicating a multi-host pod, or
        None.  These are the knobs the TPU runtime / launcher sets on pod
        slices; any of them present means single-host is the wrong
        fallback."""
        import os
        if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
            return "MEGASCALE_COORDINATOR_ADDRESS"
        if os.environ.get("JAX_COORDINATOR_ADDRESS"):
            return "JAX_COORDINATOR_ADDRESS"
        try:
            if int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1:
                return "JAX_NUM_PROCESSES"
        except ValueError:
            pass
        hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        if "," in hosts:
            return "TPU_WORKER_HOSTNAMES"
        return None

    @classmethod
    def process_index(cls) -> int:
        return jax.process_index()

    @classmethod
    def process_count(cls) -> int:
        return jax.process_count()

    @classmethod
    def reset(cls) -> None:
        """Test hook — tears down the singleton (the reference resets via
        new JVMs between Serial-tagged specs)."""
        with cls._lock:
            cls._mesh = None
            cls._node_number = 1
            cls._core_number = 1
