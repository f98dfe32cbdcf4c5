"""Cluster-spec environment injection.

Reference: ``SetClusterSpec`` in ``pkg/controller.v1/pytorch/pod.go``
(SURVEY.md §2 "Pod management"): inject MASTER_ADDR/MASTER_PORT/WORLD_SIZE/
RANK/PYTHONUNBUFFERED so c10d's ``env://`` rendezvous works; rank 0 is the
Master, worker i gets rank i+1.

TPU-native replacement (BASELINE.json:5): the same topology is expressed for
``jax.distributed.initialize`` — coordinator address, world size, process
id — and the device side from ``resources``: ``JAX_PLATFORMS`` pins the
platform, and a ``tpu_chips`` replica gets libtpu's own variables
(:func:`tpu_process_env`) so it opens exactly its chips. The legacy
MASTER_ADDR set is injected too, for parity and for torch-based workloads.

The init-container DNS gate of the reference (workers loop ``nslookup
$MASTER_ADDR``) is replaced by jax.distributed's built-in
connect-with-timeout retry (see runtime/rendezvous.py).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..api.types import ReplicaType, TPUJob


def replica_rank(rtype: ReplicaType, index: int) -> int:
    """Master → 0; Worker i → i+1 (reference rank assignment)."""
    return 0 if rtype == ReplicaType.MASTER else index + 1


# libtpu lays a host's chips out on a grid. A process that owns part of
# it is told its own extent (TPU_CHIPS_PER_PROCESS_BOUNDS) and how the
# processes sharing the host tile it (TPU_PROCESS_BOUNDS), both "x,y,z".
# (chips per process, processes) -> (chip bounds, process bounds), for
# the splits of a one-chip machine and of a 2x2 four-chip host.
_TPU_BOUNDS = {
    (1, 1): ("1,1,1", "1,1,1"),
    (4, 1): ("2,2,1", "1,1,1"),
    (1, 4): ("1,1,1", "2,2,1"),
}


def tpu_process_env(
    chips: int, process: int, processes: int, host: str, port_base: int
) -> Dict[str, str]:
    """libtpu's per-process variables for replica ``process`` of a gang of
    ``processes`` on ONE host, each owning ``chips`` chips.

    A process that opens libtpu takes every chip it can see, so without
    these the first replica of a gang holds the whole host and the rest
    fail or hang. ``TPU_VISIBLE_CHIPS`` gives each replica its own
    consecutive chips; the bounds say how chips and processes tile the
    host; and a gang is told where its peers' libtpu listen
    (``port_base + process``) so the processes form one slice. A split
    that is not in ``_TPU_BOUNDS`` gets its chips and nothing else:
    libtpu then lays it out, or refuses to, inside the replica."""
    first = process * chips
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(c) for c in range(first, first + chips)
        ),
    }
    bounds = _TPU_BOUNDS.get((chips, processes))
    if bounds is not None:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"], env["TPU_PROCESS_BOUNDS"] = bounds
    if processes > 1:
        ports = [port_base + i for i in range(processes)]
        env["CLOUD_TPU_TASK_ID"] = str(process)
        env["TPU_PROCESS_PORT"] = str(ports[process])
        env["TPU_PROCESS_ADDRESSES"] = ",".join(
            f"{host}:{p}" for p in ports
        )
    return env


def build_cluster_env(
    job: TPUJob,
    rtype: ReplicaType,
    index: int,
    *,
    num_processes: Optional[int] = None,
    coordinator_host: str = "127.0.0.1",
    status_dir: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
    trace_dir: Optional[str] = None,
    spool_dir: Optional[str] = None,
    rank: Optional[int] = None,
    coordinator_port: Optional[int] = None,
    resize_generation: Optional[int] = None,
) -> Dict[str, str]:
    """Build the injected environment for one replica process.

    ``num_processes`` overrides the spec's total (elastic re-rendezvous with
    a different world size); defaults to spec.total_replicas().
    ``rank``/``coordinator_port`` override the index-derived rank and the
    spec's port — a replica joining a RESIZED world (controller/elastic.py)
    takes its rank from the resize record's compacted map (survivor
    indices stay sparse, ranks must be dense) and the generation's own
    coordinator port. ``resize_generation`` stamps the world epoch this
    replica belongs to; the rendezvous layer fences it against newer
    resize records.
    """
    total = num_processes if num_processes is not None else job.spec.total_replicas()
    rank = replica_rank(rtype, index) if rank is None else rank
    port = coordinator_port if coordinator_port is not None else (job.spec.port or 23456)
    coordinator = f"{coordinator_host}:{port}"
    key = f"{job.metadata.namespace}/{job.metadata.name}"

    env: Dict[str, str] = {
        # ---- reference-parity set (c10d env:// rendezvous) ----
        "MASTER_ADDR": coordinator_host,
        "MASTER_PORT": str(port),
        "WORLD_SIZE": str(total),
        "RANK": str(rank),
        "PYTHONUNBUFFERED": "1",
        # ---- TPU-native set (jax.distributed) ----
        "TPUJOB_COORDINATOR_ADDRESS": coordinator,
        "TPUJOB_NUM_PROCESSES": str(total),
        "TPUJOB_PROCESS_ID": str(rank),
        # ---- job identity / bookkeeping ----
        "TPUJOB_NAME": job.metadata.name,
        "TPUJOB_NAMESPACE": job.metadata.namespace,
        "TPUJOB_KEY": key,
        "TPUJOB_REPLICA_TYPE": rtype.value,
        "TPUJOB_REPLICA_INDEX": str(index),
        "TPUJOB_RESTART_COUNT": str(job.status.restart_count),
        "TPUJOB_RESIZE_GENERATION": str(
            job.status.resize_generation
            if resize_generation is None
            else resize_generation
        ),
    }

    resources = job.spec.replica_specs[rtype].template.resources
    if resources.cpu_devices > 0:
        # Test/CI backend: virtual CPU devices (SURVEY.md §4).
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={resources.cpu_devices}"
        )
    elif resources.tpu_chips > 0:
        # The chip first: with no chip, or a chip another process holds,
        # backend creation raises in the replica — it never computes on
        # the CPU instead. The CPU backend rides along for host-side
        # weight init (workloads/generate.py --init-host). Injected env
        # is laid over the inherited one (controller/runner.py), so this
        # beats a JAX_PLATFORMS=cpu exported around the supervisor.
        env["JAX_PLATFORMS"] = "tpu,cpu"
        env.update(
            tpu_process_env(
                resources.tpu_chips, rank, total, coordinator_host, port + 1
            )
        )

    if status_dir is not None:
        env["TPUJOB_STATUS_DIR"] = status_dir
    if checkpoint_dir is not None:
        env["TPUJOB_CHECKPOINT_DIR"] = checkpoint_dir
    # Flight-recorder knob (obs/trace.py): with a per-job trace dir the
    # replica's step loop / device feed / rendezvous / async-checkpoint
    # spans land where `tpujob trace <job>` merges them. Explicitly
    # cleared otherwise — a supervisor tracing ITSELF must not leak its
    # own (root) trace dir into replicas via inherited environment.
    if trace_dir is not None:
        env["TPUJOB_TRACE_DIR"] = trace_dir
        # Ring sizing / flush cadence are spec knobs, not fixed
        # constants (obs/trace.py reads these once at tracer creation).
        ob = job.spec.observability
        if ob is not None:
            if ob.trace_ring_bytes > 0:
                env["TPUJOB_TRACE_RING_BYTES"] = str(ob.trace_ring_bytes)
            if ob.trace_flush_every > 0:
                env["TPUJOB_TRACE_FLUSH_EVERY"] = str(ob.trace_flush_every)
    else:
        env["TPUJOB_TRACE_DIR"] = ""
    # Live health-engine policy (spec.observability.alerts): evaluated
    # by the SUPERVISOR, but threaded into replicas like the trace
    # knobs so replica-side tooling (an in-container `tpujob why`, a
    # sidecar evaluating the same rules) resolves the identical bar.
    ob = job.spec.observability
    if ob is not None and ob.alerts is not None:
        import json as _json

        env["TPUJOB_ALERTS"] = _json.dumps(
            ob.alerts.to_dict(), sort_keys=True
        )
    # Serve plane (spec.serving): each serving replica gets its OWN
    # spool directory — the router's dispatch target for this replica —
    # so `workloads/serve.py --spool` needs no per-replica args
    # plumbing. The SLO block rides along as JSON for replica-side
    # tooling parity, like TPUJOB_ALERTS.
    if spool_dir is not None:
        env["TPUJOB_SPOOL_DIR"] = spool_dir
    sv = job.spec.serving
    if sv is not None:
        import json as _json

        env["TPUJOB_SERVING"] = _json.dumps(sv.to_dict(), sort_keys=True)
        # The transport tier rides its own var so the engine loop can
        # gate ring-attach on one string compare, no JSON parse.
        env["TPUJOB_SERVE_TRANSPORT"] = sv.transport
    # Auto-remediation policy (spec.remediation): acted on by the
    # SUPERVISOR, threaded into replicas like TPUJOB_ALERTS so
    # replica-side tooling resolves the identical policy.
    rm = job.spec.remediation
    if rm is not None:
        import json as _json

        env["TPUJOB_REMEDIATION"] = _json.dumps(
            rm.to_dict(), sort_keys=True
        )
    # A committed raise_ckpt_cadence remediation stamps this annotation;
    # workloads multiply their checkpoint frequency by it so the "write
    # more often" decision survives restarts (it rides the spec, not a
    # live signal).
    from ..controller.remediation import CKPT_CADENCE_ANNOTATION

    cadence = job.metadata.annotations.get(CKPT_CADENCE_ANNOTATION)
    if cadence:
        env["TPUJOB_CKPT_CADENCE_FACTOR"] = str(cadence)
    # Data-plane policy (spec.data_plane): workloads read these as the
    # defaults for --async-checkpoint / --prefetch, so host-I/O overlap
    # is a SPEC property, not per-workload args plumbing.
    dp = job.spec.data_plane
    if dp is not None:
        if dp.async_checkpoint:
            env["TPUJOB_ASYNC_CHECKPOINT"] = "1"
        if dp.prefetch > 0:
            env["TPUJOB_PREFETCH"] = str(dp.prefetch)
        if dp.prefetch_depth_max > 0:
            env["TPUJOB_PREFETCH_DEPTH_MAX"] = str(dp.prefetch_depth_max)
        if dp.autotune:
            env["TPUJOB_FEED_AUTOTUNE"] = "1"
        if dp.prefetch_workers > 0:
            env["TPUJOB_PREFETCH_WORKERS"] = str(dp.prefetch_workers)
    return env
