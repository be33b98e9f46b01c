"""kubernetes_tpu — a TPU-native cluster-scheduling framework.

Re-implements the capability surface of Kubernetes' kube-scheduler
(reference: pkg/scheduler in M00nF1sh/kubernetes @ 2024-10-08) as a batched
constraint-satisfaction and scoring system on TPU via JAX/XLA.

The reference schedules one pod per cycle, running a Filter→Score plugin
pipeline over all nodes with a 16-way host thread pool
(pkg/scheduler/schedule_one.go:65).  This framework instead:

- mirrors the scheduler cache snapshot (pkg/scheduler/backend/cache/snapshot.go)
  into HBM as packed, interned int/float tensors,
- evaluates every Filter/Score plugin as a vmapped kernel over a
  ``(pending_pods × nodes)`` problem,
- commits a whole batch of pods with a sequential-equivalent ``lax.scan``
  so decisions match the reference's serial assume/bind protocol.

Package layout:
    api/        core object model (Pod, Node, quantities, selectors)
    snapshot/   string interning + packed device tensor schema
    oracle/     scalar golden model of plugin semantics (for property tests)
    ops/        batched JAX kernels, one per device-backed plugin
    framework/  plugin interface: extension points, Status, CycleState, runtime
    plugins/    in-tree plugins (device-backed or host-backed)
    cache/      host cache with assume protocol + incremental device mirror
    queue/      activeQ/backoffQ/unschedulable queue with queueing hints
    config/     KubeSchedulerConfiguration-shaped profile/config surface
    metrics/    Prometheus-style metrics registry
    utils/      misc helpers
"""

__version__ = "0.1.0"

# The score kernels do exact integer arithmetic in int64 (emulated on TPU;
# float64 is never used, so TPU compatibility is preserved).  Without x64,
# packing real-world quantities (memory in bytes > 2^31) overflows at the
# jit boundary, so the requirement is enforced at import: a failure here
# is an error, not something to carry on from.
import os as _os

# The process's malloc policy (one glibc arena: util/allocator.py), set
# here because glibc fixes its arena limit once and keeps the arenas it
# has made: before the backend's thread pools, so before anything below
# can start one.
from kubernetes_tpu.util import allocator as _allocator

_allocator.engage()

import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

# Shape-stable counter-based PRNG: the seeded tie-break contract is that
# ``random.bits(fold_in(key, attempt), (n,))[i]`` depends only on
# (key, attempt, i) — the device pipeline draws over the PADDED node bucket
# (n_cap) while the serial oracle draws over the real node count, and the
# two must agree on the shared prefix.  The legacy threefry lowering blocks
# counters by total shape, so the prefix differs between the two widths
# where partitionable is off — pin it explicitly.
_jax.config.update("jax_threefry_partitionable", True)

# Persistent compilation cache: the wave/chain pipelines compile in minutes
# per (shape, static-args) variant on the TPU compiler, so executables are
# kept on disk for later processes.  Where JAX_COMPILATION_CACHE_DIR is set
# JAX reads it itself and nothing here sets a directory; where it is not,
# the cache lives at ONE fixed path inside the checkout — never a temp
# name, pid or time: a directory that moves between runs never hits.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )
