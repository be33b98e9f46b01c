"""Ahead-of-time TPU compiles of the main-path jit roots (no chip needed).

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (topology ``v5e:2x2``): what it refuses — an op or
layout the chip cannot run, a program that does not fit HBM, a donation it
cannot alias — fails here at no chip time.  The roots are compiled at the
bucket shapes ``chip_smoke.py`` dispatches (5000 nodes → the 5120 node
bucket), captured from a CPU rehearsal through the ledger's retained
``ShapeDtypeStruct`` buckets.  Nothing runs: a compile that passes is not a
chip run and says nothing about results or times.

Everything built from the topology lives in module-scoped fixtures of THIS
file (never at import, in a ``skipif``, in ``parametrize`` arguments or in
conftest): only the worker that runs this file may load libtpu, and it
keeps the library until it exits — so the compiles run in the test's own
process, and the file stays one file.

The cross-pod engine (``wave_run`` / ``chain_dispatch``) costs ~250 s of
TPU compile per (root, statics, bucket) variant at the smoke's width (5120
node bucket: wave_run 256 s, chain_dispatch 258 s; 283 s at the 1024
bucket — CHANGES.md PR 24), far past what tier-1 can afford, so it is
compiled here at a 16-node / 8-pod bucket (~57 s): same program, same
int64 scan bodies, small arrays.  Its real-width compile belongs to the
pre-chip rehearsal.  File wall ≈ 2.5 min on one worker (resident_run at the
5120 bucket is 62 s of it).
"""

import os
import time
import warnings

import pytest

SMOKE_NODES = 5000  # chip_smoke.FULL_NODES — the 5120 node bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A TPU executable compiled here is written to the persistent cache
    but cannot be read back without a chip (the next compile warns and
    recompiles) — keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def buckets():
    """{root: (args, kwargs) of ShapeDtypeStructs + statics} captured from
    single-chip CPU rehearsal drains at the smoke's node width."""
    from kubernetes_tpu.tools import paritycheck as pc

    nodes = pc._basic_nodes(SMOKE_NODES)
    out = {}

    def harvest(sched):
        for name, ks in sched.kernels._kstats.items():
            for b in ks.buckets.values():
                if b["spec"] is not None:
                    out.setdefault(name, b["spec"])

    # SchedulingBasic backlog → static_eval, resident_run, usage_checksum
    _, s = pc._drain(
        nodes,
        pc._basic_pods(2048),
        return_sched=True,
        mesh_dispatch=False,
    )
    harvest(s)
    # residentDrain off → the sig_scan pipeline
    _, s = pc._drain(
        nodes,
        pc._basic_pods(1024, seed=31),
        return_sched=True,
        mesh_dispatch=False,
        resident_drain=False,
    )
    harvest(s)
    # TopologySpreading at a tiny bucket → the cross-pod root (wave_run)
    from chip_smoke import spread_pods

    _, s = pc._drain(
        pc._basic_nodes(16, zones=8),
        spread_pods(8),
        return_sched=True,
        mesh_dispatch=False,
    )
    harvest(s)
    return out


def _compile(fn, spec, sharding):
    """Lower + compile ``fn`` at ``spec`` with every array leaf placed on
    ``sharding``; returns (compiled, seconds, donation warnings)."""
    import jax

    def place(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding
            )
        return leaf

    args, kwargs = jax.tree_util.tree_map(place, spec)
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = fn.lower(*args, **kwargs).compile()
    donation = [
        str(w.message) for w in caught if "donated" in str(w.message).lower()
    ]
    return compiled, time.perf_counter() - t0, donation


# root → must the compiled program alias donated inputs in place?  The
# resident design rests on in-place HBM state: a donating root whose
# alias_size is 0 (or that warns "donated buffers were not usable") copies
# its state every dispatch.
MAIN_PATH_ROOTS = [
    ("fastpath.static_eval", False),
    ("resident.usage_checksum", False),
    ("fastpath.sig_scan", True),
    ("resident.resident_run", True),
]


@pytest.mark.parametrize("root,donates", MAIN_PATH_ROOTS)
def test_main_path_root_compiles_for_v5e(
    root, donates, buckets, one_chip, no_persistent_cache
):
    from kubernetes_tpu.observability import kernels

    assert root in buckets, (
        f"the CPU rehearsal never dispatched {root}: {sorted(buckets)}"
    )
    compiled, secs, donation = _compile(
        kernels._wrapped_fn(root), buckets[root], one_chip
    )
    ma = compiled.memory_analysis()
    print(
        f"{root}: compiled for v5e in {secs:.1f}s, temp "
        f"{ma.temp_size_in_bytes} B, alias {ma.alias_size_in_bytes} B"
    )
    assert not donation, donation
    if donates:
        assert ma.alias_size_in_bytes > 0, (
            f"{root} donates its resident state but the TPU program "
            "aliases nothing — every dispatch would copy it"
        )
    # one v5e chip has 16 GB of HBM; the program's own temporaries must
    # leave room for the resident snapshot beside them
    assert ma.temp_size_in_bytes < 8 << 30


def test_cross_pod_root_compiles_for_v5e(
    buckets, one_chip, no_persistent_cache
):
    """The wave engine (speculation vmap + term-factored int64 admission
    scan) at the largest bucket that compiles in under a minute."""
    from kubernetes_tpu.observability import kernels

    assert "wave.wave_run" in buckets, sorted(buckets)
    compiled, secs, donation = _compile(
        kernels._wrapped_fn("wave.wave_run"),
        buckets["wave.wave_run"],
        one_chip,
    )
    ma = compiled.memory_analysis()
    print(
        f"wave.wave_run (16-node bucket): compiled for v5e in {secs:.1f}s, "
        f"temp {ma.temp_size_in_bytes} B"
    )
    assert not donation, donation


def test_mesh_placed_root_compiles_on_four_devices(
    buckets, topo, no_persistent_cache
):
    """static_eval with the snapshot and the signature batch placed by
    parallel/mesh.py's own sharding rules on a 2x2 ('pods','nodes') Mesh
    over the described devices — node-major tensors partitioned over
    'nodes', pod-major over 'pods' (the meshDispatch placement)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from kubernetes_tpu.observability import kernels
    from kubernetes_tpu.parallel import mesh as pmesh

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("pods", "nodes"))
    (dc, db), statics = buckets["fastpath.static_eval"]

    def place(tree, shardings):
        return jax.tree_util.tree_map(
            lambda leaf, sh: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sh
            ),
            tree,
            shardings,
        )

    pdc = place(dc, pmesh.cluster_shardings(mesh, dc))
    pdb = place(db, pmesh.batch_shardings(mesh, db))
    assert len(pdc.allocatable.sharding.device_set) == 4
    assert not pdc.allocatable.sharding.is_fully_replicated
    assert not pdb.valid.sharding.is_fully_replicated
    compiled = (
        kernels._wrapped_fn("fastpath.static_eval")
        .lower(pdc, pdb, **statics)
        .compile()
    )
    out_devices = {
        d
        for sh in jax.tree_util.tree_leaves(compiled.output_shardings)
        for d in sh.device_set
    }
    assert len(out_devices) == 4, out_devices
