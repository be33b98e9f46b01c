"""The statics' spread aggregates by topology KEY (``gang.precompute``'s
spread half over ``common.compact_domain_stats``): a domain's total is a
masked sum over the key's compact node→domain map, which every row of that
key shares — never a segment sum over an index vector private to a
(pod, slot) row.

Pinned here: the four aggregate fields of ``GangStatics`` are, bit for bit,
what ``common.domain_stats`` (the segment form, the other callers'
implementation) gives, per pod and by signature, over seeded clusters whose
batches mix keys (zone, a rack key of hundreds of values, hostname), lack a
key on some nodes or in the tables, hold padded pods, invalid nodes and rows
with no eligible node, under unique AND duplicate hostname values; the
conditional path (the hostname key by its own map where hostnames repeat)
is counted by the loop; the lowered dispatch holds no scatter or gather
with an index a (row, node) pair.
"""

import copy
import dataclasses
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Container,
    LabelSelector,
    Node,
    Pod,
    Taint,
    TopologySpreadConstraint,
)
from kubernetes_tpu.ops import chain, common, filters as F, gang, wave
from kubernetes_tpu.ops.common import I32
from kubernetes_tpu.oracle.state import OracleState

from tests.test_wave import _drain_sched, _pack, _zone_nodes, run_serial

ZONE, RACK, HOST = "topology.kubernetes.io/zone", "example.com/rack", "kubernetes.io/hostname"
AGGREGATES = ("sp_dom_cnt", "sp_dom_pres", "sp_ndom", "sp_sc_dom")
APPS = ("web", "db", "cache", "batch")


# ---------------------------------------------------------------------------
# seeded clusters and batches
# ---------------------------------------------------------------------------

def _nodes(rng, n, n_racks, absent, dup_hosts):
    """``absent``: the share of nodes that lack each topology label (its own
    draw a key); ``dup_hosts``: that many nodes take another's hostname."""
    nodes = []
    for i in range(n):
        labels = {"disk": rng.choice(("ssd", "hdd"))}
        if rng.random() >= absent:
            labels[ZONE] = f"zone-{rng.randrange(3)}"
        if rng.random() >= absent:
            labels[RACK] = f"rack-{rng.randrange(n_racks)}"
        if rng.random() >= absent:
            labels[HOST] = f"node-{i}"
        taints = (Taint(key="dedicated", value="x", effect="NoSchedule"),) if rng.random() < 0.15 else ()
        nodes.append(Node(name=f"node-{i}", labels=labels, taints=taints,
                          capacity=Resource.from_map({"cpu": "16", "memory": "64Gi", "pods": 110})))
    named = [nd for nd in nodes if HOST in nd.labels]
    for nd in rng.sample(named, dup_hosts):
        nd.labels[HOST] = rng.choice([o for o in named if o is not nd]).labels[HOST]
    return nodes


def _placed(rng, nodes, n):
    return [Pod(name=f"e{i}", namespace=rng.choice(("default", "team-a")), labels={"app": rng.choice(APPS)},
                node_name=rng.choice(nodes).name,
                containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})])
            for i in range(n)]


def _pending(rng, n, keys, no_node_rows=0):
    """Pods of 1 to 3 constraints over ``keys``, hard and soft, each policy
    both ways; the first ``no_node_rows`` select a disk no node has, so with
    the Honor policy no node is eligible for their rows."""
    pods = []
    for i in range(n):
        tscs = tuple(
            TopologySpreadConstraint(
                max_skew=rng.randrange(1, 3), topology_key=key,
                when_unsatisfiable=rng.choice(("DoNotSchedule", "ScheduleAnyway")),
                label_selector=LabelSelector(match_labels={"app": rng.choice(APPS)}),
                node_affinity_policy="Honor" if i < no_node_rows else rng.choice(("Honor", "Ignore")),
                node_taints_policy=rng.choice(("Honor", "Ignore")))
            for key in rng.sample(keys, rng.randrange(1, min(3, len(keys)) + 1)))
        selector = {"disk": "tape"} if i < no_node_rows else ({"disk": "ssd"} if rng.random() < 0.3 else {})
        pods.append(Pod(name=f"p{i}", namespace=rng.choice(("default", "team-a")), labels={"app": rng.choice(APPS)},
                        node_selector=selector, topology_spread_constraints=tscs,
                        containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})]))
    return pods


# name: (nodes, racks, share of nodes without a label, duplicated hostnames, keys, pods, rows with no eligible node)
CASES = {
    "zone-and-hostname": (40, 4, 0.0, 0, (ZONE, HOST), 11, 0),
    "three-keys-rack-of-hundreds": (600, 400, 0.0, 0, (ZONE, RACK, HOST), 13, 0),
    "a-key-absent-on-some-nodes": (48, 6, 0.3, 0, (ZONE, RACK, HOST), 11, 0),
    "rows-with-no-eligible-node": (40, 4, 0.1, 0, (ZONE, HOST), 11, 4),
    "duplicate-hostnames": (48, 6, 0.1, 9, (ZONE, HOST), 11, 0),
    "duplicate-hostnames-rack-of-hundreds": (600, 400, 0.05, 40, (ZONE, RACK, HOST), 13, 2),
}


def _case(name, seed):
    n, n_racks, absent, dups, keys, n_pods, no_node = CASES[name]
    rng = random.Random(seed)
    nodes = _nodes(rng, n, n_racks, absent, dups)
    state = OracleState.build(nodes, _placed(rng, nodes, 3 * n))
    return _pack(state, _pending(rng, n_pods, list(keys), no_node))


def _segment_form(dc, db, v_cap):
    """The four fields as the parent commit computed them: ``domain_stats``
    over the label-value ids, a private segment-id vector a row."""
    node_affinity = F.mask_node_affinity(dc, db)
    taints = F.mask_taints(dc, db, F._tolerated(dc, db))
    spre = F.spread_precompute(dc, db, node_affinity, taints)
    N = dc.node_valid.shape[0]
    cnt_n = common.per_node_counts(spre.sel_match.astype(I32), dc.epod_node, N)
    te = spre.tracked[:, None, :] & spre.eligible
    dom_tot, dom_pres, _, n_dom = common.domain_stats(jnp.where(te, cnt_n, 0), te, spre.dv, v_cap)
    soft = spre.exists & ~db.tsc_hard
    all_keys = jnp.all(~soft[:, :, None] | (spre.dv >= 0), axis=1)
    counting = all_keys[:, None, :] & spre.eligible
    sc_dom, _, _, _ = common.domain_stats(jnp.where(counting, cnt_n, 0), counting, spre.dv, v_cap)
    return dict(sp_dom_cnt=jnp.where(dom_pres, dom_tot, 0), sp_dom_pres=dom_pres, sp_ndom=n_dom,
                sp_sc_dom=jnp.where(spre.dv >= 0, sc_dom, 0))


def _assert_aggregates(dc, db, pb, hostname_key, v_cap, tables, by_sig):
    sig_kw = {}
    if by_sig:
        ss = wave.static_signatures(pb, u_cap=pb.valid.shape[0])
        sig_kw = dict(sig=ss["sig"], rep_pod=ss["rep_pod"])
    got = _precompute(dc, db, hostname_key, v_cap, tables, sig_kw)
    want = jax.jit(_segment_form, static_argnums=2)(dc, db, v_cap)
    for name in AGGREGATES:
        a, b = np.asarray(getattr(got, name)), np.asarray(want[name])
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.array_equal(a, b), (name, np.argwhere(a != b)[:5])
    return got


def _precompute(dc, db, hostname_key, v_cap, tables, sig_kw):
    d_cap = tables.get("d_cap")
    arrays = {k: v for k, v in tables.items() if k != "d_cap"}
    return jax.jit(lambda dc, db, hk, arrays, kw: gang.precompute(
        dc, db, hk, v_cap, has_interpod=False, has_ports=False, has_images=False, d_cap=d_cap, **arrays, **kw)
    )(dc, db, hostname_key, arrays, sig_kw)


# ---------------------------------------------------------------------------
# (a) the property: the same integers as the segment form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("by_sig", [False, True], ids=["per-pod", "by-signature"])
@pytest.mark.parametrize("seed", [7, 4900000021])
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_aggregates_by_key_are_the_segment_forms_integers(name, seed, by_sig):
    _vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _case(name, seed)
    n, n_racks, _absent, dups, keys, n_pods, no_node = CASES[name]
    # the case is the shape it says: padded pods, invalid nodes, the bucket
    # over the rack key's hundreds of values, the hostname map only where
    # hostnames repeat
    assert pb.valid.sum() == n_pods < pb.valid.shape[0]
    assert np.asarray(dc.node_valid).sum() == n < dc.node_valid.shape[0]
    assert tables["d_cap"] == (512 if RACK in keys and n_racks > 256 else 8)
    assert (tables["sp_host_cdv"] is not None) == bool(dups)
    got = _assert_aggregates(dc, db, pb, hostname_key, v_cap, tables, by_sig)
    ndom = np.asarray(got.sp_ndom)
    slots = np.asarray(pb.tsc_topo_key)[: n_pods] >= 0
    assert (ndom[:n_pods][slots] > 0).any() and not ndom[n_pods:].any()
    if no_node:
        assert not ndom[:no_node].any() and not np.asarray(got.sp_dom_pres)[:no_node].any()


@pytest.mark.parametrize("seed", [3, 5])
def test_a_key_absent_from_the_batchs_tables_reads_no_domain(seed):
    """A topology key past the node table's label columns (interned after
    the pack): ``batch_tables`` lists no such key, every node lacks it, and
    both forms read 0 domains for its rows."""
    _vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, _tables = _case("zone-and-hostname", seed)
    K = pc.nodes.label_vals.shape[1]
    topo = np.asarray(pb.tsc_topo_key).copy()
    rows = np.argwhere(topo[:, 0] >= 0)[:3, 0]
    topo[rows, 0] = K + 2
    pb = dataclasses.replace(pb, tsc_topo_key=topo)
    db = common.DeviceBatch.from_host(pb)
    tables = gang.batch_tables(pb.tsc_topo_key, pb.aff_topo_key, pc.nodes.label_vals, hk_id)
    assert K + 2 not in np.asarray(tables["sp_keys"])
    got = _assert_aggregates(dc, db, pb, hostname_key, v_cap, tables, by_sig=False)
    assert not np.asarray(got.sp_ndom)[rows, 0].any() and not np.asarray(got.sp_sc_dom)[rows, 0].any()


@pytest.mark.parametrize("name", ["zone-and-hostname", "duplicate-hostnames"])
def test_without_the_bucket_the_node_count_bounds_the_ids(name):
    """``d_cap=None`` (a caller that popped it): the same integers."""
    _vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _case(name, 11)
    tables.pop("d_cap")
    _assert_aggregates(dc, db, pb, hostname_key, v_cap, tables, by_sig=False)


@pytest.mark.parametrize("d_cap", [8, 512])
def test_compact_domain_stats_is_domain_stats_on_compact_ids(d_cap):
    """The helper alone, lead axes and all: ids in [0, d_cap), some absent,
    a row with nothing present."""
    rng = np.random.default_rng(d_cap)
    lead, N = (5, 3), 700
    cdv = rng.integers(-1, d_cap, size=lead + (N,)).astype(np.int32)
    pres = rng.random(lead + (N,)) < 0.6
    pres[0, 0] = False
    cnt = np.where(pres, rng.integers(0, 1 << 14, size=lead + (N,)), 0).astype(np.int32)
    tot, dpres, ndom = jax.jit(common.compact_domain_stats, static_argnums=3)(cnt, pres, cdv, d_cap)
    want_tot, want_pres, _, want_ndom = jax.jit(common.domain_stats, static_argnums=3)(cnt, pres, cdv, d_cap)
    assert np.array_equal(np.asarray(tot), np.where(cdv >= 0, np.asarray(want_tot), 0))
    assert np.array_equal(np.asarray(dpres), np.asarray(want_pres))
    assert np.array_equal(np.asarray(ndom), np.asarray(want_ndom)) and not np.asarray(ndom)[0, 0]
    # integer sums: the existing-pod capacity bounds a total well inside int32
    assert np.asarray(tot).dtype == np.int32 and np.asarray(tot).max() > (1 << 14)


# ---------------------------------------------------------------------------
# (b) the conditional path is counted, and decides as the serial oracle
# ---------------------------------------------------------------------------

def _hostname_spread_pods(n):
    return [Pod(name=f"p{i}", labels={"app": "one"},
                topology_spread_constraints=(
                    TopologySpreadConstraint(max_skew=1, topology_key=HOST, when_unsatisfiable="ScheduleAnyway",
                                             label_selector=LabelSelector(match_labels={"app": "one"})),
                    TopologySpreadConstraint(max_skew=1, topology_key=ZONE, when_unsatisfiable="DoNotSchedule",
                                             label_selector=LabelSelector(match_labels={"app": "one"}))),
                containers=[Container(name="c", requests={"cpu": "100m", "memory": "64Mi"})])
            for i in range(n)]


@pytest.mark.parametrize("impostors", [0, 2], ids=["unique-hostnames", "duplicate-hostnames"])
def test_the_loop_counts_a_dispatch_that_sums_the_hostname_key_by_its_map(impostors):
    nodes = _zone_nodes(6)
    for i in range(impostors):
        nodes.append(Node(name=f"impostor-{i}", labels={ZONE: f"zone-{i}", HOST: f"node-{i}"},
                          capacity=Resource.from_map({"cpu": "8", "memory": "32Gi", "pods": 110})))
    pods = _hostname_spread_pods(16)
    want = run_serial(OracleState.build(nodes), copy.deepcopy(pods))
    got, s = _drain_sched(nodes, pods, wave=True)
    assert [got.get(p.name) for p in pods] == want
    counted = s.phases.snapshot().get("statics.host_by_domain", 0)
    assert counted == s.prom.statics_host_by_domain.value()
    if impostors:
        assert counted >= 1 and s.metrics["wave_batches"] == 0
    else:
        assert counted == 0 and s.metrics["wave_batches"] >= 1


# ---------------------------------------------------------------------------
# (c) the lowered dispatch: no index a (row, node) pair
# ---------------------------------------------------------------------------

def indexed_by_row_and_node(text, rows, n_nodes):
    """The scatter and gather ops of a lowered text under
    ``ktpu/gang/precompute`` whose index operand holds one index for each
    (row, node) pair — ``rows`` index vectors of ``n_nodes`` entries."""
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def scope(ref, depth=0):
        body = locs.get(ref, "")
        return body if depth > 8 else re.sub(r"#loc\d+", lambda m: scope(m.group(0), depth + 1), body)

    found = []
    for m in re.finditer(r'"stablehlo\.(scatter|gather)"\(', text):
        sig = re.search(r" : \(([^)]*)\) -> ", text[m.start():])
        loc = re.search(r"loc\((#loc\d+)\)", text[m.start() + sig.end():])
        operands = re.findall(r"tensor<([^>]*)>", sig.group(1))
        if "ktpu/gang/precompute" in scope(loc.group(1)) and operands[1].startswith(f"{rows}x{n_nodes}x"):
            found.append((m.group(1), operands[1]))
    return found


def test_the_lowered_per_pod_dispatch_indexes_nothing_by_row_and_node():
    """``chain_dispatch`` lowered for a per-pod (``sig=None``) spread batch:
    the parent held six such ops (``domain_stats``' two segment reductions and
    two read-backs, and the second call's pair); ``per_node_counts``' scatter,
    whose indices every row shares, stays."""
    _vocab, pc, pb, dc, db, v_cap, hk_id, hostname_key, tables = _case("zone-and-hostname", 7)
    wt = wave.wave_tables(pb, pc.nodes.label_vals, hk_id)
    P, C = pb.tsc_topo_key.shape
    N = dc.node_valid.shape[0]
    assert len({P * C, N, P, dc.epod_node.shape[0]}) == 4  # each axis tells itself apart in a shape
    lowered = chain.chain_dispatch.lower(
        dc, db, hostname_key, jnp.asarray(0, I32), jnp.asarray(0, I32), v_cap, wave=True,
        tid_sp=wt["tid_sp"], rep_sp_p=wt["rep_sp_p"], rep_sp_c=wt["rep_sp_c"], tid_ip=wt["tid_ip"],
        rep_ip_p=wt["rep_ip_p"], rep_ip_u=wt["rep_ip_u"], ip_cdv_tab=wt["ip_cdv_tab"], d2_cap=wt["d2_cap"],
        tid_pt=wt["tid_pt"], port_conf=wt["port_conf"], has_interpod=False, has_ports=False, append_terms=False,
        **tables)
    text = lowered.as_text(debug_info=True)
    assert indexed_by_row_and_node(text, P * C, N) == []
    # the reader finds the form it guards against where it is: the segment
    # form lowered beside it, under the same scope
    def segment_form(dc, db):
        with jax.named_scope("ktpu/gang/precompute"):
            return _segment_form(dc, db, v_cap)
    old = jax.jit(segment_form).lower(dc, db).as_text(debug_info=True)
    assert sorted(k for k, _ in indexed_by_row_and_node(old, P * C, N)) == ["gather"] * 3 + ["scatter"] * 3
    assert "stablehlo.scatter" in text, "per_node_counts' scatter left the program"
