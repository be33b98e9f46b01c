"""Device half of the fast commit path: per-SIGNATURE static evaluation.

The gang scan (ops/gang.py) is sequential-equivalent but pays one scan step
per pod.  For batches whose only batch-dynamic constraints are resources
(no inter-pod terms, no spread constraints, no host ports, no nominations),
pods collapse into a handful of SIGNATURES (identical requests + static
constraints), and the per-pod work factors as

    total(p, n) = static(sig(p), n) + dynamic_resources(state(n), sig(p))

This module evaluates the static half ONCE per signature on device —
[S, N] instead of [P, N] with S ~ 10 — and ships it to the host, where
kubernetes_tpu.fastpath replays the exact sequential greedy with integer
score math identical to the kernels.  Mirrors the role of
findNodesThatFitPod's static predicate subset (schedule_one.go:460) without
the per-pod loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import filters as F
from kubernetes_tpu.ops import scores as S
from kubernetes_tpu.ops.common import usage_carry_update
from kubernetes_tpu.snapshot.schema import LANE_CPU, LANE_MEM, N_FIXED_LANES

MAX = 100  # MaxNodeScore
I32 = jnp.int32
I64 = jnp.int64

# shard-rule roster: the sequential-equivalent argmax commit is the
# serial core — per-step first-max argmax over all N nodes plus the
# chosen node's gather; inherently a full-width collective per pod
_KTPU_N_COLLECTIVES = {
    "make_sig_step.step": "resolved(collective): per-pod argmax/gather "
    "over the full node axis (selectHost first-max semantics) — the "
    "packed (score, first-max-index) key all-reduces across node shards "
    "(index tiebreak keeps first-max exact); the committed node's rank-1 "
    "usage update stays local to the owning shard",
}


# ktpu: axes(dc=DeviceCluster, db=DeviceBatch)
# ktpu: static(enabled=("NodeName", "NodeUnschedulable", "TaintToleration", "NodeAffinity"), has_images=True)
@functools.partial(jax.jit, static_argnames=("enabled", "has_images"))
def static_eval(dc, db, enabled: frozenset, has_images: bool):
    """Static filters + raw static scores for a representative batch.

    Returns dict of [S, N] arrays:
      mask        — statics-feasible (node valid, name, unschedulable,
                    taints, node affinity)
      m_taints / m_nodeaff / m_nodename / m_unsched — per-kernel masks
                    (failure diagnosis)
      taint_raw / naff_raw — raw score inputs (the host verifies they are
                    CONSTANT over the feasible set, which makes their
                    normalized contribution argmax-neutral)
      img         — ImageLocality contribution (already weight-free raw,
                    no normalization pass in the reference)
    """
    with jax.named_scope("ktpu/fastpath/static_eval"):
        P = db.valid.shape[0]
        N = dc.node_valid.shape[0]
        true_pn = jnp.ones((P, N), bool)
        tolerated = F._tolerated(dc, db)
        m_nodename = F.mask_node_name(dc, db) if "NodeName" in enabled else true_pn
        m_unsched = (
            F.mask_unschedulable(dc, db)
            if "NodeUnschedulable" in enabled
            else true_pn
        )
        m_taints = (
            F.mask_taints(dc, db, tolerated)
            if "TaintToleration" in enabled
            else true_pn
        )
        m_nodeaff = (
            F.mask_node_affinity(dc, db) if "NodeAffinity" in enabled else true_pn
        )
        mask = (
            dc.node_valid[None, :]
            & db.valid[:, None]
            & m_nodename
            & m_unsched
            & m_taints
            & m_nodeaff
        )
        taint_raw = S.score_taint_toleration(dc, db)
        naff_raw = S.score_node_affinity(dc, db)
        img = (
            S.score_image_locality(dc, db)
            if has_images
            else jnp.zeros((P, N), jnp.int64)
        )
        return {
            "mask": mask,
            "m_nodename": m_nodename,
            "m_unsched": m_unsched,
            "m_taints": m_taints,
            "m_nodeaff": m_nodeaff,
            "taint_raw": taint_raw,
            "naff_raw": naff_raw,
            "img": img,
        }


# ---------------------------------------------------------------------------
# Device half of the COMMIT loop: the sequential-equivalent greedy as a
# lax.scan over signature ids.  The step builder is module-level so the
# resident drain loop (ops/resident.py) replays the EXACT same verdict
# code for its serial-fallback tail — one implementation, two kernels.
# ---------------------------------------------------------------------------


def make_sig_step(
    sig_req,
    sig_nz,
    sig_allzero,
    sig_ok,
    sig_img,
    alloc,
    allowed,
    w_fit: int,
    w_bal: int,
    w_img: int,
    check_fit: bool,
):
    """Build the one-pod greedy step ``(carry, sig_id) -> (carry, choice)``
    over carried node-usage state ``(used, nz0, nz1, num_pods)`` — the
    sequential-equivalent argmax commit shared by sig_scan and the
    resident loop's tail.  Integer score/feasibility math is bit-identical
    to kubernetes_tpu.fastpath.FastCommitter (property-tested)."""
    R = alloc.shape[1]
    N = alloc.shape[0]
    a0 = alloc[:, LANE_CPU]
    a1 = alloc[:, LANE_MEM]
    h0 = a0 > 0
    h1 = a1 > 0
    fit_w = h0.astype(I64) + h1.astype(I64)
    den_bal = jnp.maximum(a0 * a1, 1)
    ext_lane = jnp.arange(R) >= N_FIXED_LANES  # bool [R]

    def step(carry, s):
        with jax.named_scope("ktpu/fastpath/sig_step"):
            used, nz0, nz1, num_pods = carry
            active = s >= 0
            sc = jnp.maximum(s, 0)
            req = sig_req[sc]  # [R]
            snz0 = sig_nz[sc, 0]
            snz1 = sig_nz[sc, 1]
            ok = sig_ok[sc]  # [N]

            # ---- feasibility (fastpath.FastCommitter.feasible_int) ----
            if check_fit:
                fits_count = num_pods + 1 <= allowed
                avail = alloc - used  # [N, R]
                lane_ok = jnp.where(
                    (ext_lane & (req == 0))[None, :], True, req[None, :] <= avail
                )
                fits_lanes = jnp.where(
                    sig_allzero[sc], True, jnp.all(lane_ok, axis=1)
                )
                feas = ok & fits_count & fits_lanes
            else:
                feas = ok

            # ---- integer score (fastpath.FastCommitter.score_int) ----
            total = jnp.zeros((N,), I64)
            if w_fit:
                c0 = nz0 + snz0
                c1 = nz1 + snz1
                f0 = jnp.where(c0 > a0, 0, (a0 - c0) * MAX // jnp.maximum(a0, 1))
                f1 = jnp.where(c1 > a1, 0, (a1 - c1) * MAX // jnp.maximum(a1, 1))
                least = jnp.where(
                    fit_w > 0,
                    (jnp.where(h0, f0, 0) + jnp.where(h1, f1, 0))
                    // jnp.maximum(fit_w, 1),
                    0,
                )
                total = total + w_fit * least
            if w_bal:
                r0 = jnp.minimum(used[:, LANE_CPU] + req[LANE_CPU], a0)
                r1 = jnp.minimum(used[:, LANE_MEM] + req[LANE_MEM], a1)
                d = jnp.abs(r0 * a1 - r1 * a0)
                bal = jnp.where(
                    h0 & h1, MAX - (50 * d + den_bal - 1) // den_bal, MAX
                )
                total = total + w_bal * bal
            if w_img:
                total = total + w_img * sig_img[sc]

            # ---- first-max argmax over feasible nodes + one-hot commit ----
            ranked = jnp.where(feas, total, -1)
            choice = jnp.argmax(ranked).astype(I32)
            any_feas = ranked[choice] >= 0
            choice = jnp.where(active & any_feas, choice, -1)
            rows = usage_carry_update(
                {"used": used, "nz0": nz0, "nz1": nz1, "num_pods": num_pods},
                {"used": req, "nz0": snz0, "nz1": snz1, "num_pods": 1},
                choice,
                choice >= 0,
            )
            carry = (rows["used"], rows["nz0"], rows["nz1"], rows["num_pods"])
            return carry, choice

    return step


# ktpu: axes(sig_ids=i32[P], sig_req=i64[S,Rn], sig_nz=i64[S,2], sig_allzero=bool[S])
# ktpu: axes(sig_ok=bool[S,N], sig_img=i64[S,N], alloc=i64[N,Rn], allowed=i32[N])
# ktpu: axes(used=i64[N,Rn], nz0=i64[N], nz1=i64[N], num_pods=i32[N])
# ktpu: accum(i64, i32, bool)
# ktpu: static(w_fit=1, w_bal=1, w_img=1, check_fit=True)
@functools.partial(
    jax.jit,
    static_argnames=("w_fit", "w_bal", "w_img", "check_fit"),
    donate_argnames=("used", "nz0", "nz1", "num_pods"),
)
def sig_scan(
    sig_ids,  # i32 [P]   per-pod signature id, -1 pads
    sig_req,  # i64 [S, R] request row per signature
    sig_nz,  # i64 [S, 2]  non-zero-defaulted cpu,mem per signature
    sig_allzero,  # bool [S] request row entirely zero (fit check skipped)
    sig_ok,  # bool [S, N] statics-feasible (node_valid & name & unsched
    #                      & taints & node-affinity), from static_eval
    sig_img,  # i64 [S, N] ImageLocality contribution (zeros when unused)
    alloc,  # i64 [N, R]
    allowed,  # i32 [N]
    used,  # i64 [N, R]   — donated, evolves across batches
    nz0,  # i64 [N]       — donated
    nz1,  # i64 [N]       — donated
    num_pods,  # i32 [N]  — donated
    w_fit: int,
    w_bal: int,
    w_img: int,
    check_fit: bool,
):
    """One device dispatch = one batch of the signature fast path.

    Replays the reference's one-pod-at-a-time argmax commit
    (schedule_one.go:65 ScheduleOne → selectHost first-max) as a lax.scan
    whose carried state is the node usage tensors — the device-resident
    analogue of kubernetes_tpu.fastpath.FastCommitter, bit-identical to it
    (property-tested in tests/test_fastpath.py).  Per step: O(N) integer
    score + masked argmax + one-hot commit; no [P, N] tensors exist and the
    state never leaves HBM between batches.

    Returns (choices i32 [P] — node index or -1, new_state tuple).
    """
    step = make_sig_step(
        sig_req,
        sig_nz,
        sig_allzero,
        sig_ok,
        sig_img,
        alloc,
        allowed,
        w_fit,
        w_bal,
        w_img,
        check_fit,
    )
    carry, choices = jax.lax.scan(step, (used, nz0, nz1, num_pods), sig_ids)
    return choices, carry
