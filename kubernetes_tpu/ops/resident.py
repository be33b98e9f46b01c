"""Device-resident drain loop: the signature fast path as a multi-round
speculation/admission fixed point (ROADMAP item 1).

sig_scan (ops/fastpath.py) already keeps the node-usage state in HBM, but
replays the sequential greedy one pod per ``lax.scan`` step — O(N) score
work and one argmax per pod.  This module schedules the SAME runs with the
wave's speculation+admission structure (ops/wave.py): each ROUND freezes
the usage state, speculates a whole window of pods in parallel against it,
verifies exactly which prefix of the window the serial recurrence would
have placed identically, commits that agreement prefix with vectorized
scatters, and re-speculates the conflict tail from the updated state.  Per
round the heavy work is one [S, N] score pass + one sort; the per-pod work
collapses to O(S) vector arithmetic — no per-pod argmax, no per-pod scan
step.

Bit-identity argument (decisions == the serial one-pod-at-a-time greedy,
shared verdict code with sig_scan via make_sig_step):

* Scores and feasibility are packed into per-(signature, node) KEYS
  ``key = total_score * n_cap + (n_cap - 1 - n)`` (-1 when infeasible), so
  "max key" == "first-max score" exactly (smaller node index wins ties)
  and keys are unique per node.
* The round speculates a shared consumption walk: nodes sorted by the
  window-head signature's keys; the i-th *scheduled* pod of the window
  takes the i-th node of the walk.  A pod's speculated placement equals
  its serial argmax iff
    (1) its own position IS its signature's best untouched node:
        ``skey[s_i, pos_i] == suffix_max(skey[s_i])[pos_i]``, and
    (2) no already-committed node beats it after its commit:
        ``skey[s_i, pos_i] > max_{j<i committed} upd_key[s_i](n_j)``.
  Within a round each walk position is consumed at most once, so a
  committed node's post-commit key is exact (frozen state + exactly one
  commit), and both conditions are evaluated with vectorized cumulative
  maxima — condition (2) is the same term-factored delta idea the wave's
  admission pass uses, with per-node usage rows as the only "terms".
* Signatures with NO feasible node at round start ("dead") stay dead for
  the whole round (usage only grows), so their pods are admitted as
  unschedulable without consuming walk positions.
* The first window pod always agrees (the walk starts at ITS signature's
  argmax and nothing is committed yet), so every round makes progress and
  the fixed point terminates.  A round cap bounds adversarial workloads;
  any unresolved tail falls back — inside the same dispatch — to the
  sig_scan step function (make_sig_step), i.e. the exact serial replay.

One dispatch per RUN (thousands of pods), one d2h readback of the packed
placements per run; the usage state is donated and never leaves HBM
between runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops.common import usage_carry_update
from kubernetes_tpu.ops.fastpath import make_sig_step
from kubernetes_tpu.snapshot.schema import LANE_CPU, LANE_MEM, N_FIXED_LANES

MAX = 100  # MaxNodeScore
I32 = jnp.int32
I64 = jnp.int64

# shard-rule roster: the resident fixed point is the serial core made
# wide — per-round it sorts/gathers the node axis wholesale and commits
# with scatters into the N-resident usage rows.  Single-chip by design;
# sharding N means replacing exactly these with collectives.
_KTPU_N_COLLECTIVES = {
    "_upd_keys": "resolved(replicated): gathers committed nodes' "
    "usage/alloc rows ([W]-indexed reads of N-leading state) — the "
    "resident lineage's usage state is materialized whole-array per "
    "dispatch from the host committer (not node-sharded), so the reads "
    "are shard-local by layout; node-sharded residency across batches is "
    "ROADMAP item 1's open remainder",
    "resident_run.round_body": "resolved(replicated): walk-order "
    "argsort/gather over N + scatter-add commits into the N-resident "
    "usage rows — same whole-array lineage as _upd_keys: every replica "
    "applies identical rank-1 commits, so the round needs no collective "
    "(the [S,N] speculation keys partition over the pods axis instead)",
    "usage_checksum": "resolved(replicated): full reductions over the "
    "N-leading resident usage rows (the ISSUE 15 epoch guard's integrity "
    "probe) — the lineage is whole-array per dispatch (not node-sharded, "
    "see _upd_keys), so every replica computes the identical scalar and "
    "no collective is inserted",
}
NEG = jnp.iinfo(jnp.int64).min // 4  # "no committed node yet" threshold
UNRESOLVED = -2  # choice sentinel: pod not reached before the round cap


def _score_keys(feas, a0, a1, c0, c1, r0, r1, img, node_ids, n_total,
                w_fit: int, w_bal: int, w_img: int):
    """Packed (score, first-max index) keys from broadcast-ready operands
    — THE integer score formulas of make_sig_step/score_int, in one place
    for both key builders.  ``a0/a1`` are cpu/mem allocatable, ``c0/c1``
    nonzero-request sums (node + signature), ``r0/r1`` UNCLAMPED
    used+request cpu/mem, ``img`` the gathered ImageLocality term, and
    ``node_ids`` the i64 node index per element; every operand broadcasts
    against ``feas``'s shape.  Returns keys with -1 where infeasible."""
    total = jnp.zeros(feas.shape, I64)
    h0 = a0 > 0
    h1 = a1 > 0
    if w_fit:
        fit_w = h0.astype(I64) + h1.astype(I64)
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
        den = jnp.maximum(a0 * a1, 1)
        rr0 = jnp.minimum(r0, a0)
        rr1 = jnp.minimum(r1, a1)
        d = jnp.abs(rr0 * a1 - rr1 * a0)
        bal = jnp.where(h0 & h1, MAX - (50 * d + den - 1) // den, MAX)
        total = total + w_bal * bal
    if w_img:
        total = total + w_img * img
    key = total * n_total + (n_total - 1 - node_ids)
    return jnp.where(feas, key, -1)


def _sig_node_keys(
    sig_req,  # i64 [S, R]
    sig_nz,  # i64 [S, 2]
    sig_allzero,  # bool [S]
    sig_ok,  # bool [S, N]
    sig_img,  # i64 [S, N]
    alloc,  # i64 [N, R]
    allowed,  # i32 [N]
    used,  # i64 [N, R]
    nz0,  # i64 [N]
    nz1,  # i64 [N]
    num_pods,  # i32 [N]
    w_fit: int,
    w_bal: int,
    w_img: int,
    check_fit: bool,
):
    """[S, N] packed (score, first-max index) keys under the CURRENT usage
    state; -1 where infeasible.  The vectorized twin of make_sig_step's
    per-pod score/feasibility math — same integer formulas (_score_keys),
    evaluated for every signature at once."""
    R = alloc.shape[1]
    N = alloc.shape[0]
    a0 = alloc[:, LANE_CPU]  # [N]
    a1 = alloc[:, LANE_MEM]
    if check_fit:
        fits_count = (num_pods + 1 <= allowed)[None, :]  # [1, N]
        avail = alloc - used  # [N, R]
        ext_lane = jnp.arange(R) >= N_FIXED_LANES
        lane_ok = jnp.where(
            (ext_lane[None, :] & (sig_req == 0))[:, None, :],
            True,
            sig_req[:, None, :] <= avail[None, :, :],
        )  # [S, N, R]
        fits_lanes = jnp.where(
            sig_allzero[:, None], True, jnp.all(lane_ok, axis=2)
        )
        feas = sig_ok & fits_count & fits_lanes
    else:
        feas = sig_ok
    return _score_keys(
        feas,
        a0[None, :],
        a1[None, :],
        nz0[None, :] + sig_nz[:, 0, None],
        nz1[None, :] + sig_nz[:, 1, None],
        used[:, LANE_CPU][None, :] + sig_req[:, LANE_CPU, None],
        used[:, LANE_MEM][None, :] + sig_req[:, LANE_MEM, None],
        sig_img,
        jnp.arange(N, dtype=I64)[None, :],
        N,
        w_fit, w_bal, w_img,
    )


def _upd_keys(
    cnode,  # i32 [W] node each window slot would commit
    csig,  # i32 [W] committing signature per slot
    sig_req,
    sig_nz,
    sig_allzero,
    sig_ok,
    sig_img,
    alloc,
    allowed,
    used,
    nz0,
    nz1,
    num_pods,
    w_fit: int,
    w_bal: int,
    w_img: int,
    check_fit: bool,
):
    """[W, S] keys of each slot's committed node under EVERY signature
    AFTER that slot's commit — the rank-1 delta the admission pass ranks
    committed nodes by.  Exact because a walk position commits at most
    once per round.  Same formulas as _sig_node_keys (_score_keys) on
    gathered rows."""
    R = alloc.shape[1]
    N = alloc.shape[0]
    a0 = alloc[cnode, LANE_CPU]  # [W]
    a1 = alloc[cnode, LANE_MEM]
    n_used = used[cnode] + sig_req[csig]  # [W, R]
    n_nz0 = nz0[cnode] + sig_nz[csig, 0]  # [W]
    n_nz1 = nz1[cnode] + sig_nz[csig, 1]
    n_np = num_pods[cnode] + 1
    if check_fit:
        fits_count = (n_np + 1 <= allowed[cnode])[:, None]  # [W, 1]
        avail = alloc[cnode][:, None, :] - n_used[:, None, :]  # [W, 1, R]
        ext_lane = jnp.arange(R) >= N_FIXED_LANES
        lane_ok = jnp.where(
            (ext_lane[None, :] & (sig_req == 0))[None, :, :],
            True,
            sig_req[None, :, :] <= avail,
        )  # [W, S, R]
        fits_lanes = jnp.where(
            sig_allzero[None, :], True, jnp.all(lane_ok, axis=2)
        )
        feas = sig_ok[:, cnode].T & fits_count & fits_lanes  # [W, S]
    else:
        feas = sig_ok[:, cnode].T
    return _score_keys(
        feas,
        a0[:, None],
        a1[:, None],
        n_nz0[:, None] + sig_nz[None, :, 0],
        n_nz1[:, None] + sig_nz[None, :, 1],
        n_used[:, LANE_CPU][:, None] + sig_req[None, :, LANE_CPU],
        n_used[:, LANE_MEM][:, None] + sig_req[None, :, LANE_MEM],
        sig_img[:, cnode].T,
        cnode.astype(I64)[:, None],
        N,
        w_fit, w_bal, w_img,
    )


# adaptive-stop tuning: every GRACE rounds the loop must have admitted at
# least GRACE*MIN_YIELD pods since the last checkpoint, or it stops and
# hands the tail over (serial tail or host committer).  MIN_YIELD is the
# approximate break-even between one round's [S, N] prep and the host
# committer's per-pod cost.
STOP_GRACE = 4
MIN_YIELD = 64


# ktpu: axes(sig_ids=i32[P], sig_req=i64[S,Rn], sig_nz=i64[S,2], sig_allzero=bool[S])
# ktpu: axes(sig_ok=bool[S,N], sig_img=i64[S,N], alloc=i64[N,Rn], allowed=i32[N])
# ktpu: axes(used=i64[N,Rn], nz0=i64[N], nz1=i64[N], num_pods=i32[N])
# ktpu: accum(i64, i32, bool)
# ktpu: static(w_fit=1, w_bal=1, w_img=1, check_fit=True, window=8, serial_tail=True)
@functools.partial(
    jax.jit,
    static_argnames=(
        "w_fit", "w_bal", "w_img", "check_fit", "window", "serial_tail"
    ),
    donate_argnames=("used", "nz0", "nz1", "num_pods"),
)
def resident_run(
    sig_ids,  # i32 [P] per-pod signature id in queue order, -1 pads (suffix)
    sig_req,  # i64 [S, R]
    sig_nz,  # i64 [S, 2]
    sig_allzero,  # bool [S]
    sig_ok,  # bool [S, N]
    sig_img,  # i64 [S, N]
    alloc,  # i64 [N, R]
    allowed,  # i32 [N]
    used,  # i64 [N, R] — donated, resident across runs
    nz0,  # i64 [N]     — donated
    nz1,  # i64 [N]     — donated
    num_pods,  # i32 [N] — donated
    w_fit: int,
    w_bal: int,
    w_img: int,
    check_fit: bool,
    window: int,
    serial_tail: bool = True,
):
    """One dispatch = one resident RUN: the ``sig_ids`` feed is placed on
    device through the speculation/admission fixed point.  With
    ``serial_tail`` (the fully-device-resident mode), anything the round
    cap or adaptive stop leaves unresolved is finished in-kernel by the
    exact sig_scan replay; without it, unresolved pods come back as
    UNRESOLVED (-2) and the caller finishes them on the host committer —
    the right trade when serial device steps are slower than host heaps.

    Returns (choices i32 [P], new_state tuple, stats i64 [3]) where stats
    is (rounds, pods_resolved_by_fixed_point, tail_left 0/1).  With
    serial_tail the returned STATE always covers the whole run; without
    it the state covers exactly the resolved prefix.
    """
    P = sig_ids.shape[0]
    N = alloc.shape[0]
    W = min(window, N)
    # pads are a suffix by construction (host packs live pods first)
    p_live = jnp.sum((sig_ids >= 0).astype(I32))
    ids_pad = jnp.concatenate([sig_ids, jnp.full((W,), -1, I32)])
    iota_w = jnp.arange(W, dtype=I32)
    # round cap: the fixed point admits >=1 pod per round, but an
    # adversarial interleaving could degenerate to exactly that — cap the
    # rounds at a small multiple of the best case and let the tail
    # finish, so the worst case is one tail replay + bounded overhead.
    r_cap = 64 + 8 * (P // W + 1)
    # stop quota scaled by the window: on small clusters (W < MIN_YIELD)
    # even perfect full-window rounds cannot admit MIN_YIELD pods — and
    # their per-round [S, N] prep is proportionally cheaper, so the
    # break-even admission rate is lower too
    min_yield = min(MIN_YIELD, max(1, W // 4))

    score_kw = dict(
        w_fit=w_fit, w_bal=w_bal, w_img=w_img, check_fit=check_fit
    )

    def round_body(carry):
        q, used, nz0, nz1, num_pods, choices, rounds, q_ckpt, stop = carry
        with jax.named_scope("ktpu/resident/round"):
            keys = _sig_node_keys(
                sig_req, sig_nz, sig_allzero, sig_ok, sig_img,
                alloc, allowed, used, nz0, nz1, num_pods, **score_kw
            )  # [S, N]
            win = jax.lax.dynamic_slice(ids_pad, (q,), (W,))  # [W]
            live = win >= 0
            sig_w = jnp.maximum(win, 0)
            # shared consumption walk: nodes in the window head's preference
            # order (keys are unique, so argsort is deterministic)
            order = jnp.argsort(-keys[sig_w[0]]).astype(I32)  # [N]
            skey = keys[:, order]  # [S, N] every sig's keys along the walk
            sufmax = jnp.flip(
                jax.lax.cummax(jnp.flip(skey, axis=1), axis=1), axis=1
            )  # [S, N] best untouched key at-or-after each position
            dead = sufmax[:, 0] < 0  # [S] no feasible node at all this round
            dead_w = dead[sig_w] & live
            sched_spec = live & ~dead_w  # speculated to consume a position
            si = sched_spec.astype(I32)
            pos = jnp.minimum(jnp.cumsum(si) - si, N - 1)  # exclusive count
            ckey = skey[sig_w, pos]  # [W] speculated placement's key
            csuf = sufmax[sig_w, pos]  # [W] its sig's true untouched max
            cnode = order[pos]  # [W]
            u = _upd_keys(
                cnode, sig_w, sig_req, sig_nz, sig_allzero, sig_ok, sig_img,
                alloc, allowed, used, nz0, nz1, num_pods, **score_kw
            )  # [W, S] post-commit keys of each slot's node
            u = jnp.where(sched_spec[:, None], u, NEG)
            # exclusive running max over predecessors' committed nodes
            thr = jax.lax.cummax(u, axis=0)
            thr = jnp.concatenate([jnp.full((1, u.shape[1]), NEG, I64), thr[:-1]])
            thr_i = thr[iota_w, sig_w]  # [W]
            ok_sched = sched_spec & (ckey >= 0) & (ckey == csuf) & (ckey > thr_i)
            agree = ok_sched | dead_w
            disagree = ~agree
            any_dis = jnp.any(disagree)
            first = jnp.argmax(disagree).astype(I32)
            A = jnp.where(any_dis, first, W)  # admitted prefix length (>= 1)
            adm = iota_w < A
            commit = adm & ok_sched
            # windowed form of THE shared usage commit (ops/common.py): each
            # walk position commits at most once per round, so the scatter-add
            # equals replaying the scalar rank-1 form per admitted slot
            rows = usage_carry_update(
                {"used": used, "nz0": nz0, "nz1": nz1, "num_pods": num_pods},
                {
                    "used": sig_req[sig_w],
                    "nz0": sig_nz[sig_w, 0],
                    "nz1": sig_nz[sig_w, 1],
                    "num_pods": 1,
                },
                cnode,
                commit,
            )
            used, nz0, nz1, num_pods = (
                rows["used"], rows["nz0"], rows["nz1"], rows["num_pods"]
            )
            cvals = jnp.where(commit, cnode, -1)  # admitted dead pods: -1
            # choices is padded by W so this window write NEVER reaches the
            # array end — XLA CLAMPS out-of-range dynamic_update_slice starts,
            # which would silently shift the write onto earlier results
            old = jax.lax.dynamic_slice(choices, (q,), (W,))
            choices = jax.lax.dynamic_update_slice(
                choices, jnp.where(adm & live, cvals, old), (q,)
            )
            q = q + A
            rounds = rounds + 1
            # adaptive stop: every STOP_GRACE rounds the loop must have
            # yielded STOP_GRACE*MIN_YIELD admissions since the checkpoint —
            # workloads whose agreement prefixes collapse (adversarial sig
            # interleavings) hand over to the tail instead of burning rounds
            at_ckpt = rounds % STOP_GRACE == 0
            stop = at_ckpt & (q - q_ckpt < STOP_GRACE * min_yield)
            q_ckpt = jnp.where(at_ckpt, q, q_ckpt)
            return (q, used, nz0, nz1, num_pods, choices, rounds, q_ckpt, stop)

    def round_cond(carry):
        q, _, _, _, _, _, rounds, _, stop = carry
        return (q < p_live) & (rounds < r_cap) & ~stop

    choices0 = jnp.full((P + W,), UNRESOLVED, I32)
    # the loop's own machinery (condition, carry) belongs to the rounds too
    with jax.named_scope("ktpu/resident/round"):
        (
            q, used, nz0, nz1, num_pods, choices, rounds, _, _
        ) = jax.lax.while_loop(
            round_cond,
            round_body,
            (
                jnp.zeros((), I32), used, nz0, nz1, num_pods, choices0,
                jnp.zeros((), I64), jnp.zeros((), I32), jnp.zeros((), bool),
            ),
        )
    choices = choices[:P]
    tail_left = q < p_live

    if serial_tail:
        # fully-device-resident mode: finish unresolved pods with the
        # EXACT sig_scan replay (shared step) inside the same dispatch,
        # entered only when needed so the common case pays nothing.
        def run_tail(args):
            used, nz0, nz1, num_pods, choices = args
            step = make_sig_step(
                sig_req, sig_nz, sig_allzero, sig_ok, sig_img,
                alloc, allowed, **score_kw
            )
            masked = jnp.where(jnp.arange(P, dtype=I32) < q, -1, sig_ids)
            carry, tail_choices = jax.lax.scan(
                step, (used, nz0, nz1, num_pods), masked
            )
            used, nz0, nz1, num_pods = carry
            choices = jnp.where(choices == UNRESOLVED, tail_choices, choices)
            return used, nz0, nz1, num_pods, choices

        used, nz0, nz1, num_pods, choices = jax.lax.cond(
            tail_left,
            run_tail,
            lambda args: args,
            (used, nz0, nz1, num_pods, choices),
        )
    stats = jnp.stack([rounds, q.astype(I64), tail_left.astype(I64)])
    return choices, (used, nz0, nz1, num_pods), stats


# ---------------------------------------------------------------------------
# epoch guard (ISSUE 15): cheap device-side integrity probe of the
# resident usage lineage
# ---------------------------------------------------------------------------

# ktpu: axes(used=i64[N,Rn], nz0=i64[N], nz1=i64[N], num_pods=i32[N])
# ktpu: accum(i64, i32, bool)
@jax.jit
def usage_checksum(used, nz0, nz1, num_pods):
    """Cheap device-side checksum of the resident usage state: the exact
    i64 sum of every row.  The host committer tracks the same quantity
    incrementally (base sum + per-harvest commit deltas — the commit
    arithmetic is identical int math on both sides), so before a round's
    commits are applied the two MUST agree; a mismatch means the lineage
    is torn (a dispatch died mid-round, or a donated buffer was clobbered)
    and the harvest resyncs from the host committer instead of silently
    committing torn usage rows.  One tiny dispatch per device-path batch,
    async-fetched alongside the choices readback."""
    return (
        jnp.sum(used)
        + jnp.sum(nz0)
        + jnp.sum(nz1)
        + jnp.sum(num_pods.astype(I64))
    )
