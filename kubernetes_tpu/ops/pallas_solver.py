"""Pallas TPU kernel for the default-policy sequential-commit solve.

The XLA `lax.scan` in models/batch_solver.py dispatches ~45us of work per
pod step; at 10k pending pods the north-star wave spends ~0.45s in the
scan even though each step touches only ~200k vector elements. This
module lowers the same sequential-commit loop to a single Pallas kernel:
the mutable cluster state (per-dimension usage planes, port/PD bitmask
words, per-service peer counts) lives in VMEM scratch that persists
across grid steps, per-pod rows stream from HBM, and each step runs a
handful of fused VPU ops plus two small MXU matmuls — no per-step HBM
round-trips, no XLA loop overhead.

Decisions are bit-identical to ``solve_jit`` (and therefore to the serial
oracle) by construction: every score is computed in exact integer
arithmetic, including the IEEE-float32 spread-score emulation
(ops/kernels.spread_score rationale) re-derived here in pure int32 — the
12-bit-limb long division replaces the int64 shift path because the TPU
kernel type has no 64-bit lanes. The FNV-1a tie-break is a 16-bit-limb
Horner modulo. The k-th-best selection uses triangular-matmul prefix
ranks (exact: counts < 2^24 in f32 with HIGHEST precision).

Scope (``eligible`` says so): the WHOLE modeled policy vocabulary —
PodFitsResources/PodFitsPorts/NoDiskConflict/MatchNodeSelector/HostName
filters (the selector/host/static masks ride the XLA MXU pre-pass, as in
solve_jit), CheckNodeLabelPresence (static mask), CheckServiceAffinity
(anchor values in a [G, LANES] VMEM scratch, lanes 0..L-1; the has-anchor
flag lane-replicated in a sibling scratch so commits need no cross-lane
broadcast), LeastRequested/ServiceSpreading/Equal priorities,
NodeLabelPriority (static additive plane), and ServiceAntiAffinity
(one zone-index plane a label; each value's mask built in the kernel and
the label folded over its own value count) — int32 resource waves. Gang
(PodGroup all-or-nothing) waves are in-domain: the kernel checkpoints the
committed state (including anchors) at each scheduling-unit start and a
failing member rolls the whole run back — solve_jit's gang_step, with the
checkpoint in a second set of VMEM planes. Fallbacks to the XLA scan:
waves whose counts could reach 2^15 (the limb domains), >32640 nodes,
>4 affinity labels, more group rows than ``max_groups`` (membership rides
nine 31-bit mask lanes of the pod row; a pod's own counts row is read by a
dynamic index), or int64 resource planes.

ref: pkg/scheduler/generic_scheduler.go:54-128 (the serial loop being
batched), plugin/pkg/scheduler/scheduler.go:90-119 (commit-per-decision).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubernetes_tpu.models.policy import BatchPolicy

__all__ = ["eligible", "max_groups", "solve_pallas"]

LANES = 128
NEG = -1

# podrow lane layout (one packed [128] i32 row per pod)
_REQ0 = 0          # R request values
_PORTS0 = 8        # Wp port bitmask words (bitcast u32->i32)
_PDS0 = 16         # Wd pd bitmask words
_TIE0 = 24         # 4 big-endian 16-bit limbs of the FNV-1a u64
_GID = 28
_MEMBER = 29       # member bitmask over groups 0..30 (the first mask lane)
_ZREQ = 30         # 1 when the pod requests zero of everything
_START = 31        # 1 when this pod begins a new scheduling unit (gangs)
_AFF0 = 32         # L <= 4 ServiceAffinity selector-pinned value codes
_MEMBER1 = 40      # mask lanes 1..: groups 31..61, 62..92, ... (31 a lane,
                   # so that every lane is a non-negative i32)
_MEMBER_BITS = 31

_MAX_R = 8
_MAX_W = 8
_MAX_G = 256       # group rows a wave may carry: 9 mask lanes of 31 bits
_MAX_N = 32640     # tie-break/limb domains need counts < 2^15
_MAX_COUNT = 1 << 15
_MAX_A = 4         # anti-affinity labels, one zone-index plane each
_FOLD = 4          # zone values folded a loop step (independent reductions)
_MAX_L = 4         # ServiceAffinity labels riding podrow lanes 32..35
_VMEM_BUDGET = 12 << 20   # leave headroom under the ~16MB per-core VMEM


def eligible(inp, pol: Optional[BatchPolicy], gangs: bool,
             peer_bound: int) -> bool:
    """True when the wave is in the kernel's proven domain.

    ``peer_bound`` is the largest initial per-group peer TOTAL (sum of a
    group's counts row) — the caller reads it from the host-side snapshot
    (a device reduction here would force a sync per wave); it bounds both
    the ServiceSpreading max-count and the anti-affinity num-peers, which
    must stay below 2^15 for the limb arithmetic. Gang waves are
    in-domain: the kernel carries a checkpoint copy of the committed
    state and rolls a failed run back, mirroring solve_jit's gang_step.
    Zone anti-affinity is in-domain via one zone-index plane a label,
    whatever its value count; ServiceAffinity anchors live in two tiny
    [G, LANES] scratches; NodeLabelPriority is one extra static plane."""
    if pol is None:
        return False
    if pol.all_infeasible:
        return False
    if inp.cap.dtype != jnp.int32:
        return False
    band_prio = getattr(inp, "band_prio", None)
    if band_prio is not None and band_prio.shape[0] > 0:
        # kube-preempt waves carry the evictable-band planes and the
        # min-victim-cost sub-program; the VMEM kernel does not model
        # them — those waves take the XLA scan (batch_solver solve_jit),
        # which is the bit-identity-gated reference implementation
        return False
    N, R = inp.cap.shape
    G = inp.group_counts.shape[0]
    if not (R <= _MAX_R and inp.node_ports.shape[1] <= _MAX_W
            and inp.node_pds.shape[1] <= _MAX_W and G <= _MAX_G
            and N <= _MAX_N):
        return False
    A = 0
    if pol.anti_affinity:
        A = inp.zone_idx.shape[0]
        if not (0 < A <= _MAX_A and A == len(pol.anti_affinity)):
            return False
    L = 0
    if pol.has_affinity:
        L = inp.node_aff_vals.shape[1]
        # the snapshot must have been encoded for THIS policy's labels, and
        # the pinned codes must ride podrow lanes _AFF0..
        if not (0 < L <= _MAX_L and L == len(pol.affinity_labels)):
            return False
    # spread/anti-affinity totals stay below 2^15: initial peers plus
    # every wave commit
    if peer_bound + inp.req.shape[0] >= _MAX_COUNT:
        return False
    # VMEM budget: a wave that would exceed the ~16MB per-core VMEM must
    # take the XLA scan instead of dying in a Mosaic RESOURCE_EXHAUSTED
    # compile error
    Wp, Wd = inp.node_ports.shape[1], inp.node_pds.shape[1]
    return _vmem_bytes(N, R, Wp, Wd, G, L=L, A=A,
                       label_prefs=bool(pol.label_prefs),
                       gangs=gangs) <= _VMEM_BUDGET


def _wave_planes(R: int, Wp: int, Wd: int, G: int) -> int:
    """Node planes every wave keeps in VMEM: the mutable state (usage twice
    over, port and disk words, the counts rows) as input and as scratch,
    and the capacity and the exceeded flag beside the inputs."""
    state = 2 * R + Wp + Wd + G
    return (state + R + 1) + state


def _vmem_bytes(N: int, R: int, Wp: int, Wd: int, G: int, *, L: int = 0,
                A: int = 0, label_prefs: bool = False,
                gangs: bool = False) -> int:
    """The one VMEM account of a wave, which ``eligible`` and ``max_groups``
    both read: every node plane (inputs, scratch state, gang checkpoints)
    is VMEM-resident, beside one value plane a ServiceAffinity label, one
    zone-index plane an anti-affinity label (whatever its value count: the
    kernel folds a label's values from that plane), the static score plane
    of label preferences, and the anchor rows of ServiceAffinity (input,
    scratch and checkpoint copies of the values and the has-flags)."""
    NR = max(1, -(-N // LANES))
    planes = _wave_planes(R, Wp, Wd, G) + L + A
    if label_prefs:
        planes += 1
    if gangs:
        planes += 2 * R + Wp + Wd + G + 1            # checkpoint copy
    anchors = 6 if L else 0
    return planes * NR * LANES * 4 + anchors * G * LANES * 4


def max_groups(n_nodes: int, R: int = 2, Wp: int = 1, Wd: int = 1,
               pol: Optional[BatchPolicy] = None) -> int:
    """The most group rows (a power of two, 8 to ``_MAX_G``) that a wave
    without gangs may carry at ``n_nodes`` and stay inside ``eligible``'s
    VMEM account, beside ``R`` resource dimensions, ``Wp`` / ``Wd`` port
    and disk words and the planes of ``pol`` (its ServiceAffinity labels,
    anti-affinity labels and label preferences): a counts row is a node
    plane twice over (the input and the scratch it is committed into).
    What the wave loop cuts a wave at, and the encoder's group bucket stops
    at; past ``_MAX_N`` nodes no wave takes the kernel and only ``_MAX_G``
    bounds the rows."""
    if n_nodes > _MAX_N:
        return _MAX_G
    pol = pol or BatchPolicy()
    sizes = dict(L=len(pol.affinity_labels), A=len(pol.anti_affinity),
                 label_prefs=bool(pol.label_prefs))
    cap = 8
    while cap * 2 <= _MAX_G and _vmem_bytes(
            n_nodes, R, Wp, Wd, cap * 2, **sizes) <= _VMEM_BUDGET:
        cap *= 2
    return cap


def _exponent(x_f32: jnp.ndarray) -> jnp.ndarray:
    """frexp-style exponent e with x = m * 2^e, m in [0.5, 1) — exact bit
    extraction, valid for positive finite x. lax.bitcast_convert_type
    lowers both in Mosaic and in the interpreter."""
    bits = jax.lax.bitcast_convert_type(x_f32, jnp.int32)
    return ((bits >> 23) & 0xFF) - 126


def _spread_score_i32(total, counts):
    """Exact int32 emulation of int(10 * (f32(total-count) / f32(total))):
    the same two IEEE round-to-nearest-even steps as ops/kernels.
    spread_score, but via 12-bit-limb long division (no 64-bit lanes on
    the TPU kernel type). Domain: 0 <= count <= total < 2^15.

    ``total`` is a 0-d scalar, counts any 2D block."""
    a = jnp.maximum(total - counts, 0)
    b = jnp.maximum(total, 1)
    # exponents (a=0 guarded at the end; f32 conversion exact below 2^24).
    # ea rides the vector bitcast; b is a 0-d scalar and tpu.bitcast only
    # takes vectors, so its bit-length comes from 15 scalar compares.
    ea = _exponent(jnp.maximum(a, 1).astype(jnp.float32))
    eb = jnp.int32(0)
    for j in range(15):
        eb = eb + (b >= (1 << j)).astype(jnp.int32)
    # significand m = RNE_24bit(a * 2^k / b), m in [2^23, 2^24)
    k = 23 + eb - ea                       # a <= b so k >= 23; k <= 38
    t = k % 12
    s = k // 12                            # 1..3
    v0 = a << t                            # < 2^27
    q = v0 // b
    r = v0 - q * b
    for i in (1, 2, 3):                    # remaining 12-bit zero limbs
        act = i <= s
        x = r << 12
        d = x // b
        q = jnp.where(act, (q << 12) + d, q)
        r = jnp.where(act, x - d * b, r)
    # normalize into [2^23, 2^24): exact floor/remainder shift identities
    lo = q < (1 << 23)
    hi = q >= (1 << 24)
    bit_up = ((r << 1) >= b) & lo
    q2 = jnp.where(lo, (q << 1) + bit_up.astype(jnp.int32), q)
    r2 = jnp.where(lo, (r << 1) - bit_up.astype(jnp.int32) * b, r)
    q3 = jnp.where(hi, q2 >> 1, q2)
    r3 = jnp.where(hi, (q2 & 1) * b + r2, r2)
    k = k + lo.astype(jnp.int32) - hi.astype(jnp.int32)
    # round to nearest, ties to even mantissa
    m = q3 + (((r3 << 1) > b) | (((r3 << 1) == b) & (q3 & 1 == 1))
              ).astype(jnp.int32)
    roll = m == (1 << 24)
    m = jnp.where(roll, 1 << 23, m)
    k = k - roll.astype(jnp.int32)
    # y = RN_f32(10 * q): 10*m < 2^28, drop to 24 significant bits
    z = 10 * m
    d2 = 3 + (z >= (1 << 27)).astype(jnp.int32)
    half = 1 << (d2 - 1)
    rem = z & ((1 << d2) - 1)
    zm = z >> d2
    zm = zm + ((rem > half) | ((rem == half) & (zm & 1 == 1))
               ).astype(jnp.int32)
    zroll = zm == (1 << 24)
    zm = jnp.where(zroll, 1 << 23, zm)
    d2 = d2 + zroll.astype(jnp.int32)
    # trunc(y) with y = zm * 2^(d2-k). k-d2 ranges over [17, 35]; an i32
    # shift by >= 32 is undefined (hardware masks mod 32), and zm < 2^24
    # means any shift >= 24 is exactly 0 — clamp to keep it defined.
    score = jnp.where(k - d2 >= 24, 0, zm >> jnp.minimum(k - d2, 23))
    score = jnp.where(a == 0, 0, score)
    return jnp.where(total > 0, score, 10)


def _make_kernel(P, NR, PR, R, Wp, Wd, G, pol: BatchPolicy,
                 gangs: bool = False, B: int = 1, L: int = 0):
    """Build the kernel body for static shapes/policy. Argument order:
    inputs (smask, podrow, cap, fit0, score0, fitexc, ports0, pds0,
    counts0, offl, advx[, sstat when label-prefs][, affv, anchor0, has0
    when service-affinity][, zidx, nvals when anti-affinity]), outputs
    (chosen, win), scratches (fit, score, ports, pds, counts[, aff, has
    when service-affinity][, the matching ckpt_* copies and flags when
    gangs]).

    ``B`` pods are processed per grid step (unrolled, strictly in pod
    order — the sequential-commit semantics are untouched); the grid
    bookkeeping and block switching are a large share of the ~10us
    per-pod cost at B=1."""
    w_lr, w_spread, w_equal = pol.w_lr, pol.w_spread, pol.w_equal
    A = len(pol.anti_affinity)
    has_sstat = bool(pol.label_prefs)
    has_aff = L > 0

    def kernel(smask_ref, podrow_ref, cap_ref, fit0_ref, score0_ref,
               fitexc_ref, ports0_ref, pds0_ref, counts0_ref, offl_ref,
               advx_ref, *rest):
        i = 0
        sstat_ref = affv_ref = anchor0_ref = has0_ref = None
        zidx_ref = nvals_ref = None
        if has_sstat:
            sstat_ref = rest[i]
            i += 1
        if has_aff:
            affv_ref, anchor0_ref, has0_ref = rest[i:i + 3]
            i += 3
        if A:
            zidx_ref, nvals_ref = rest[i], rest[i + 1]
            i += 2
        chosen_ref, win_ref = rest[i], rest[i + 1]
        i += 2
        fit_ref, score_ref, ports_ref, pds_ref, counts_ref = rest[i:i + 5]
        i += 5
        state_refs = [fit_ref, score_ref, ports_ref, pds_ref, counts_ref]
        init_refs = [fit0_ref, score0_ref, ports0_ref, pds0_ref, counts0_ref]
        aff_refs = None
        if has_aff:
            aff_refs = (rest[i], rest[i + 1])        # anchor values, has
            i += 2
            state_refs += list(aff_refs)
            init_refs += [anchor0_ref, has0_ref]
        gang_refs = rest[i:]
        p = pl.program_id(0)
        if gangs:
            ckpt_refs = tuple(gang_refs[:-1])        # mirrors state_refs
            flags_ref = gang_refs[-1]

        @pl.when(p == 0)
        def _init():
            for s_ref, s0_ref in zip(state_refs, init_refs):
                s_ref[:] = s0_ref[:]
            chosen_ref[:] = jnp.full_like(chosen_ref, NEG)
            win_ref[:] = jnp.full_like(win_ref, NEG)
            if gangs:
                flags_ref[:] = jnp.zeros_like(flags_ref)

        # the gang failed-flag threads through the unrolled pods as a
        # traced value; the plane is read once per step, written once
        if gangs:
            failed = flags_ref[0, 0] != 0            # 0-d bool
        for b in range(B):
            failed = _pod_step(
                p * B + b, b, pol, gangs, A, L, R, Wp, Wd, G, NR, PR,
                w_lr, w_spread, w_equal,
                smask_ref, podrow_ref, cap_ref, fitexc_ref, offl_ref,
                advx_ref, sstat_ref, affv_ref,
                zidx_ref, nvals_ref,
                chosen_ref, win_ref, tuple(state_refs), aff_refs,
                ckpt_refs if gangs else None,
                failed if gangs else None)
        if gangs:
            flags_ref[:] = jnp.zeros_like(flags_ref) + failed.astype(
                jnp.int32)

    return kernel


def _pod_step(p_global, b, pol, gangs, A, L, R, Wp, Wd, G, NR, PR,
              w_lr, w_spread, w_equal,
              smask_ref, podrow_ref, cap_ref, fitexc_ref, offl_ref,
              advx_ref, sstat_ref, affv_ref, zidx_ref, nvals_ref,
              chosen_ref, win_ref, state_refs, aff_refs, ckpt_refs, failed):
    """One pod's filter/score/select/commit against the live VMEM state.
    Returns the threaded gang failed-flag (None when not a gang wave)."""
    fit_ref, score_ref, ports_ref, pds_ref, counts_ref = state_refs[:5]
    if aff_refs is not None:
        aff_ref, has_ref = aff_refs
    # every per-pod quantity is extracted as a 0-d scalar (row[0, i])
    row = podrow_ref[b]                          # [1, 128] i32
    static_row = smask_ref[b]                    # [NR, 128] i32
    gid = row[0, _GID]                           # 0-d

    if True:
        # ---- gang bookkeeping (solve_jit gang_step twin) -----------------
        # A new scheduling unit checkpoints the committed state; a failing
        # member pins the state at the checkpoint (undoing the run's
        # earlier commits) and blocks the run's remaining members.
        if gangs:
            start = row[0, _START] != 0              # 0-d bool
            @pl.when(start)
            def _checkpoint():
                for c_ref, s_ref in zip(ckpt_refs, state_refs):
                    c_ref[:] = s_ref[:]
            failed = failed & ~start                 # 0-d bool

        # ---- Filter ------------------------------------------------------
        feasible = static_row != 0
        if gangs:
            # remaining members of an already-failed gang place nowhere
            feasible = feasible & ~failed
        if pol.use_resources:
            res_ok = jnp.ones((NR, LANES), jnp.bool_)
            for r in range(R):
                cap_r = cap_ref[r]
                fit_r = fit_ref[r]
                req_r = row[0, _REQ0 + r]                       # 0-d
                ok_r = cap_r - fit_r >= req_r
                if r < 2:
                    # cpu/memory are unconstrained at zero capacity
                    ok_r = ok_r | (cap_r == 0)
                res_ok = res_ok & ok_r
            zreq = row[0, _ZREQ] != 0                           # 0-d
            feasible = feasible & (zreq | ((fitexc_ref[:] == 0) & res_ok))
        if pol.use_ports:
            conflict = jnp.zeros((NR, LANES), jnp.bool_)
            for w in range(Wp):
                pw = row[0, _PORTS0 + w]
                conflict = conflict | ((ports_ref[w] & pw) != 0)
            feasible = feasible & ~conflict
        if pol.use_disk:
            conflict = jnp.zeros((NR, LANES), jnp.bool_)
            for w in range(Wd):
                pw = row[0, _PDS0 + w]
                conflict = conflict | ((pds_ref[w] & pw) != 0)
            feasible = feasible & ~conflict
        if L:
            # CheckServiceAffinity, anchor-derived constraints
            # (predicates.go:256-276): once the pod's group has an anchor,
            # labels the selector didn't pin must match the anchor's
            # values. The anchor row is gathered by a masked [G, LANES]
            # reduction (no dynamic VMEM indexing); the has flag is
            # lane-replicated in has_ref so one masked lane read suffices.
            g_iota = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 0)
            l_iota = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 1)
            selrow = g_iota == gid                   # gid<0 matches nothing
            picked = jnp.where(selrow, aff_ref[:], 0)
            has = jnp.sum(jnp.where(selrow & (l_iota == 0),
                                    has_ref[:], 0)) != 0      # 0-d bool
            dyn = jnp.ones((NR, LANES), jnp.bool_)
            for l in range(L):
                a_l = jnp.sum(jnp.where(l_iota == l, picked, 0))    # 0-d
                pin_l = row[0, _AFF0 + l]                           # 0-d
                need = (pin_l == -2) & (a_l >= 0)
                dyn = dyn & (~need | (affv_ref[l] == a_l))
            feasible = feasible & (~has | dyn)

        # ---- Score -------------------------------------------------------
        score = jnp.zeros((NR, LANES), jnp.int32)
        if w_lr:
            total_sc = jnp.zeros((NR, LANES), jnp.int32)
            n_dyn = jnp.int32(2)
            for r in range(R):
                cap_r = cap_ref[r]
                req_r = row[0, _REQ0 + r]
                tot_r = score_ref[r] + req_r
                sc_r = ((cap_r - tot_r) * 10) // jnp.maximum(cap_r, 1)
                sc_r = jnp.where((cap_r == 0) | (tot_r > cap_r), 0, sc_r)
                total_sc = total_sc + sc_r
                if r >= 2:
                    # the serial divisor counts extra dims advertised by
                    # some FEASIBLE node (generic_scheduler.go:70-75)
                    adv = jnp.any((advx_ref[r] != 0) & feasible)
                    n_dyn = n_dyn + adv.astype(jnp.int32)
            score = score + (total_sc // n_dyn) * w_lr
        if w_spread or A:
            # counts row of the pod's first service (a dynamic index on
            # the scratch's leading axis; the off-list peers a scalar in
            # SMEM); gid < 0 matches no group so the totals are 0 and the
            # scores the no-service defaults.
            # one row read whatever G is: the row index is clamped and
            # the row zeroed for a pod without a service
            has_g = (gid >= 0).astype(jnp.int32)                # 0-d
            g_at = jnp.clip(gid, 0, G - 1)
            counts_row = counts_ref[g_at] * has_g
            off = offl_ref[g_at] * has_g
        if w_spread:
            max_count = jnp.maximum(jnp.max(counts_row), off)   # 0-d
            spread = _spread_score_i32(max_count, counts_row)
            score = score + spread * w_spread
        if A:
            # ServiceAntiAffinity (spreading.go:104-168): per-zone peer
            # counts restricted to feasible nodes (the serial path scores
            # over the filtered list); num counts ALL peers, off-list
            # included. Exact int32 throughout.
            num = jnp.sum(counts_row) + off                     # 0-d
            c = counts_row * feasible.astype(jnp.int32)
        for a, (_label, w) in enumerate(pol.anti_affinity):
            # each value's mask comes from the label's index plane, the
            # label folded over its own value count (nvals, SMEM) in a
            # loop of _FOLD values a step; a code past the label's last
            # matches no node, so the step's overshoot folds nothing
            zi = zidx_ref[a]                                    # [NR,128]

            def fold(k, cnt, zi=zi):
                for u in range(_FOLD):
                    zv = zi == k * _FOLD + u
                    zc_v = jnp.sum(jnp.where(zv, c, 0))         # 0-d
                    cnt = jnp.where(zv, zc_v, cnt)
                return cnt

            cnt = jax.lax.fori_loop(
                0, (nvals_ref[a] + _FOLD - 1) // _FOLD, fold,
                jnp.zeros((NR, LANES), jnp.int32))
            s = _spread_score_i32(num, cnt)
            s = jnp.where(zi >= 0, s, 0)                        # labelled
            score = score + s * w
        if pol.label_prefs:
            # NodeLabelPriority: static additive plane (priorities.go:98-134)
            score = score + sstat_ref[:]
        if w_equal:
            score = score + w_equal
        masked = jnp.where(feasible, score, NEG)

        # ---- select host (deterministic tie-break) -----------------------
        top = jnp.max(masked)
        best = (masked == top) & feasible
        cntb = jnp.maximum(jnp.sum(best.astype(jnp.int32)), 1)
        # FNV-1a u64 mod cntb: 16-bit-limb Horner, every partial < 2^31
        k_tie = jnp.int32(0)
        for i in range(4):
            limb = row[0, _TIE0 + i]                            # 0-d
            k_tie = ((k_tie << 16) + limb) % cntb
        # global inclusive rank of each best node, in node-index order:
        # in-row prefix via upper-triangular MXU matmul, plus the exclusive
        # prefix of full-row sums (exact: counts < 2^24 in f32/HIGHEST)
        bf = best.astype(jnp.float32)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) <=
               jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
               ).astype(jnp.float32)
        within = jax.lax.dot(bf, tri,
                             precision=jax.lax.Precision.HIGHEST)
        # row totals replicated across lanes (bf @ ones), then the strict
        # row-prefix — both as matmuls, so no [NR,1]->[NR,128] broadcast
        ones = jnp.ones((LANES, LANES), jnp.float32)
        row_tot = jax.lax.dot(bf, ones,
                              precision=jax.lax.Precision.HIGHEST)
        tri_r = (jax.lax.broadcasted_iota(jnp.int32, (NR, NR), 0) >
                 jax.lax.broadcasted_iota(jnp.int32, (NR, NR), 1)
                 ).astype(jnp.float32)
        excl = jax.lax.dot(tri_r, row_tot,
                           precision=jax.lax.Precision.HIGHEST)  # [NR, 128]
        rank = (within + excl).astype(jnp.int32)
        sel = best & (rank == k_tie + 1)                # one node or none
        flat = (jax.lax.broadcasted_iota(jnp.int32, (NR, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (NR, LANES), 1))
        any_f = top > NEG
        chosen = jnp.where(any_f, jnp.sum(jnp.where(sel, flat, 0)),
                           jnp.int32(NEG))

        # ---- commit ------------------------------------------------------
        onehot = sel                                     # all-False if none
        for r in range(R):
            req_r = row[0, _REQ0 + r]                    # 0-d
            upd = jnp.where(onehot, req_r, 0)
            fit_ref[r] = fit_ref[r] + upd
            score_ref[r] = score_ref[r] + upd
        for w in range(Wp):
            pw = row[0, _PORTS0 + w]
            ports_ref[w] = jnp.where(onehot, ports_ref[w] | pw,
                                     ports_ref[w])
        for w in range(Wd):
            pw = row[0, _PDS0 + w]
            pds_ref[w] = jnp.where(onehot, pds_ref[w] | pw, pds_ref[w])
        members = [row[0, _member_lane(lane)]            # 0-d each
                   for lane in range(-(-G // _MEMBER_BITS))]
        for g in range(G):
            lane, bit = divmod(g, _MEMBER_BITS)
            in_g = (members[lane] >> bit) & 1            # 0-d
            counts_ref[g] = counts_ref[g] + \
                jnp.where(onehot, in_g, 0)
        if L:
            # set the anchor of every group this commit gives its first
            # peer (solve_jit's newly = member & ~has_anchor & committed):
            # one full-plane masked write per scratch, no G-loop
            g_iota = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 0)
            l_iota = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 1)
            in_g_rows = jnp.zeros((G, LANES), jnp.bool_)
            for lane in range(-(-G // _MEMBER_BITS)):
                bit = g_iota - lane * _MEMBER_BITS
                here = (bit >= 0) & (bit < _MEMBER_BITS)
                in_g_rows = in_g_rows | (here & ((jnp.right_shift(
                    members[lane],
                    jnp.clip(bit, 0, _MEMBER_BITS - 1)) & 1) != 0))
            newly = in_g_rows & (has_ref[:] == 0) & any_f
            newvals = jnp.zeros((G, LANES), jnp.int32)
            for l in range(L):
                # the chosen node's value code for label l (0-d; harmless
                # garbage when nothing was chosen — newly is then False)
                ch_l = jnp.sum(jnp.where(onehot, affv_ref[l], 0))
                newvals = jnp.where(l_iota == l, ch_l, newvals)
            aff_ref[:] = jnp.where(newly & (l_iota < L), newvals,
                                   aff_ref[:])
            has_ref[:] = jnp.where(newly, 1, has_ref[:])

        # ---- gang rollback ------------------------------------------------
        if gangs:
            failed = failed | ~any_f
            @pl.when(failed)
            def _rollback():
                # pin the state at the run's checkpoint: undoes every
                # commit since the unit started (this step committed
                # nothing — a failed member chose no node)
                for c_ref, s_ref in zip(ckpt_refs, state_refs):
                    s_ref[:] = c_ref[:]

        # ---- write decision ----------------------------------------------
        oh_p = ((jax.lax.broadcasted_iota(jnp.int32, (PR, LANES), 0)
                 == p_global // LANES) &
                (jax.lax.broadcasted_iota(jnp.int32, (PR, LANES), 1)
                 == p_global % LANES))
        chosen_ref[:] = jnp.where(oh_p, chosen, chosen_ref[:])
        win_ref[:] = jnp.where(oh_p, jnp.where(any_f, top, NEG),
                               win_ref[:])
    return failed


def _member_lane(lane: int) -> int:
    """The podrow lane of the ``lane``-th membership mask."""
    return _MEMBER if lane == 0 else _MEMBER1 + lane - 1


def _value_counts(zidx):
    """Each anti-affinity label's own value count, from its [N] row of
    zone codes (0.. in first-seen order, -1 unlabelled): the values the
    kernel folds for that label."""
    return jnp.max(zidx, axis=1) + 1


def _pad_nodes(x, Npad, fill=0):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Npad - x.shape[-1])],
                   constant_values=fill)


@jax.jit
def _tie_limbs(tie_hi, tie_lo):
    """Split the FNV-1a u64 halves into 4 big-endian 16-bit limbs [P, 4]
    i32. Runs under the ambient (x64) semantics — the only place the
    pallas path touches a 64-bit array."""
    hi = tie_hi.astype(jnp.uint64)
    lo = tie_lo.astype(jnp.uint64)
    return jnp.stack([((hi >> 16) & 0xFFFF).astype(jnp.int32),
                      (hi & 0xFFFF).astype(jnp.int32),
                      ((lo >> 16) & 0xFFFF).astype(jnp.int32),
                      (lo & 0xFFFF).astype(jnp.int32)], axis=1)


def solve_pallas(inp, pol: Optional[BatchPolicy] = None,
                 interpret: bool = False, gangs: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in twin of ``solve_jit(inp, pol=pol, gangs=gangs)`` for
    eligible waves. The XLA prolog (selector matmul, plane transposition,
    pod-row packing) and the Pallas kernel compile into one program; use
    ``interpret=True`` to run the kernel on CPU for tests.

    The core jit runs (traces, lowers, compiles) under
    ``jax.enable_x64(False)``: with x64 on, weak python-int literals in
    the kernel body and in the BlockSpec index maps materialize as int64,
    and the Mosaic TPU backend either rejects them or — for i64->i32
    conversions routed through its ``_convert_helper`` fallback — recurses
    forever. The only genuinely 64-bit inputs (the tie-break hashes) are
    split into 16-bit limbs outside, under the ambient semantics."""
    if pol is None:
        pol = BatchPolicy()
    limbs = _tie_limbs(inp.tie_hi, inp.tie_lo)
    with jax.enable_x64(False):
        return _solve_pallas_x32(
            *_x32_operands(inp, limbs),
            pol=pol, interpret=interpret, gangs=gangs,
            B=int(os.environ.get("KTPU_PALLAS_BLOCK", "1")))


def _x32_operands(inp, limbs) -> tuple:
    """_solve_pallas_x32's positional operands, from SolverInputs-shaped
    ``inp`` (arrays, or the abstract shapes tests/test_tpu_compile.py
    lowers for the chip's compiler) and the [P, 4] tie-break limbs."""
    return (inp.cap, inp.advertises, inp.fit_used, inp.fit_exceeded,
            inp.score_used, inp.node_ports, inp.node_sel, inp.node_pds,
            inp.node_extra_ok, inp.req, inp.pod_ports, inp.pod_sel,
            inp.pod_pds, inp.pod_host_idx, limbs, inp.pod_gid,
            inp.pod_group_member, inp.group_counts, inp.gang_start,
            inp.zone_idx,
            inp.score_static, inp.node_aff_vals, inp.pod_aff_static,
            inp.anchor_vals0, inp.has_anchor0)


@functools.partial(jax.jit,
                   static_argnames=("pol", "interpret", "gangs", "B"))
def _solve_pallas_x32(cap_in, advertises, fit_used, fit_exceeded,
                      score_used, node_ports, node_sel, node_pds,
                      node_extra_ok, req_in, pod_ports, pod_sel, pod_pds,
                      pod_host_idx, tie_limbs, pod_gid, pod_group_member,
                      group_counts, gang_start, zone_idx,
                      score_static, node_aff_vals, pod_aff_static,
                      anchor_vals0, has_anchor0,
                      *, pol: BatchPolicy, interpret: bool, gangs: bool,
                      B: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    N, R = cap_in.shape
    P = req_in.shape[0]
    Wp = node_ports.shape[1]
    Wd = node_pds.shape[1]
    G = max(group_counts.shape[0], 1)
    L = node_aff_vals.shape[1] if pol.has_affinity else 0
    NR = max(1, -(-N // LANES))
    Npad = NR * LANES
    PR = max(1, -(-P // LANES))

    arange_n = jnp.arange(N, dtype=jnp.int32)
    # ---- static mask (the MXU pre-pass, identical to solve_jit) ----------
    static_mask = jnp.broadcast_to(node_extra_ok[None, :], (P, N))
    if pol.use_selector:
        violations = jnp.dot(pod_sel.astype(jnp.float32),
                             (~node_sel).astype(jnp.float32).T,
                             precision=jax.lax.Precision.HIGHEST)
        static_mask = static_mask & (violations == 0)
    if pol.use_host:
        host_ok = (pod_host_idx[:, None] == -1) | \
                  (pod_host_idx[:, None] == arange_n[None, :])
        static_mask = static_mask & host_ok
    if L:
        # node-selector-pinned affinity constraints are static per pod
        # (predicates.go:247-254); -2 = label not pinned by the selector
        for l in range(L):
            pinned = pod_aff_static[:, l, None]                # [P, 1]
            static_mask = static_mask & (
                (pinned == -2) | (node_aff_vals[None, :, l] == pinned))
    # int32: 4 bytes/node/pod in HBM (~200MB at 10k x 5k), streamed at
    # 20KB/step
    smask = _pad_nodes(static_mask.astype(jnp.int32), Npad, 0)
    smask = smask.reshape(P, NR, LANES)

    # ---- node planes: [axis, NR, 128], padding infeasible ----------------
    def plane(x, fill=0):
        return _pad_nodes(x.T.astype(jnp.int32), Npad,
                          fill).reshape(-1, NR, LANES)

    cap = plane(cap_in)
    fit0 = plane(fit_used)
    score0 = plane(score_used)
    fitexc = _pad_nodes(fit_exceeded.astype(jnp.int32)[None, :], Npad,
                        1).reshape(NR, LANES)
    ports0 = plane(jax.lax.bitcast_convert_type(node_ports, jnp.int32))
    pds0 = plane(jax.lax.bitcast_convert_type(node_pds, jnp.int32))
    gc = group_counts if group_counts.shape[0] else \
        jnp.zeros((1, N + 1), jnp.int32)
    counts0 = _pad_nodes(gc[:, :N].astype(jnp.int32), Npad, 0)
    counts0 = counts0.reshape(G, NR, LANES)
    offl = gc[:, N].astype(jnp.int32)                    # [G], to SMEM
    advx = plane(advertises)
    # NodeLabelPriority static score plane + ServiceAffinity planes/anchors
    extra_args, extra_specs = [], []
    if pol.label_prefs:
        sstat = _pad_nodes(score_static.astype(jnp.int32)[None, :], Npad,
                           0).reshape(NR, LANES)
        extra_args.append(sstat)
        extra_specs.append(pl.BlockSpec((NR, LANES), lambda p: (0, 0)))
    if L:
        affv = plane(node_aff_vals)                  # fill 0 is fine: the
        # padded nodes are statically infeasible, so their codes never win
        anchor0 = jnp.zeros((G, LANES), jnp.int32)
        anchor0 = anchor0.at[:, :L].set(
            anchor_vals0[:G].astype(jnp.int32))
        has0 = jnp.broadcast_to(
            has_anchor0[:G].astype(jnp.int32)[:, None], (G, LANES))
        extra_args += [affv, anchor0, has0]
        extra_specs += [pl.BlockSpec((L, NR, LANES), lambda p: (0, 0, 0)),
                        pl.BlockSpec((G, LANES), lambda p: (0, 0)),
                        pl.BlockSpec((G, LANES), lambda p: (0, 0))]

    # ---- pod rows --------------------------------------------------------
    podrow = jnp.zeros((P, LANES), jnp.int32)
    podrow = podrow.at[:, _REQ0:_REQ0 + R].set(req_in.astype(jnp.int32))
    podrow = podrow.at[:, _PORTS0:_PORTS0 + Wp].set(
        jax.lax.bitcast_convert_type(pod_ports, jnp.int32))
    podrow = podrow.at[:, _PDS0:_PDS0 + Wd].set(
        jax.lax.bitcast_convert_type(pod_pds, jnp.int32))
    podrow = podrow.at[:, _TIE0:_TIE0 + 4].set(tie_limbs)
    podrow = podrow.at[:, _GID].set(pod_gid.astype(jnp.int32))
    for lane in range(-(-pod_group_member.shape[1] // _MEMBER_BITS)):
        part = pod_group_member[:, lane * _MEMBER_BITS:
                                (lane + 1) * _MEMBER_BITS]
        member_bits = jnp.sum(
            part.astype(jnp.int32)
            * (jnp.int32(1) << jnp.arange(part.shape[1], dtype=jnp.int32)
               )[None, :], axis=1)
        podrow = podrow.at[:, _member_lane(lane)].set(member_bits)
    podrow = podrow.at[:, _ZREQ].set(
        jnp.all(req_in == 0, axis=1).astype(jnp.int32))
    if gangs:
        podrow = podrow.at[:, _START].set(gang_start.astype(jnp.int32))
    if L:
        podrow = podrow.at[:, _AFF0:_AFF0 + L].set(
            pod_aff_static.astype(jnp.int32))

    # ---- anti-affinity: the [A, N] zone-index plane, -1 unlabelled ------
    # (padding nodes too), and each label's own value count, to SMEM: the
    # kernel builds each value's mask from the index plane, so VMEM holds
    # one plane a label whatever its vocabulary
    A = len(pol.anti_affinity)
    zone_args, zone_specs = [], []
    if A:
        zidx = zone_idx.astype(jnp.int32)              # [A, N]
        nvals = _value_counts(zidx)                    # [A]
        zidx = _pad_nodes(zidx, Npad, -1).reshape(A, NR, LANES)
        zone_args = [zidx, nvals]
        zone_specs = [pl.BlockSpec((A, NR, LANES), lambda p: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)]

    # B pods per grid step (strictly in pod order): padding rows get an
    # all-zero static mask, so they are infeasible everywhere, commit
    # nothing, and write NEG decisions that the final [:P] slice drops.
    B = B if P >= B else 1
    PB = -(-P // B)
    Ppad = PB * B
    if Ppad != P:
        smask = jnp.pad(smask, ((0, Ppad - P), (0, 0), (0, 0)))
        podrow = jnp.pad(podrow, ((0, Ppad - P), (0, 0)))

    kernel = _make_kernel(P, NR, PR, R, Wp, Wd, G, pol, gangs, B, L)
    state_shapes = [
        pltpu.VMEM((R, NR, LANES), jnp.int32),   # fit
        pltpu.VMEM((R, NR, LANES), jnp.int32),   # score_used
        pltpu.VMEM((Wp, NR, LANES), jnp.int32),  # ports
        pltpu.VMEM((Wd, NR, LANES), jnp.int32),  # pds
        pltpu.VMEM((G, NR, LANES), jnp.int32),   # counts
    ]
    if L:
        state_shapes += [pltpu.VMEM((G, LANES), jnp.int32),   # anchors
                         pltpu.VMEM((G, LANES), jnp.int32)]   # has flags
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(PB,),
        in_specs=[
            pl.BlockSpec((B, NR, LANES), lambda p: (p, 0, 0)),   # smask
            pl.BlockSpec((B, 1, LANES), lambda p: (p, 0, 0)),    # podrow
            pl.BlockSpec(cap.shape, lambda p: (0, 0, 0)),        # cap
            pl.BlockSpec(fit0.shape, lambda p: (0, 0, 0)),
            pl.BlockSpec(score0.shape, lambda p: (0, 0, 0)),
            pl.BlockSpec(fitexc.shape, lambda p: (0, 0)),
            pl.BlockSpec(ports0.shape, lambda p: (0, 0, 0)),
            pl.BlockSpec(pds0.shape, lambda p: (0, 0, 0)),
            pl.BlockSpec((G, NR, LANES), lambda p: (0, 0, 0)),   # counts0
            pl.BlockSpec(memory_space=pltpu.SMEM),               # offl
            pl.BlockSpec(advx.shape, lambda p: (0, 0, 0)),
        ] + extra_specs + zone_specs,
        out_specs=[
            pl.BlockSpec((PR, LANES), lambda p: (0, 0)),
            pl.BlockSpec((PR, LANES), lambda p: (0, 0)),
        ],
        scratch_shapes=state_shapes + (
            # gang checkpoints mirror state_shapes ref-for-ref, then the
            # failed flag
            state_shapes + [pltpu.VMEM((8, LANES), jnp.int32)]
            if gangs else []),
    )
    chosen2d, win2d = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((PR, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((PR, LANES), jnp.int32)],
        interpret=interpret,
    )(smask, podrow.reshape(-1, 1, LANES), cap, fit0, score0, fitexc,
      ports0, pds0, counts0, offl, advx, *extra_args, *zone_args)
    return chosen2d.reshape(-1)[:P], win2d.reshape(-1)[:P]
