"""TPU batch scheduler — the north-star solver.

Lifts the reference's serial per-pod loop (ref:
pkg/scheduler/generic_scheduler.go:54-128 Schedule/findNodesThatFit and
plugin/pkg/scheduler/scheduler.go:90-119 scheduleOne) into ONE compiled XLA
call over a dense (pending_pods x nodes) problem:

- **Batched Filter pre-pass** (MXU): node-selector satisfaction is an exact
  boolean matmul over the interned (key,value) vocabulary; pinned-host masks
  broadcast. This replaces the nodes x predicates short-circuit loop.
- **Sequential commit scan** (`lax.scan` over pods): the reference schedules
  pods one at a time, each decision updating node state before the next; the
  scan reproduces that exactly — per-step vector ops over [N] (resource fit,
  port/PD conflict, LeastRequested + ServiceSpreading scores, deterministic
  tie-break) and a one-hot carry update on the chosen node. Decisions are
  bit-identical to the serial oracle by construction: same integer score
  truncation, same float32 spread rounding, same FNV-1a-mod-count tie-break
  over nodes in list order.

The full policy plugin vocabulary is modeled (models/policy.BatchPolicy —
the jit-static description of the configured predicate/priority sets):

- CheckNodeLabelPresence rides the static ``node_extra_ok`` mask;
- NodeLabelPriority is a static additive score plane;
- CheckServiceAffinity (predicates.go:238-324): constraints pinned by the
  pod's node selector are folded into the static mask; constraints derived
  from the first committed service peer's node ("anchor") are tracked in
  the scan carry — each commit sets the anchor of every service group the
  pod belongs to, exactly reproducing the serial "first pod in list order"
  lookup;
- ServiceAntiAffinity (spreading.go:104-168): per-zone peer counts via
  one-hot matmuls, restricted to nodes feasible for the current pod — the
  serial path computes priorities over the *filtered* node list, so zone
  counts exclude infeasible nodes.

TPU dtype strategy: v5e has no native int64 — every wide i64 op is emulated
as multiple i32 ops. Byte capacities exceed int32, but floor division and
integer comparison are invariant under a common scaling, so the encoder
divides all memory values by their collective gcd; when the scaled wave fits
int32 (it virtually always does — Mi-granular quantities reduce 64Gi to
65536) the whole scan runs native int32, falling back to int64 otherwise.
Host-port / PD sets ride as packed uint32 bitmask words instead of [N, K]
bool planes, so conflict checks are W-word AND+reduce instead of K-lane ops.

Everything is static-shaped, no data-dependent Python control flow — XLA
compiles the whole wave to a single TPU program. Sharding over the node axis
for multi-chip is layered on in kubernetes_tpu.parallel.mesh without
changing this module.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax


def ensure_x64() -> None:
    """The int64 fallback path needs x64; without it jnp silently downcasts
    and 8Gi byte capacities wrap. Called at the array-creation boundary
    (snapshot_to_inputs) rather than at import so merely importing this
    module does not flip process-global dtype semantics."""
    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)


import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.models import gang
from kubernetes_tpu.models.policy import BatchPolicy
from kubernetes_tpu.models.snapshot import ClusterSnapshot
from kubernetes_tpu.ops.kernels import (
    calculate_score as _calculate_score,
    masked_top_count,
    select_kth_true,
    spread_score as _spread_score,
    u64_mod_small as _u64_mod,
)
from kubernetes_tpu.util import metrics, tracing
from kubernetes_tpu.util.metrics import wave_parts

__all__ = ["solve", "solve_jit", "solve_device", "SolverInputs",
           "decisions_to_names", "WaveRouter", "WavePlan", "default_router",
           "snapshot_to_host_inputs", "ship_inputs", "warm_compile"]

NEG = -1  # masked score sentinel (scores are always >= 0)

# kube-preempt score-channel constants (models/preempt.py owns the host
# side): a preempting placement's score is _PSCORE_BASE - band_slot, and
# the preemption node selection maximizes _PREEMPT_BIG - victim_count
# (so the minimum-victim-cost node wins under the same masked_top_count
# machinery; victim counts are bounded far below _PREEMPT_BIG).
_PSCORE_BASE = -2
_PREEMPT_BIG = 1 << 30

_I32_HEADROOM = (2**31 - 1) // 10  # calculate_score multiplies by 10

# KTPU_DEBUG=1: recompute encoder-resident zone_counts0 planes from the
# group_counts/node_zone planes and assert they match (the same class of
# insurance as snapshot.py's _ktpu_rows verification)
_DEBUG_VERIFY_ZONES = os.environ.get("KTPU_DEBUG", "") not in ("", "0")


def derive_zone_counts(node_zone: np.ndarray, group_counts: np.ndarray,
                       V: int) -> np.ndarray:
    """[A, G, V] per-group per-zone peer totals: zone_counts[a, g, v] =
    sum of group_counts[g, n] over nodes n whose zone code for dim ``a``
    is ``v``. Unlabeled nodes (code -1) and the off-list slot N count
    toward no zone — exactly the set the one-hot contraction used to
    cover."""
    A = node_zone.shape[0]
    N = node_zone.shape[1]
    G = group_counts.shape[0]
    out = np.zeros((A, G, V), np.int32)
    gc = np.asarray(group_counts[:, :N], np.int32)
    for a in range(A):
        zi = node_zone[a]
        m = zi >= 0
        if m.any():
            np.add.at(out[a].T, zi[m].astype(np.int64), gc[:, m].T)
    return out


class SolverInputs(NamedTuple):
    """Device-ready arrays (see ClusterSnapshot for shapes/meaning).
    Resource planes are [_, R] with R the wave's resource-dimension count
    (cpu, memory, then node-advertised extras — jit-static); int32 when the
    per-dimension gcd-scaled wave fits, else int64; port/pd sets are packed
    uint32 bitmask words."""

    cap: jnp.ndarray             # [N, R]
    advertises: jnp.ndarray      # [N, R] bool — capacity key present
    fit_used: jnp.ndarray        # [N, R]
    fit_exceeded: jnp.ndarray
    score_used: jnp.ndarray      # [N, R]
    node_ports: jnp.ndarray      # [N, Wp] u32 packed
    node_sel: jnp.ndarray
    node_pds: jnp.ndarray        # [N, Wd] u32 packed
    node_extra_ok: jnp.ndarray
    req: jnp.ndarray             # [P, R]
    pod_ports: jnp.ndarray       # [P, Wp] u32 packed
    pod_sel: jnp.ndarray
    pod_pds: jnp.ndarray         # [P, Wd] u32 packed
    pod_host_idx: jnp.ndarray
    tie_hi: jnp.ndarray
    tie_lo: jnp.ndarray
    pod_gid: jnp.ndarray
    pod_group_member: jnp.ndarray
    group_counts: jnp.ndarray
    gang_start: jnp.ndarray      # [P] bool — rollback checkpoint markers
    # policy extensions (zero-size planes when unused)
    score_static: jnp.ndarray    # [N] i32
    node_aff_vals: jnp.ndarray   # [N, L] i32
    pod_aff_static: jnp.ndarray  # [P, L] i32
    anchor_vals0: jnp.ndarray    # [G, L] i32
    has_anchor0: jnp.ndarray     # [G] bool
    zone_idx: jnp.ndarray        # [A, N] i32 zone codes, -1 unlabeled
    zone_counts0: jnp.ndarray    # [A, G, V] i32 initial per-group peers/zone
    # kube-preempt planes (models/preempt.py). B == 0 compiles the exact
    # pre-preemption program; B > 0 adds the evictable-capacity planes to
    # the scan carry and the minimum-victim-cost preemption sub-program.
    pod_prio: jnp.ndarray        # [P] i32 resolved pod priorities
    pod_can_preempt: jnp.ndarray  # [P] bool — PreemptionPolicy != Never
    band_prio: jnp.ndarray       # [B] i32 band values (BAND_EMPTY padded)
    evict_cap: jnp.ndarray       # [N, B, R] evictable capacity (res dtype)
    evict_cnt: jnp.ndarray       # [N, B] i32 evictable pod counts


def _pack_bits(a: np.ndarray) -> np.ndarray:
    """[R, K] bool -> [R, W] uint32 bitmask words (little-endian bits:
    bit j of word w is column 32 w + j). One ``packbits`` into bytes, the
    bytes of a word in memory order: at a wave's sizes a numpy call costs
    the wave loop a hand-off of the interpreter, not arithmetic."""
    rows, K = a.shape
    W = max(1, (K + 31) // 32)
    words = np.zeros((rows, W * 4), np.uint8)
    words[:, :(K + 7) // 8] = np.packbits(a, axis=1, bitorder="little")
    return words.view("<u4")


def _resource_scales(snap: ClusterSnapshot) -> np.ndarray:
    """Per-dimension gcd of every value in that resource column — dividing a
    whole column by a common factor is exact for each comparison and floor
    division the solver performs. (Memory reduces by Mi granularity; cpu
    milli-values usually by 100.) The per-band evictable sums participate:
    a band subtotal must divide exactly too, and a node TOTAL's gcd can be
    coarser than its per-band parts'."""
    parts = [snap.cap, snap.fit_used, snap.score_used, snap.req]
    if snap.evict_cap is not None and snap.evict_cap.size:
        parts.append(snap.evict_cap.reshape(-1, snap.evict_cap.shape[2]))
    cols = np.concatenate(parts, axis=0)                   # [*, R]
    R = cols.shape[1]
    scales = np.ones(R, np.int64)
    for r in range(R):
        vals = cols[:, r]
        vals = vals[vals != 0]
        if vals.size:
            scales[r] = np.gcd.reduce(np.abs(vals))
    return scales


def _fits_i32(*arrays) -> bool:
    total = 0
    for a in arrays:
        if a.size:
            total = max(total, int(np.abs(a).max()))
    return total <= _I32_HEADROOM


def snapshot_to_inputs(snap: ClusterSnapshot,
                       device=None) -> SolverInputs:
    """encode_snapshot output -> device-resident SolverInputs. ``device``
    pins placement (the wave router's host route); None uses the default
    device and the packed single-shipment transfer when enabled."""
    return ship_inputs(snapshot_to_host_inputs(snap), device)


def snapshot_to_host_inputs(snap: ClusterSnapshot) -> SolverInputs:
    """The host-side (numpy) half of snapshot_to_inputs: scaling, dtype
    narrowing, bit-packing — everything up to the device transfer. Every
    plane anew from the snapshot: the cold path. The wave loop goes through
    models/resident.ResidentPlanes.host_inputs, which keeps the node planes
    between waves and falls back to this whenever it cannot patch them."""
    return host_inputs_scaled(snap)[0]


def host_inputs_scaled(snap: ClusterSnapshot
                       ) -> Tuple[SolverInputs, np.ndarray]:
    """-> (snapshot_to_host_inputs(snap), the [R] resource scales it
    divided by)."""
    ensure_x64()
    scales = _resource_scales(snap)
    g = scales[None, :]                                    # [1, R]
    cap = snap.cap // g
    fit_used = snap.fit_used // g
    score_used = snap.score_used // g
    req = snap.req // g
    N = snap.n_nodes
    R0 = snap.cap.shape[1]
    evict_cap = (snap.evict_cap if snap.evict_cap is not None
                 else np.zeros((N, 0, R0), np.int64)) // g[None, :, :]
    evict_cnt = (snap.evict_cnt if snap.evict_cnt is not None
                 else np.zeros((N, 0), np.int32))

    # int32 is safe when no running sum can reach 2^31/10: the largest
    # initial value plus the whole batch's requests bounds every accumulator
    req_total = req.sum(axis=0, keepdims=True)             # [1, R]
    use_i32 = _fits_i32(cap, fit_used, score_used + req_total,
                        cap + req_total, evict_cap)
    rdt = np.int32 if use_i32 else np.int64

    G = snap.group_counts.shape[0]
    score_static = (snap.score_static if snap.score_static is not None
                    else np.zeros(N, np.int32))
    node_aff_vals = (snap.node_aff_vals if snap.node_aff_vals is not None
                     else np.zeros((N, 0), np.int32))
    node_zone = (snap.node_zone if snap.node_zone is not None
                 else np.zeros((0, N), np.int32))

    host = SolverInputs(
        cap=cap.astype(rdt),
        advertises=np.asarray(snap.advertised, bool),
        fit_used=fit_used.astype(rdt),
        fit_exceeded=np.asarray(snap.fit_exceeded, bool),
        score_used=score_used.astype(rdt),
        node_ports=_pack_bits(snap.node_ports),
        node_sel=np.ascontiguousarray(snap.node_sel),
        node_pds=_pack_bits(snap.node_pds),
        node_extra_ok=np.asarray(snap.node_extra_ok, bool),
        group_counts=np.ascontiguousarray(snap.group_counts),
        score_static=score_static.astype(np.int32),
        node_aff_vals=node_aff_vals.astype(np.int32),
        zone_idx=node_zone.astype(np.int32),
        evict_cap=np.ascontiguousarray(evict_cap.astype(rdt)),
        evict_cnt=np.ascontiguousarray(evict_cnt, np.int32),
        **host_small_planes(snap, node_zone),
        **host_wave_planes(snap, req.astype(rdt), G),
    )
    return host, scales


def host_small_planes(snap: ClusterSnapshot, node_zone: np.ndarray) -> dict:
    """The two resident planes with no node axis (``zone_counts0``,
    ``band_prio``): small, and built whole every wave on either path."""
    zone_counts0 = snap.zone_counts0
    if zone_counts0 is None or _DEBUG_VERIFY_ZONES:
        V = max(1, int(node_zone.max(initial=-1)) + 1)
        want = derive_zone_counts(node_zone, snap.group_counts, V)
    if zone_counts0 is None:
        # per-group per-zone initial peer totals over labeled nodes —
        # derived here for the full encoder; the incremental encoder keeps
        # these resident and hands them down (O(changed) maintenance)
        zone_counts0 = want
    elif _DEBUG_VERIFY_ZONES:
        assert zone_counts0.shape == want.shape and \
            np.array_equal(zone_counts0, want), (
                "resident zone_counts0 diverged from the group_counts/"
                "node_zone planes — the incremental encoder's O(changed) "
                "zone maintenance is out of sync")
    band_prio = (snap.band_prio if snap.band_prio is not None
                 else np.zeros(0, np.int32))
    return {"zone_counts0": np.ascontiguousarray(zone_counts0, np.int32),
            "band_prio": np.ascontiguousarray(band_prio, np.int32)}


def host_wave_planes(snap: ClusterSnapshot, req: np.ndarray, G: int) -> dict:
    """The pod-axis planes (parallel/mesh.WAVE_FIELDS), new every wave;
    ``req`` comes scaled and narrowed from the caller."""
    P = snap.req.shape[0]  # includes pod-axis padding (n_pods is the real count)
    pod_aff_static = (snap.pod_aff_static if snap.pod_aff_static is not None
                      else np.zeros((P, 0), np.int32))
    anchor_vals0 = (snap.anchor_vals0 if snap.anchor_vals0 is not None
                    else np.zeros((G, 0), np.int32))
    has_anchor0 = (snap.has_anchor0 if snap.has_anchor0 is not None
                   else np.zeros(G, bool))
    return dict(
        req=req,
        pod_ports=_pack_bits(snap.pod_ports),
        pod_sel=np.ascontiguousarray(snap.pod_sel),
        pod_pds=_pack_bits(snap.pod_pds),
        pod_host_idx=np.ascontiguousarray(snap.pod_host_idx),
        tie_hi=np.ascontiguousarray(snap.tie_hi),
        tie_lo=np.ascontiguousarray(snap.tie_lo),
        pod_gid=np.ascontiguousarray(snap.pod_gid),
        pod_group_member=np.ascontiguousarray(snap.pod_group_member),
        gang_start=np.asarray(snap.pod_run_start
                              if snap.pod_run_start is not None
                              else np.ones(P, bool), bool),
        pod_aff_static=pod_aff_static.astype(np.int32),
        anchor_vals0=anchor_vals0.astype(np.int32),
        has_anchor0=np.asarray(has_anchor0, bool),
        pod_prio=np.ascontiguousarray(
            snap.pod_prio if snap.pod_prio is not None
            else np.zeros(P, np.int32), np.int32),
        pod_can_preempt=np.asarray(
            snap.pod_can_preempt if snap.pod_can_preempt is not None
            else np.ones(P, bool), bool),
    )


def ship_inputs(host: SolverInputs, device=None) -> SolverInputs:
    """Place host (numpy) SolverInputs onto a device, every plane anew: the
    cold path (the wave loop ships through models/resident.py, which keeps
    the node planes on the device and sends what changed). ``device=None``:
    the default device, via the packed single-shipment transfer when
    enabled. An explicit device (the router's host-CPU route) uses plain
    device_put — packing exists to amortize a fixed per-transfer cost,
    which a host-local backend does not pay."""
    if device is not None:
        return SolverInputs(*(jax.device_put(a, device) for a in host))
    if _pack_transfer_enabled():
        return pack_and_ship(host)
    return SolverInputs(*(jnp.asarray(a) for a in host))


# -- packed transfer ---------------------------------------------------------
# Where every host->device transfer pays a fixed cost, shipping
# SolverInputs' ~32 arrays separately makes small waves transfer-latency-
# bound. Instead the arrays are packed into ONE uint8 buffer host-side
# (memcpy-speed), shipped as a single transfer, and re-materialized on
# device by a jitted program (static offsets per shape bucket; XLA
# bitcasts — backend-independent semantics). Two programs unpack such a
# buffer: models/resident.py's apply program, which the wave loop runs (the
# pod planes and the changed rows of the node planes, patched into the
# planes the device kept), and ``_unpack_device`` below for the callers
# that keep nothing (the whole tree: a compile of its own per shape bucket,
# and on a v5e a slow one, PERF.md).
# KTPU_PACK_TRANSFER, for ``ship_inputs`` alone: auto (default: on for
# non-CPU backends) | on | off.

_PACK_ALIGN = 8


def _pack_transfer_enabled() -> bool:
    mode = os.environ.get("KTPU_PACK_TRANSFER", "auto").strip().lower()
    if mode in ("on", "1", "true"):
        return True
    if mode in ("off", "0", "false"):
        return False
    if mode != "auto":
        raise ValueError(
            f"KTPU_PACK_TRANSFER={mode!r}: expected on|off|auto")
    return jax.default_backend() != "cpu"


def _pack_spec(arrays):
    """-> (hashable spec, total bytes). Offsets are _PACK_ALIGN-aligned."""
    spec = []
    off = 0
    for a in arrays:
        off = (off + _PACK_ALIGN - 1) // _PACK_ALIGN * _PACK_ALIGN
        spec.append((str(a.dtype), tuple(a.shape), off, int(a.nbytes)))
        off += a.nbytes
    return tuple(spec), off


def pack_arrays(arrays) -> Tuple[np.ndarray, tuple]:
    """-> (one uint8 buffer holding every array, the spec that unpacks it).
    One concatenate of byte views and alignment gaps, not a copy an array:
    each numpy call of this size hands the interpreter away."""
    spec, total = _pack_spec(arrays)
    pieces, end = [], 0
    for a, (_, _, off, nb) in zip(arrays, spec):
        if off > end:
            pieces.append(np.zeros(off - end, np.uint8))
        pieces.append(np.ascontiguousarray(a).reshape(-1).view(np.uint8))
        end = off + nb
    return np.concatenate(pieces), spec


def pack_and_ship(host: "SolverInputs") -> "SolverInputs":
    buf, spec = pack_arrays(host)
    return SolverInputs(*_unpack_device(jnp.asarray(buf), spec))


def unpack_arrays(buf: jnp.ndarray, spec) -> tuple:
    """Traced: the arrays of ``pack_arrays`` out of the device buffer."""
    out = []
    for dtype_str, shape, off, nb in spec:
        seg = jax.lax.slice(buf, (off,), (off + nb,))
        dt = np.dtype(dtype_str)
        if dt == np.bool_:
            arr = (seg != 0).reshape(shape)
        elif dt.itemsize == 1:
            arr = jax.lax.bitcast_convert_type(seg, dt).reshape(shape)
        else:
            arr = jax.lax.bitcast_convert_type(
                seg.reshape(-1, dt.itemsize), jnp.dtype(dtype_str)
            ).reshape(shape)
        out.append(arr)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("spec",))
def _unpack_device(buf: jnp.ndarray, spec) -> tuple:
    return unpack_arrays(buf, spec)


@functools.partial(jax.jit,
                   static_argnames=("w_lr", "w_spread", "w_equal", "unroll",
                                    "pol", "gangs", "zone_bf16"))
def solve_jit(inp: SolverInputs, w_lr: int = 1, w_spread: int = 1,
              w_equal: int = 0, unroll: int = 1,
              pol: Optional[BatchPolicy] = None, gangs: bool = False,
              zone_bf16: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Solve one wave. Returns (chosen_node_idx[P] int32 — -1 unschedulable,
    scores[P] int32 — the winning combined score, -1 if unschedulable).

    ``pol`` is the static policy description; when omitted, the default
    provider's plugin set with the given legacy weights applies.

    ``gangs`` enables all-or-nothing PodGroup runs (models/gang.py): the
    scan carries a checkpoint of its committed state from each run's first
    member; a failing member restores it — later pods schedule as if the
    failed group never placed — and blocks the run's remaining members.
    Callers then drop the failed runs' earlier tentative choices with
    gang.apply_all_or_nothing. Off by default: the checkpoint copy doubles
    the carry, so waves without gangs compile the original program.

    ``zone_bf16`` stores the anti-affinity zone scatter basis and the
    per-step infeasible-peer contraction in bfloat16 instead of float32.
    Exact — hence still bit-identical to the serial oracle — ONLY under
    the caller-checked bound that every peer count the contraction can
    see stays <= 256 (integers through 256 are exact in bf16's 8-bit
    significand; the f32 accumulator keeps the sums exact). Gated by
    models/submesh.zone_bf16_ok and proven live by the submesh parity
    probe; never flipped on the default path."""
    if pol is None:
        pol = BatchPolicy(w_lr=w_lr, w_spread=w_spread, w_equal=w_equal)
    N, R = inp.cap.shape
    P = inp.req.shape[0]
    L = inp.node_aff_vals.shape[1]
    rdt = inp.cap.dtype
    arange_n = jnp.arange(N, dtype=jnp.int32)
    # per-dim fit rule (serial twin: predicates.dim_fits): cpu/memory —
    # always dims 0,1 — are unconstrained at zero capacity (reference
    # parity); extended dims are strict, so a GPU pod can't land GPU-less
    unconstrained = (inp.cap == 0) & (jnp.arange(R) < 2)[None, :]  # [N, R]
    # extra dims a node advertises — the per-step LeastRequested divisor is
    # 2 + however many of these some FEASIBLE node advertises, because the
    # serial path prioritizes over the filtered node list and so derives
    # its resource universe from exactly that subset
    # (generic_scheduler.go:70-75; priorities.least_requested_priority).
    # Name presence, not cap != 0: a zero-quantity advertisement still
    # widens the serial universe (resource_universe iterates keys).
    adv_extra = inp.advertises & (jnp.arange(R) >= 2)[None, :]     # [N, R]

    if pol.all_infeasible:
        # no nonzero-weight priorities: prioritizeNodes emits nothing and
        # Schedule fails every pod (generic_scheduler.go:76-80)
        return (jnp.full(P, -1, jnp.int32), jnp.full(P, NEG, jnp.int32))

    # ---- batched Filter pre-pass (MXU) -----------------------------------
    static_mask = jnp.broadcast_to(inp.node_extra_ok[None, :], (P, N))
    if pol.use_selector:
        # selector violations: required pairs the node lacks. int8 inputs
        # with an int32 accumulator — integer arithmetic, exact at any
        # vocabulary width (counts bound by the [S] axis << 2^31), and the
        # narrowest MXU-native operand dtype: a quarter the f32 plane
        # bytes the former HIGHEST-precision float path streamed.
        violations = jnp.dot(inp.pod_sel.astype(jnp.int8),
                             (~inp.node_sel).astype(jnp.int8).T,
                             preferred_element_type=jnp.int32)  # [P, N]
        static_mask = static_mask & (violations == 0)
    if pol.use_host:
        host_ok = (inp.pod_host_idx[:, None] == -1) | \
                  (inp.pod_host_idx[:, None] == arange_n[None, :])
        static_mask = static_mask & host_ok
    if pol.has_affinity:
        # node-selector-pinned affinity constraints are static per pod
        # (predicates.go:247-254); -2 = label not pinned by the selector
        for l in range(L):
            pinned = inp.pod_aff_static[:, l, None]            # [P, 1]
            static_mask = static_mask & (
                (pinned == -2) | (inp.node_aff_vals[None, :, l] == pinned))

    # ---- sequential commit scan over pods --------------------------------
    class Carry(NamedTuple):
        fit_used: jnp.ndarray        # [N, R] resource dtype
        score_used: jnp.ndarray      # [N, R]
        ports: jnp.ndarray           # [N, Wp] u32 packed
        pds: jnp.ndarray             # [N, Wd] u32 packed
        counts: jnp.ndarray          # [G, N+1] i32
        anchor_vals: jnp.ndarray     # [G, L] i32
        has_anchor: jnp.ndarray      # [G] bool
        zone_counts: jnp.ndarray     # [A, G, V] i32 peers per zone
        evict_cap: jnp.ndarray       # [N, B, R] evictable capacity
        evict_cnt: jnp.ndarray       # [N, B] i32 evictable pod counts

    V = inp.zone_counts0.shape[2]
    B = inp.band_prio.shape[0]
    # kube-preempt sub-program: compiled only when the encoder's emit gate
    # shipped bands (models/preempt.py) — a B == 0 wave runs the exact
    # legacy program, zero-size carry planes included
    enable_p = B > 0 and pol.use_resources
    if pol.anti_affinity:
        # scan-invariant zone scatter basis, derived on device once per
        # wave (XLA hoists it out of the scan): the wire/encoder ship only
        # the compact [A, N] index plane. Under the zone_bf16 gate the
        # basis (0/1 — exact in any float dtype) and the peer-count
        # operand ride in bf16; the f32 accumulator keeps sums exact.
        _zdt = jnp.bfloat16 if zone_bf16 else jnp.float32
        zone_onehot = (inp.zone_idx[:, :, None] ==
                       jnp.arange(V, dtype=jnp.int32)[None, None, :]
                       ).astype(_zdt)                        # [A, N, V]
    init = Carry(inp.fit_used, inp.score_used,
                 inp.node_ports, inp.node_pds, inp.group_counts,
                 inp.anchor_vals0, inp.has_anchor0, inp.zone_counts0,
                 inp.evict_cap, inp.evict_cnt)

    # Per-node LeastRequested reciprocal magics, one [N, R] integer-divide
    # pass per WAVE instead of one per STEP: for d = safe_cap and
    # M = floor(2^32 / d), floor(x / d) differs from (x * M) >> 32 by at
    # most one for every 0 <= x <= 10d when d < 2^28 (the error term is
    # x * (2^32 - M * d) / (d * 2^32) <= 10d / 2^32 < 1), so a single
    # compare-and-increment fixup recovers the exact quotient with only
    # vectorizable multiplies — XLA CPU cannot vectorize the integer
    # divides the scan otherwise pays at [N, R] per step. Applied only to
    # int32 resource planes, whose encoder contract (cap * 10 fits the
    # dtype) bounds d under the 2^28 proof bound.
    lr_magic = bool(pol.w_lr) and rdt == jnp.int32
    if lr_magic:
        safe_cap = jnp.where(inp.cap == 0, 1, inp.cap).astype(jnp.int64)
        cap_magic = (jnp.int64(1) << 32) // safe_cap           # [N, R]

    def step(carry: Carry, xs, blocked=None):
        (static_row, req, pod_ports, pod_pds,
         tie_hi, tie_lo, gid, member, aff_static, prio, can_p) = xs[:11]

        feasible = static_row
        if blocked is not None:
            # remaining members of an already-failed gang place nowhere
            feasible = feasible & ~blocked
        if pol.use_ports:
            # Filter: host ports (predicates.go:326-338) — packed-word AND,
            # branched out entirely for the (common) portless pod: ANDing
            # an all-zero word is the identity, so the taken branch is a
            # constant all-True row and the [N, Wp] plane never streams
            feasible = feasible & jax.lax.cond(
                jnp.any(pod_ports != 0),
                lambda: ~jnp.any(carry.ports & pod_ports[None, :] != 0,
                                 axis=1),
                lambda: jnp.ones(N, bool))
        if pol.use_disk:
            # Filter: GCE PD exclusivity (predicates.go:68-83) — same
            # zero-word branch as ports
            feasible = feasible & jax.lax.cond(
                jnp.any(pod_pds != 0),
                lambda: ~jnp.any(carry.pds & pod_pds[None, :] != 0,
                                 axis=1),
                lambda: jnp.ones(N, bool))
        if pol.has_affinity:
            # anchor-derived constraints (predicates.go:256-276): apply for
            # labels the selector didn't pin, once the group has a peer
            safe_g = jnp.maximum(gid, 0)
            row = carry.anchor_vals[safe_g]                    # [L]
            has = (gid >= 0) & carry.has_anchor[safe_g]
            dyn = jnp.ones(N, bool)
            for l in range(L):
                need = (aff_static[l] == -2) & (row[l] >= 0)
                dyn = dyn & (~need | (inp.node_aff_vals[:, l] == row[l]))
            feasible = feasible & (~has | dyn)
        # everything except resources — the preemption branch re-checks
        # resource fit with freed capacity against exactly this base
        # (victims conservatively keep their ports/PDs/group membership
        # for the rest of the wave, so only the resource term may relax)
        feasible_nores = feasible
        if pol.use_resources:
            # Filter: resources over all R dims (predicates.go:127-152 —
            # a pod requesting zero of everything always fits; pre-exceeded
            # nodes fail; per-dim rule per ``unconstrained`` above)
            res_ok = jnp.all(unconstrained |
                             (inp.cap - carry.fit_used >= req[None, :]),
                             axis=1)
            zero_req = jnp.all(req == 0)
            # fit_exceeded is static: committed pending pods always fit, so
            # they never flip a node into the pre-exceeded state.
            feasible = feasible & \
                (zero_req | (~inp.fit_exceeded & res_ok))

        score = jnp.zeros(N, jnp.int32)
        if pol.w_lr:
            # Score: LeastRequested (priorities.go:41-75 — all-pods usage),
            # averaged over the dims the FEASIBLE nodes advertise (sum //
            # n_dyn == the reference's (cpu+mem)/2 when only cpu+memory are
            # advertised; dims advertised by no feasible node score 0 on
            # every node, so only the divisor varies with the filter)
            n_dyn = (jnp.asarray(2, rdt) +
                     jnp.sum((adv_extra & feasible[:, None]).any(axis=0)
                             ).astype(rdt))
            total = carry.score_used + req[None, :]
            if lr_magic:
                # magic-multiply twin of _calculate_score (proof at
                # cap_magic): identical values lane-for-lane — discarded
                # lanes are pinned to 0 by the same zero/exceeded rule
                x = jnp.maximum((inp.cap - total) * jnp.asarray(10, rdt),
                                0).astype(jnp.int64)
                q = (x * cap_magic) >> 32
                q = q + (x - (q + 1) * safe_cap >= 0)
                cs = jnp.where((inp.cap == 0) | (total > inp.cap),
                               0, q).astype(rdt)
                raw = cs.sum(axis=1)
            else:
                raw = _calculate_score(total, inp.cap).sum(axis=1)
            if R <= 256:
                # raw is a sum of R per-dim scores each in [0, 10], so
                # raw <= 10R and n_dyn <= R: floor(raw / n_dyn) ==
                # (raw * (2^20 // n_dyn + 1)) >> 20 exactly (magic
                # error e <= n_dyn needs raw * e < 2^20 — 10R * R fits
                # for R <= 256, and the product stays under 2^31).
                # One scalar divide per step instead of an [N] integer-
                # divide pass, which XLA CPU cannot vectorize
                magic = jnp.asarray(1 << 20, rdt) // n_dyn + 1
                lr = ((raw * magic) >> 20).astype(jnp.int32)
            else:
                lr = (raw // n_dyn).astype(jnp.int32)
            score = score + lr * pol.w_lr
        if pol.w_spread:
            # Score: ServiceSpreading (spreading.go:37-86) — branched out
            # entirely for the serviceless pod, whose score is the
            # constant 10 on every node (spreading.go:42-44)
            def _spread_on():
                counts_row = carry.counts[jnp.maximum(gid, 0)]  # [N+1]
                return _spread_score(jnp.max(counts_row), counts_row[:N])
            spread = jax.lax.cond(
                gid >= 0, _spread_on,
                lambda: jnp.full((N,), jnp.int32(10)))
            score = score + spread * pol.w_spread
        if pol.anti_affinity:
            counts_row = carry.counts[jnp.maximum(gid, 0)]     # [N+1]
        for a, (_label, w) in enumerate(pol.anti_affinity):
            # Score: ServiceAntiAffinity (spreading.go:104-168). The serial
            # path scores over the FILTERED node list, so per-zone counts
            # include only nodes feasible for this pod; peers off-list
            # (slot N) and on infeasible nodes don't count. The per-zone
            # totals over ALL labeled nodes ride the carry (seeded from
            # the encoder's resident zone_counts0 plane, updated one-hot
            # per commit); the per-step work is only the exact integer
            # subtraction of peers sitting on infeasible labeled nodes —
            # O(N) segment arithmetic instead of the former two [N, V]
            # one-hot matmuls per step.
            counts_eff = jnp.where(gid >= 0, counts_row, jnp.int32(0))
            num = jnp.sum(counts_eff)
            zi = inp.zone_idx[a]                                    # [N]
            labeled = zi >= 0
            safe_zi = jnp.where(labeled, zi, 0)
            zrow = jnp.where(gid >= 0,
                             carry.zone_counts[a, jnp.maximum(gid, 0)],
                             jnp.int32(0))                          # [V]
            # peers on infeasible labeled nodes, folded per zone: one
            # [N, V] contraction (f32: HIGHEST, exact for integers <
            # 2^24; bf16 under the gated <= 256 peer bound — either way
            # accumulated in f32, so the fold is exact integer math);
            # unlabeled nodes have an all-zero one-hot row
            c_inf = (counts_eff[:N] * ~feasible).astype(_zdt)
            zc = zrow - jnp.matmul(
                zone_onehot[a].T, c_inf,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).astype(jnp.int32)
            cnt = jnp.where(labeled, jnp.take(zc, safe_zi),
                            jnp.int32(0))                           # [N]
            s = _spread_score(num, cnt)
            s = jnp.where(labeled, s, jnp.int32(0))
            score = score + s * w
        if pol.label_prefs:
            score = score + inp.score_static
        if pol.w_equal:
            score = score + jnp.int32(pol.w_equal)
        masked = jnp.where(feasible, score, jnp.int32(NEG))

        # select host (generic_scheduler.go:84-96, deterministic tie-break)
        top, any_feasible, best, cnt = masked_top_count(masked, NEG)
        best = best & feasible
        k = _u64_mod(tie_hi, tie_lo, cnt)
        chosen = select_kth_true(best, k)
        chosen = jnp.where(any_feasible, chosen, jnp.int32(-1))
        win_score = jnp.where(any_feasible, top, jnp.int32(NEG))

        if enable_p:
            # ---- preemption (kube-preempt; models/preempt.py rule) -------
            # Considered only when NO node is normally feasible and the
            # pod may preempt. Candidate victim sets are priority-prefix
            # sets per node: threshold t over bands strictly below the
            # pod's priority; freed(t) is monotone, so the minimal
            # fitting t is the lowest-sufficient set. Across nodes the
            # minimal victim COUNT wins, normal FNV tie-break among ties.
            below = inp.band_prio < prio                          # [B]
            # leq[b, c]: band b evicts under threshold band c
            leq = (inp.band_prio[:, None] <= inp.band_prio[None, :]) \
                & below[:, None]                                  # [B, B]
            # dtype pins: jnp.sum would promote i32 to i64 under x64
            freed = jnp.sum(carry.evict_cap[:, :, None, :]
                            * leq.astype(rdt)[None, :, :, None],
                            axis=1, dtype=rdt)                    # [N, B, R]
            ccost = jnp.sum(carry.evict_cnt[:, :, None]
                            * leq.astype(jnp.int32)[None, :, :],
                            axis=1, dtype=jnp.int32)              # [N, B]
            head = (inp.cap - carry.fit_used)[:, None, :] + freed
            fits = jnp.all(unconstrained[:, None, :] |
                           (head >= req[None, None, :]), axis=2)  # [N, B]
            fits = fits & below[None, :] & feasible_nores[:, None] \
                & (~inp.fit_exceeded)[:, None]
            node_fits = fits.any(axis=1)
            # minimal sufficient threshold per node (band values are
            # distinct by vocabulary construction; BAND_EMPTY slots never
            # fit because ``below`` is False there)
            bidx = jnp.argmin(jnp.where(fits, inp.band_prio[None, :],
                                        jnp.int32(2**31 - 1)),
                              axis=1).astype(jnp.int32)           # [N]
            cost = jnp.take_along_axis(
                ccost, bidx[:, None], axis=1)[:, 0]               # [N]
            pmask = node_fits & can_p
            masked_p = jnp.where(pmask, jnp.int32(_PREEMPT_BIG) - cost,
                                 jnp.int32(NEG))
            _ptop, p_any, pbest, pcnt = masked_top_count(masked_p, NEG)
            pbest = pbest & pmask
            pchosen = select_kth_true(pbest, _u64_mod(tie_hi, tie_lo,
                                                      pcnt))
            pchosen = jnp.where(p_any, pchosen, jnp.int32(-1))
            did_preempt = ~any_feasible & (pchosen >= 0)
            chosen = jnp.where(any_feasible, chosen, pchosen)
            safe_c = jnp.maximum(chosen, 0)
            bsel = bidx[safe_c]
            # the score channel reports the threshold band slot
            # (models/preempt.preempt_score) so the host-side victim
            # replay can expand the decision without extra outputs
            win_score = jnp.where(
                any_feasible, win_score,
                jnp.where(did_preempt,
                          jnp.int32(_PSCORE_BASE) - bsel, jnp.int32(NEG)))
            evicted = leq[:, bsel] & did_preempt                  # [B]
            freed_sel = jnp.where(did_preempt, freed[safe_c, bsel],
                                  jnp.zeros_like(freed[0, 0]))    # [R]
        else:
            did_preempt = jnp.bool_(False)
            evicted = jnp.zeros((B,), bool)
            freed_sel = jnp.zeros((R,), rdt)

        # commit: dynamic-row scatter of every accumulator at the chosen
        # node. The former one-hot mul-add streamed every [N, R]/[N, W]
        # carry plane through memory per step; the scatter touches ONE
        # row (exact: the delta is zero off-row, and an unplaced pod
        # adds an all-zero row at index 0 — integer + 0 is the identity)
        safe_row = jnp.maximum(chosen, 0)
        placed = chosen >= 0
        if pol.has_affinity:
            committed = chosen >= 0
            chosen_vals = inp.node_aff_vals[jnp.maximum(chosen, 0)]  # [L]
            newly = member & ~carry.has_anchor & committed
            anchor_vals = jnp.where(newly[:, None], chosen_vals[None, :],
                                    carry.anchor_vals)
            has_anchor = carry.has_anchor | newly
        else:
            anchor_vals = carry.anchor_vals
            has_anchor = carry.has_anchor
        if pol.anti_affinity:
            # mirror of the counts update in zone space: every group the
            # pod belongs to gains one peer in the chosen node's zone
            # (nothing when unplaced or the chosen node is unlabeled)
            zv = inp.zone_idx[:, jnp.maximum(chosen, 0)]         # [A]
            zhit = ((chosen >= 0) & (zv >= 0))[:, None, None]    # [A, 1, 1]
            zone_counts = carry.zone_counts + (
                member[None, :, None] & zhit &
                (jnp.arange(V, dtype=jnp.int32)[None, None, :]
                 == zv[:, None, None])).astype(jnp.int32)
        else:
            zone_counts = carry.zone_counts
        # preemption eviction lands with the commit: the chosen node's
        # evicted-band capacity leaves both accumulators and the evictable
        # planes zero out there — later pods see the post-eviction cluster
        row_delta = jnp.where(placed, req - freed_sel, jnp.zeros_like(req))
        carry = Carry(
            fit_used=carry.fit_used.at[safe_row].add(row_delta),
            score_used=carry.score_used.at[safe_row].add(row_delta),
            ports=carry.ports.at[safe_row].set(
                carry.ports[safe_row]
                | jnp.where(placed, pod_ports, jnp.uint32(0))),
            pds=carry.pds.at[safe_row].set(
                carry.pds[safe_row]
                | jnp.where(placed, pod_pds, jnp.uint32(0))),
            counts=carry.counts.at[:, safe_row].add(
                (member & placed).astype(jnp.int32)),
            anchor_vals=anchor_vals,
            has_anchor=has_anchor,
            zone_counts=zone_counts,
            evict_cap=carry.evict_cap.at[safe_row].set(
                jnp.where(evicted[:, None], jnp.zeros((), rdt),
                          carry.evict_cap[safe_row])),
            evict_cnt=carry.evict_cnt.at[safe_row].set(
                jnp.where(evicted, jnp.int32(0),
                          carry.evict_cnt[safe_row])),
        )
        return carry, (chosen, win_score)

    xs = (static_mask, inp.req, inp.pod_ports, inp.pod_pds,
          inp.tie_hi, inp.tie_lo, inp.pod_gid, inp.pod_group_member,
          inp.pod_aff_static, inp.pod_prio, inp.pod_can_preempt)
    if not gangs:
        _, (chosen, scores) = jax.lax.scan(step, init, xs, unroll=unroll)
        return chosen, scores

    def gang_step(carry, x):
        state, ckpt, failed = carry
        core, start = x[:-1], x[-1]
        # a new scheduling unit begins: checkpoint the committed state
        ckpt = jax.tree.map(lambda s, c: jnp.where(start, s, c), state, ckpt)
        failed = failed & ~start
        new_state, (chosen, win) = step(state, core, blocked=failed)
        failed = failed | (chosen < 0)
        # rollback: a failed run's commits (including this step's no-op)
        # are undone, pinning the state at the checkpoint until the run ends
        new_state = jax.tree.map(lambda c, n: jnp.where(failed, c, n),
                                 ckpt, new_state)
        return (new_state, ckpt, failed), (chosen, win)

    _, (chosen, scores) = jax.lax.scan(
        gang_step, (init, init, jnp.bool_(False)),
        xs + (inp.gang_start,), unroll=unroll)
    return chosen, scores


def solve_device(inp: SolverInputs, pol: Optional[BatchPolicy],
                 gangs: bool, peer_bound: int, force_scan: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compiled-solve dispatcher. Default-policy int32 waves (gang or
    not) on a real TPU run the Pallas sequential-commit kernel
    (ops/pallas_solver — state resident in VMEM, ~4.5x faster than the
    lax.scan at 10k x 5k and bit-identical by construction); everything
    else takes the XLA scan. ``KTPU_PALLAS``: auto (default, TPU only) |
    off | interpret (run the kernel through the Pallas interpreter — any
    backend, tests). ``force_scan`` pins the XLA scan regardless — the
    wave router's host-CPU route passes it because its inputs live on
    the CPU device even when the process default backend is a TPU."""
    from kubernetes_tpu.ops import pallas_solver

    mode = os.environ.get("KTPU_PALLAS", "auto")
    use = (not force_scan
           and mode in ("auto", "interpret")
           and pallas_solver.eligible(inp, pol or BatchPolicy(), gangs,
                                      peer_bound)
           and (mode == "interpret" or jax.default_backend() == "tpu"))
    # the kernel gives way to the scan without a word (backend, domain,
    # mode): the counter is what says which program a wave really took
    devices = getattr(inp.cap, "devices", None)
    wave_programs().inc(
        "pallas" if use else "scan",
        next(iter(devices())).platform if devices
        else jax.default_backend())
    if use:
        return pallas_solver.solve_pallas(inp, pol=pol or BatchPolicy(),
                                          interpret=(mode == "interpret"),
                                          gangs=gangs)
    return solve_jit(inp, pol=pol, gangs=gangs)


def wave_programs() -> metrics.Counter:
    """Waves by the program that solved them — ``pallas`` kernel or XLA
    ``scan`` (counted by solve_device), ``scan-sharded`` (the GSPMD scan
    of solve's mesh arm) — and the platform their inputs lived on: a TPU
    process's host-routed waves count under ``cpu``."""
    return metrics.default_registry().counter(
        "solver_wave_program_total",
        "Waves dispatched by solve_device or solve's mesh arm, by compiled "
        "program and the platform of the device(s) that held their inputs",
        ("program", "platform"))


def mesh_placed_bytes() -> metrics.Counter:
    """Bytes that crossed from the host to the device(s) of ``solve``, on
    either arm: a wave's packed buffer (the pod planes and the dirty rows)
    where the node planes are resident, every plane (padded to the mesh, a
    replicated plane counted once) where they are placed whole."""
    return metrics.default_registry().counter(
        "solver_mesh_placed_bytes_total",
        "Bytes of solver planes that crossed from the host to the device(s) "
        "of in-process waves")


def peer_bound_of(source) -> int:
    """Largest initial per-group peer total — the pallas-eligibility bound
    on spread/anti-affinity arithmetic. ``source`` is anything carrying a
    ``group_counts`` [G, N+1] array: a ClusterSnapshot (numpy, host-side)
    or a SolverInputs (device array; int() forces one readback)."""
    gc = source.group_counts
    return int(gc.sum(axis=1).max()) if gc.size else 0


# -- host-vs-device wave router ---------------------------------------------
# Where a wave pays a fixed dispatch cost on the device, small waves are
# dispatch-bound there yet take tens of ms on the host CPU backend. The
# router times BOTH full pipelines (ship + solve + readback) once per
# shape bucket and thereafter routes the bucket to the measured winner.
# The reference's analog of taking the cheap path: it schedules small
# clusters serially with no batching at all
# (ref: plugin/pkg/scheduler/scheduler.go:87-90).
#
# KTPU_WAVE_ROUTER: auto (default: calibrate when a CPU device exists
# beside a non-CPU default backend and the wave is small enough that the
# host could plausibly win) | off | host | device.

_ROUTER_MAX_HOST_CELLS = 1 << 23  # beyond ~8M pod*node cells the device
                                  # always wins; skip paying a CPU compile


def _host_cpu_device():
    """The CPU device to route host waves to, or None when routing is
    moot (CPU is already the default backend, or no CPU backend exists —
    e.g. JAX_PLATFORMS pins the accelerator alone)."""
    try:
        if jax.default_backend() == "cpu":
            return None
        devs = jax.local_devices(backend="cpu")
    except RuntimeError:
        return None
    return devs[0] if devs else None


class WavePlan(NamedTuple):
    path: str        # "host" | "device"
    device: object   # jax.Device for the host route, None for default
    host_s: float    # calibration steady pipeline times (nan: not measured)
    device_s: float
    cold_s: float    # chosen path's FIRST pipeline run (compile + per-shape
                     # transfer setup + one run; nan when not calibrated)


_NAN = float("nan")
_PLAN_DEVICE = WavePlan("device", None, _NAN, _NAN, _NAN)


class WaveRouter:
    """Measured host-vs-device dispatch, cached per shape bucket (the
    incremental encoder's pow-2 bucketing keeps the bucket set finite, so
    calibration is a once-per-shape cost like XLA compilation).

    Calibrations persist: ``load_calibrations(path)`` (wired by
    util/warmstart.enable) restores prior measured plans keyed by the
    same (shapes, policy, gangs, pallas-eligibility) tuple — serialized
    via its stable repr — so a restarted scheduler skips the O(seconds..
    minutes) per-shape calibration the same way the JAX persistent
    compilation cache skips the compile. Timings are machine-local, which
    is exactly what a repo-local cache dir scopes them to."""

    def __init__(self, cal_runs: int = 2):
        self.cal_runs = cal_runs
        self._plans: dict = {}
        self._lock = threading.Lock()
        self._persisted: dict = {}   # repr(key) -> plan fields
        self._cal_path: Optional[str] = None

    # -- persistence --------------------------------------------------------
    def load_calibrations(self, path: str) -> int:
        """Point the router at a calibration store, loading any prior
        plans. Returns the number of usable entries. Unreadable or
        version-skewed files are ignored (calibration is always safe to
        re-pay)."""
        with self._lock:
            self._cal_path = path
        try:
            with open(path) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            return 0
        if not isinstance(data, dict) or data.get("v") != 1:
            return 0
        plans = data.get("plans")
        if not isinstance(plans, dict):
            return 0
        with self._lock:
            self._persisted.update(plans)
            return len(plans)

    @staticmethod
    def _cal_key(key) -> str:
        """Persisted-store key: the in-memory plan key PLUS the default
        backend and its device count (the mesh shape). Calibration
        timings are a property of the attached devices — a 'device' plan
        measured on a TPU must never be restored into a CPU-only restart,
        and a plan measured on one host device must not leak into a run
        where --xla_force_host_platform_device_count carved the same
        cores into an 8-device sub-mesh (different threadpool split,
        different timings)."""
        return f"{jax.default_backend()}x{jax.device_count()}|{key!r}"

    def save_calibrations(self) -> None:
        """Best-effort atomic write of every known plan (persisted +
        this process's fresh calibrations) to the configured store."""
        with self._lock:
            path = self._cal_path
            if not path:
                return
            merged = dict(self._persisted)
            for key, plan in self._plans.items():
                if plan.host_s == plan.host_s:  # calibrated plans only
                    merged[self._cal_key(key)] = {
                        "path": plan.path, "host_s": plan.host_s,
                        "device_s": plan.device_s, "cold_s": plan.cold_s}
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as fh:
                json.dump({"v": 1, "plans": merged}, fh)
            os.replace(tmp, path)
        except OSError:
            pass

    def _from_persisted(self, key, cpu) -> Optional[WavePlan]:
        with self._lock:
            rec = self._persisted.get(self._cal_key(key))
        if not isinstance(rec, dict):
            return None
        try:
            if rec["path"] == "host":
                plan = WavePlan("host", cpu, float(rec["host_s"]),
                                float(rec["device_s"]), float(rec["cold_s"]))
            else:
                plan = WavePlan("device", None, float(rec["host_s"]),
                                float(rec["device_s"]), float(rec["cold_s"]))
        except (KeyError, TypeError, ValueError):
            return None
        with self._lock:
            self._plans[key] = plan
        return plan

    def plan_for(self, host: SolverInputs, pol, gangs: bool,
                 peer_bound: int) -> WavePlan:
        mode = os.environ.get("KTPU_WAVE_ROUTER", "auto").strip().lower()
        if mode not in ("auto", "off", "host", "device"):
            # validate BEFORE any environment-dependent early-outs: a typo
            # must fail the same way on CPU-only CI as on the live TPU
            raise ValueError(
                f"KTPU_WAVE_ROUTER={mode!r}: expected auto|off|host|device")
        if mode in ("off", "device"):
            return _PLAN_DEVICE
        cpu = _host_cpu_device()
        if cpu is None:
            return _PLAN_DEVICE
        if mode == "host":
            return WavePlan("host", cpu, _NAN, _NAN, _NAN)
        P, N = host.req.shape[0], host.cap.shape[0]
        if P * N > _ROUTER_MAX_HOST_CELLS:
            return _PLAN_DEVICE
        # the device path compiles a different program when the Pallas
        # kernel is eligible — key the cached timings on that variant, not
        # just the shapes (peer_bound flips eligibility at equal shapes)
        from kubernetes_tpu.ops import pallas_solver
        elig = pallas_solver.eligible(host, pol or BatchPolicy(), gangs,
                                      peer_bound)
        key = (tuple((a.dtype.str, a.shape) for a in host), pol, gangs, elig)
        with self._lock:
            plan = self._plans.get(key)
        if plan is None:
            plan = self._from_persisted(key, cpu)
        if plan is None:
            plan = self._calibrate(host, pol, gangs, peer_bound, cpu)
            with self._lock:
                self._plans[key] = plan
            self.save_calibrations()
        return plan

    def _time_path(self, host, pol, gangs, peer_bound, device):
        """-> (cold_s, steady_s): first full pipeline (compile + per-shape
        transfer setup + run), then the best of cal_runs steady runs."""
        force_scan = device is not None

        def once() -> float:
            t0 = time.perf_counter()
            inp = ship_inputs(host, device)
            chosen, scores = solve_device(inp, pol, gangs, peer_bound,
                                          force_scan=force_scan)
            np.asarray(jnp.stack([chosen, scores]))
            return time.perf_counter() - t0

        cold = once()
        return cold, min(once() for _ in range(self.cal_runs))

    def _calibrate(self, host, pol, gangs, peer_bound, cpu) -> WavePlan:
        # device first: it is the known-good default, so if the host path
        # turns out pathologically slow the stall is bounded by one host
        # compile + runs, never paid before the device numbers exist
        dev_cold, device_s = self._time_path(host, pol, gangs, peer_bound,
                                             None)
        host_cold, host_s = self._time_path(host, pol, gangs, peer_bound,
                                            cpu)
        if host_s < device_s:
            return WavePlan("host", cpu, host_s, device_s, host_cold)
        return WavePlan("device", None, host_s, device_s, dev_cold)


default_router = WaveRouter()


def _mesh_min_nodes() -> int:
    """parallel.mesh.DEFAULT_MESH_MIN_NODES, imported lazily: parallel/
    mesh imports this module at load, so the constant cannot be a
    top-level import here."""
    from kubernetes_tpu.parallel.mesh import DEFAULT_MESH_MIN_NODES
    return DEFAULT_MESH_MIN_NODES


def _takes_mesh(host: SolverInputs, mesh, pol, gangs: bool,
                peer_bound: int) -> bool:
    """Whether a wave solves over ``mesh``: only at or above the node
    floor, and only outside the kernel's domain — a cluster that fits one
    core's VMEM is faster on one device, since sharding the node axis
    puts a cross-shard argmax inside every pod step (docs/design/
    solver.md)."""
    if mesh is None or int(host.cap.shape[0]) < _mesh_min_nodes():
        return False
    from kubernetes_tpu.ops import pallas_solver
    return not pallas_solver.eligible(host, pol or BatchPolicy(), gangs,
                                      peer_bound)


def solve(snap: ClusterSnapshot,
          host: Optional[SolverInputs] = None,
          mesh=None, resident=None) -> Tuple[np.ndarray, np.ndarray]:
    """Host entry: encode -> device -> solve -> host decisions (including
    the all-or-nothing gang post-pass when the wave has PodGroups).
    Waves route through the measured host-vs-device dispatch (WaveRouter):
    a small wave may be dispatch-bound on the device and run faster on
    the host CPU backend. ``host`` short-circuits the host-side
    encode when the caller already holds the wave's host inputs (the wave
    loop, which reads the wave's bucket from them for the prewarm).

    ``resident`` (a models/resident.ResidentPlanes, one a scheduler): the
    node planes outlive the wave, on the host and on the device(s), and a
    wave patches them with the rows the encoder touched and ships only the
    pod planes and those rows; it rebuilds and places whole whenever the
    snapshot does not allow that (counted, with the reason, in
    ``solver_resident_waves_total``). Without it — RemoteSolver's
    fallback, chip_smoke.py, tests — every plane is built and placed anew:
    the cold path, which is also what a rebuild runs.

    ``mesh`` (a parallel.mesh Mesh, kube-scheduler --mesh): a wave at or
    above the mesh node floor that is outside the Pallas kernel's domain
    takes the GSPMD scan over the mesh (``_takes_mesh``) — the in-process
    twin of kube-solverd's MeshExecutor, resident planes included: they
    stay sharded under ``input_shardings`` and are patched in place. A
    kernel-eligible wave takes the one-device arm whatever the mesh. Both
    arms keep the same six parts and count the bytes that crossed to the
    device(s) (``solver_mesh_placed_bytes_total``); decisions are
    bit-identical either way (parallel/mesh.py contract) and the gang
    post-pass is applied here exactly as on the router path."""
    # the phases hang on the caller's ambient span (the wave's wave.solve);
    # off the wave loop (solverd) there is none and they only keep time
    part = wave_parts()
    if host is None:
        with tracing.phase("wave.solve.hostprep", part, "solve.hostprep"):
            host = (resident.host_inputs(snap) if resident is not None
                    else snapshot_to_host_inputs(snap))
    has_gangs = snap.has_gangs
    with tracing.phase("wave.solve.route", part, "solve.route"):
        peer_bound = peer_bound_of(snap)
        sharded = _takes_mesh(host, mesh, snap.policy, has_gangs, peer_bound)
        device = None
        if not sharded:
            device = default_router.plan_for(host, snap.policy, has_gangs,
                                             peer_bound).device
    with tracing.phase("wave.solve.ship", part, "solve.ship"):
        inp, nbytes = _ship_wave(host, mesh if sharded else None, device,
                                 resident)
        mesh_placed_bytes().inc(by=nbytes)
    if sharded:
        from kubernetes_tpu.parallel import mesh as pmesh
        with tracing.phase("wave.solve.launch", part, "solve.launch"):
            wave_programs().inc("scan-sharded",
                                mesh.devices.flat[0].platform)
            # donate=False: the resident planes outlive the wave, and the
            # pod planes may alias host memory (parallel/mesh.py)
            chosen, scores = pmesh.sharded_program(
                mesh, snap.policy or BatchPolicy(), has_gangs,
                donate=False)(*pmesh.split_inputs(inp))
        with tracing.phase("wave.solve.readback", part, "solve.readback"):
            # replicated outputs: the first copy waits for the program,
            # the second is there by then
            chosen, scores = np.asarray(chosen), np.asarray(scores)
        # padded nodes are infeasible: no index points past the real ones
        assert chosen.max(initial=-1) < int(host.cap.shape[0])
    else:
        with tracing.phase("wave.solve.launch", part, "solve.launch"):
            chosen, scores = solve_device(
                inp, snap.policy, has_gangs, peer_bound,
                force_scan=device is not None)
        with tracing.phase("wave.solve.readback", part, "solve.readback"):
            # ONE device->host readback, not two: at churn rates a second
            # sync per wave starves the feeder and watch pumps
            both = np.asarray(jnp.stack([chosen, scores]))
        chosen, scores = both[0], both[1]
    with tracing.phase("wave.solve.post", part, "solve.post"):
        # the wave's own device arrays (the pod planes) are let go here,
        # inside the last part, not in this frame's teardown after it:
        # freeing a device array gives the interpreter away once
        inp = None
        if has_gangs:
            chosen = gang.apply_all_or_nothing(snap.pod_rid, chosen)
            # keep the chosen/score pairing: rolled-back members'
            # tentative winning scores are as stale as their hosts
            scores = np.where(chosen < 0, np.int32(NEG), scores)
    return chosen, scores


def _ship_wave(host: SolverInputs, mesh, device, resident
               ) -> Tuple[SolverInputs, int]:
    """One wave's planes onto where it solves -> (device SolverInputs,
    bytes that crossed). ``mesh``: the sharded arm (the planes padded to
    it); ``device``: the router's host route; neither: the default device.
    ``resident`` ships what changed, where it holds ``host``'s node planes
    and the wave goes to the device(s); everything else is the cold path."""
    if resident is not None:
        if device is not None:
            resident.bypass(host)
        else:
            shipped = resident.ship(host, mesh)
            if shipped is not None:
                return shipped
    if mesh is not None:
        from kubernetes_tpu.parallel import mesh as pmesh
        return pmesh.place_on_mesh(host, mesh)
    return ship_inputs(host, device), sum(int(a.nbytes) for a in host)


def warm_compile(host: SolverInputs, pol, gangs: bool,
                 peer_bound: int = 0, mesh=None) -> None:
    """kube-slipstream prewarm entry: run (and discard) one wave of this
    exact shape through the same dispatch ``solve`` uses, so the compiled
    executables — router calibration included, since calibration IS the
    first compile of both paths, and the resident planes' apply program at
    every bucket of its row ladder — are resident in the jit cache (and
    the util/warmstart.py persistent cache) before a live wave needs them.
    ``host`` is the caller's own exemplar and is placed on planes of its
    own (ResidentPlanes.warm): a scheduler's live planes are never seen,
    let alone donated, from here. The results are read back to host
    because a dispatch whose outputs are never consumed may be elided
    wholesale; the readback is the fence that forces the compile to really
    happen. Runs on the prewarm thread — never on the wave loop."""
    from kubernetes_tpu.models.resident import ResidentPlanes
    ensure_x64()
    if _takes_mesh(host, mesh, pol, gangs, peer_bound):
        from kubernetes_tpu.parallel import mesh as pmesh
        chosen, scores = pmesh.sharded_program(
            mesh, pol or BatchPolicy(), gangs, donate=False)(
                *pmesh.split_inputs(ResidentPlanes.warm(host, mesh)))
        np.asarray(chosen), np.asarray(scores)
        return
    plan = default_router.plan_for(host, pol, gangs, peer_bound)
    inp = (ResidentPlanes.warm(host) if plan.device is None
           else ship_inputs(host, plan.device))
    chosen, scores = solve_device(inp, pol, gangs, peer_bound,
                                  force_scan=plan.device is not None)
    np.asarray(jnp.stack([chosen, scores]))


def decisions_to_names(snap: ClusterSnapshot, chosen: np.ndarray):
    """Map node indices back to host names; None = unschedulable. Slices
    off pod-axis padding (the incremental encoder pow-2 buckets P with
    never-feasible null rows)."""
    return [snap.node_names[i] if i >= 0 else None
            for i in chosen[:len(snap.pod_names)]]
