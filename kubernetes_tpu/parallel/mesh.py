"""Multi-chip sharding for the batch solver.

The scaling model (SURVEY.md section 5 "long-context" note): the
(pods x nodes) problem is our sequence. When the node axis outgrows one
chip's HBM or FLOPs, shard it over a ``jax.sharding.Mesh``:

- 2D mesh ("pods", "nodes"): the batched Filter pre-pass — an MXU matmul of
  pod features against node features — shards both operands (data-parallel
  over pods, tensor-parallel over nodes).
- the sequential-commit scan keeps its [N]-shaped carries sharded over
  "nodes"; per-step reductions (max/sum/cumsum for the deterministic
  tie-break) become XLA collectives over ICI, inserted by the SPMD
  partitioner — no hand-written communication.

Nodes are padded to the mesh size with permanently-infeasible entries
(node_extra_ok=False), so padding can never win a tie-break and decisions
remain bit-identical to the unsharded / serial paths.
"""

from __future__ import annotations

import contextlib
import functools
import os
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubernetes_tpu.models.batch_solver import SolverInputs, solve_jit

__all__ = ["make_mesh", "maybe_mesh", "pad_inputs_for_mesh", "solve_sharded",
           "place_on_mesh", "split_inputs",
           "shard_memory_report", "sharded_program", "input_shardings",
           "RESIDENT_FIELDS", "WAVE_FIELDS", "DEFAULT_MESH_MIN_NODES",
           "scatter_rows", "pad_rows_to", "may_alias_host",
           "donation_warnings_scoped"]

_DEBUG = os.environ.get("KTPU_DEBUG", "") not in ("", "0")

# Below this node count the mesh dispatch stays out of the way by default:
# small waves are kernel- or single-device territory (the measured numbers
# in solve_sharded's docstring), and the production full-shape planes the
# mesh exists for start around here.
DEFAULT_MESH_MIN_NODES = 4096

# The resident/wave split of SolverInputs, shared with the solver daemon's
# delta wire (solver/protocol.DELTA_FIELDS names the same set): node/group/
# zone planes persist between waves (device-resident under the mesh
# executor), pod-axis planes are new every wave and safe to donate.
RESIDENT_FIELDS = (
    "cap", "advertises", "fit_used", "fit_exceeded", "score_used",
    "node_ports", "node_sel", "node_pds", "node_extra_ok",
    "group_counts", "score_static", "node_aff_vals",
    "zone_idx", "zone_counts0",
    "evict_cap", "evict_cnt", "band_prio",
)
WAVE_FIELDS = tuple(f for f in SolverInputs._fields
                    if f not in RESIDENT_FIELDS)


# -- patching a resident plane on the device ---------------------------------
# Shared by kube-solverd's MeshExecutor (solver/mesh_exec.py: one scatter a
# changed plane) and the in-process resident planes (models/resident.py: one
# apply program a wave). THE DONATION RULE: only a buffer that an XLA
# program produced may be donated. One that ``jax.device_put`` made from a
# host numpy array may ALIAS that array's memory on the CPU backend
# (zero-copy when alignment allows); donating it frees memory numpy still
# owns and corrupts the native heap (observed live as ``malloc(): unsorted
# double linked list corrupted`` killing the daemon mid-churn). So the first
# patch after a fresh placement does not donate; every later one does.

def scatter_rows(base, rows, vals, axis: int = 0):
    """``base`` with ``vals`` written at ``rows`` along ``axis`` (0, or 1
    for a plane whose node axis is its second: ``vals`` is then [.., k])."""
    if axis == 0:
        return base.at[rows].set(vals)
    return base.at[:, rows].set(vals)


def may_alias_host(platform: str) -> bool:
    """Whether a ``device_put`` onto ``platform`` can share the host
    array's memory (the donation rule above): the CPU backend alone."""
    return platform == "cpu"


def pad_rows_to(rows: np.ndarray, vals: np.ndarray, want: int):
    """A delta of k changed rows brought to ``want`` >= k by repeating the
    last (row, value) pair — idempotent under scatter-set (same index,
    same value) — so that the programs that apply it compile once a
    bucket and not once a row count."""
    extra = want - len(rows)
    if extra <= 0 or len(rows) == 0:
        return rows, vals
    rows = np.concatenate([rows, np.repeat(rows[-1:], extra, axis=0)])
    vals = np.concatenate([vals, np.repeat(vals[-1:], extra, axis=0)])
    return rows, vals


def pow2_rows(rows: np.ndarray, vals: np.ndarray):
    """``pad_rows_to`` the next power of two: O(log k) programs a plane."""
    return pad_rows_to(rows, vals, 1 << max(len(rows) - 1, 0).bit_length())


@functools.lru_cache(maxsize=256)
def scatter_fn(sharding, donate: bool = True):
    """One plane's row scatter as a program of its own, keeping the plane's
    sharding and (by default) donating the old buffer — the copy-on-write
    delta apply, on device. ``donate`` follows the donation rule above."""
    return jax.jit(scatter_rows, out_shardings=sharding,
                   donate_argnums=(0,) if donate else ())


@contextlib.contextmanager
def donation_warnings_scoped():
    """A program that donates planes it cannot alias to an output (the
    sharded program's pod planes: the scan carry is [N]-shaped and sourced
    from the NON-donated resident planes — by design) makes XLA report them
    unusable once per compile. Expected there, but the warning stays live
    for everyone else in the process."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def make_mesh(devices=None, pods_axis: int = 1) -> Mesh:
    """Mesh over available devices: ("pods", "nodes"). With pods_axis=1 the
    whole mesh shards the node axis (pure tensor-parallel layout)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % pods_axis != 0:
        raise ValueError(f"{n} devices not divisible by pods_axis={pods_axis}")
    arr = np.array(devices).reshape(pods_axis, n // pods_axis)
    return Mesh(arr, ("pods", "nodes"))


def maybe_mesh(mode: str = "auto", pods_axis: int = 1) -> Optional[Mesh]:
    """Resolve a --mesh flag to a Mesh or None. ``auto`` builds the mesh
    exactly when more than one device is attached (real multi-chip, or CPU
    sub-meshes via --xla_force_host_platform_device_count); ``on`` demands
    one (raises on a single-device host); ``off`` is None."""
    mode = (mode or "auto").strip().lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"mesh={mode!r}: expected auto|on|off")
    if mode == "off":
        return None
    n = jax.device_count()
    if n <= 1:
        if mode == "on":
            raise RuntimeError("--mesh on requires >1 device "
                               f"(have {n}; set XLA_FLAGS="
                               "--xla_force_host_platform_device_count=N)")
        return None
    return make_mesh(pods_axis=pods_axis)


@functools.lru_cache(maxsize=512)
def _pad_width(n: int, shards: int) -> int:
    """Memoized node-axis pad width per (shape bucket N, mesh shards) —
    the per-wave re-derivation this cache replaces showed up as O(fields)
    numpy pad calls on every full-shape wave."""
    return (-n) % shards


def _assert_padding_invariant(padded: SolverInputs, n: int) -> None:
    """KTPU_DEBUG gate: padding rows must be decision-invariant — never
    feasible (so they cannot win any tie-break), never advertising
    resources, never zone-labeled. A violation here means a future field
    was added to SolverInputs without teaching pad_inputs_for_mesh its
    decision-invariant fill."""
    total = int(padded.cap.shape[0])
    if total == n:
        return
    assert not np.asarray(padded.node_extra_ok[n:]).any(), \
        "mesh padding produced a feasible node (node_extra_ok True)"
    assert np.asarray(padded.fit_exceeded[n:]).all(), \
        "mesh padding produced a node with headroom (fit_exceeded False)"
    assert not np.asarray(padded.advertises[n:]).any(), \
        "mesh padding advertises resources"
    assert not np.asarray(padded.cap[n:]).any(), \
        "mesh padding carries capacity"
    assert (np.asarray(padded.zone_idx[:, n:]) == -1).all(), \
        "mesh padding is zone-labeled (would perturb anti-affinity counts)"
    assert (np.asarray(padded.node_aff_vals[n:]) == -1).all(), \
        "mesh padding carries affinity label values"
    assert not np.asarray(padded.evict_cnt[n:]).any(), \
        "mesh padding holds evictable pods (preemption could target it)"


# (axis, decision-invariant fill) of each plane pad_inputs_for_mesh
# extends (absent = unpadded). The ONE definition: pad_inputs_for_mesh
# materializes from it, shard_memory_report derives padded-as-allocated
# sizes from it without building the pads, and the mesh executor pads a
# SINGLE re-established plane host-side from it. Fills are the
# never-wins guarantees _assert_padding_invariant re-checks: pad nodes
# are never feasible (node_extra_ok False, fit_exceeded True), advertise
# nothing, carry no capacity, are zone-unlabeled (-1) and
# affinity-unlabeled (-1).
PAD_SPEC = {
    "cap": (0, 0), "advertises": (0, False), "fit_used": (0, 0),
    "fit_exceeded": (0, True), "score_used": (0, 0),
    "node_ports": (0, 0), "node_sel": (0, 0), "node_pds": (0, 0),
    "node_extra_ok": (0, False), "score_static": (0, 0),
    "node_aff_vals": (0, -1),
    "group_counts": (1, 0), "zone_idx": (1, -1),
    # kube-preempt: pad nodes hold no evictable pods, so they can never
    # be preempted onto (their freed capacity is zero and they are
    # infeasible anyway per node_extra_ok/fit_exceeded above)
    "evict_cap": (0, 0), "evict_cnt": (0, 0),
}


def pad_plane(name: str, x, pad: int, xp=np):
    """One plane padded per PAD_SPEC (identity when unpadded or pad==0).
    ``xp`` selects the array module: np for a host-side single-plane pad
    (the executor's residency re-establish), jnp inside traced code."""
    spec = PAD_SPEC.get(name)
    if spec is None or pad == 0:
        return x
    axis, fill = spec
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return xp.pad(x, widths, constant_values=fill)


def pad_inputs_for_mesh(inp: SolverInputs, mesh: Mesh) -> Tuple[SolverInputs, int]:
    """Pad the node axis to a multiple of the "nodes" mesh axis with
    infeasible nodes (PAD_SPEC fills). Returns (padded inputs, original
    N). Pad widths are memoized per (N, mesh shards); with KTPU_DEBUG
    set, the padded planes are re-checked for the decision-invariance
    the fills guarantee."""
    shards = mesh.shape["nodes"]
    n = int(inp.cap.shape[0])
    pad = _pad_width(n, shards)
    if pad == 0:
        return inp, n
    padded = SolverInputs(**{name: pad_plane(name, getattr(inp, name),
                                             pad, xp=jnp)
                             for name in SolverInputs._fields})
    if _DEBUG:
        _assert_padding_invariant(padded, n)
    return padded, n


def input_shardings(mesh: Mesh) -> SolverInputs:
    """Sharding spec per input: node-axis arrays shard over "nodes"; per-pod
    arrays shard the scan axis over "pods" where legal, else replicate."""
    def s(*spec):
        return NamedSharding(mesh, P(*spec))

    node = s("nodes")
    node2d = s("nodes", None)
    rep = s()
    return SolverInputs(
        cap=node2d, advertises=node2d, fit_used=node2d, fit_exceeded=node,
        score_used=node2d,
        node_ports=node2d, node_sel=node2d, node_pds=node2d,
        node_extra_ok=node,
        req=rep,
        pod_ports=rep, pod_sel=rep, pod_pds=rep,
        pod_host_idx=rep, tie_hi=rep, tie_lo=rep,
        pod_gid=rep, pod_group_member=rep,
        # counts: small [G, N+1] — the +1 overflow slot breaks even node
        # sharding; replicate (GSPMD gathers the one-hot update, tiny)
        group_counts=rep,
        gang_start=rep,
        score_static=node,
        node_aff_vals=node2d,
        pod_aff_static=rep,
        anchor_vals0=rep, has_anchor0=rep,
        zone_idx=s(None, "nodes"),
        zone_counts0=rep,
        pod_prio=rep, pod_can_preempt=rep,
        # evictable planes are node-major like cap/fit_used; band values
        # are a tiny [B] vector every shard needs
        band_prio=rep,
        evict_cap=s("nodes", None, None),
        evict_cnt=s("nodes", None),
    )


def shard_memory_report(inp: SolverInputs, mesh: Mesh) -> dict:
    """Bytes per device for one wave under the mesh's shardings: the
    (padded, as actually allocated) inputs plus the scan carry, which
    duplicates the mutable planes on-device. The multi-chip dryrun logs
    this for the 5k-node planes so HBM headroom is visible without TPU
    hardware."""
    shardings = input_shardings(mesh)
    shards = mesh.shape["nodes"]
    pad = _pad_width(int(inp.cap.shape[0]), shards)

    def nbytes(name: str) -> int:
        # padded-as-allocated size, by shape arithmetic only: no device
        # pads are materialized here (MeshExecutor calls this on the
        # solve thread once per new resident bucket)
        a = getattr(inp, name)
        shape = list(a.shape)
        if name in PAD_SPEC:
            shape[PAD_SPEC[name][0]] += pad
        return int(np.prod(shape)) * a.dtype.itemsize

    per_device = 0
    replicated = 0
    for name, sh in zip(SolverInputs._fields, shardings):
        b = nbytes(name)
        if "nodes" in sh.spec:
            per_device += b // shards  # padded: node axis divides evenly
        else:
            replicated += b
    # the lax.scan carry holds live copies of the mutable planes
    # (kubernetes_tpu.models.batch_solver solve_jit Carry); same layout
    carry_sharded = sum(nbytes(f) for f in (
        "fit_used", "score_used", "node_ports", "node_pds",
        "evict_cap", "evict_cnt")) // shards
    carry_replicated = sum(nbytes(f) for f in (
        "group_counts", "anchor_vals0", "has_anchor0"))
    return {
        "devices": int(np.prod(list(mesh.shape.values()))),
        "node_shards": shards,
        "sharded_bytes_per_device": per_device,
        "replicated_bytes_per_device": replicated,
        "carry_bytes_per_device": carry_sharded + carry_replicated,
        "total_bytes_per_device": (per_device + replicated
                                   + carry_sharded + carry_replicated),
    }


def solve_sharded(inp: SolverInputs, mesh: Optional[Mesh] = None,
                  w_lr: int = 1, w_spread: int = 1, w_equal: int = 0,
                  pol=None, gangs: bool = False,
                  peer_bound: Optional[int] = None,
                  prefer_kernel: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Solve one wave under a device mesh. Decisions are identical to the
    single-device path; only the layout (and dispatch) changes. Gang
    callers apply gang.apply_all_or_nothing to the returned decisions, as
    with solve.

    Dispatch is a measured crossover, not a blind shard:

    - **Kernel-eligible waves bypass the mesh and run on ONE device**
      through models/batch_solver.solve_device — the Pallas
      sequential-commit kernel on real TPUs (or KTPU_PALLAS=interpret),
      the plain single-device scan on other backends. Either way that
      beats sharding: the state for a whole 32k-node cluster fits a
      single core's VMEM (ops/pallas_solver eligible()), while sharding
      the node axis puts a cross-shard argmax + tie-break collective
      inside EVERY pod step — per-step latency that dwarfs the step's
      arithmetic. Measured on an 8-device host mesh (4097 nodes x 512
      pods, solve only, inputs pre-placed; shared-memory collectives —
      far cheaper than real ICI): the sharded scan runs ~7.5x SLOWER
      than the same scan on one device (1.49s vs 0.20s median); on real
      TPU hardware the kernel then beats the single-device scan by a
      further ~4.5x (models/batch_solver.py solve_device). Sharding at
      these sizes buys capacity, not speed.
    - **Waves beyond the kernel's domain take the GSPMD scan over the
      mesh** — node planes sharded, per-step reductions riding
      XLA-inserted collectives. This is the capacity path: it is how a
      wave whose planes exceed one chip's HBM/VMEM runs at all.

    ``peer_bound`` (see batch_solver.peer_bound_of) gates kernel
    eligibility; None computes it from the inputs (one host readback)."""
    from kubernetes_tpu.models.batch_solver import peer_bound_of, solve_device
    from kubernetes_tpu.models.policy import BatchPolicy
    from kubernetes_tpu.ops import pallas_solver

    p = pol or BatchPolicy(w_lr=w_lr, w_spread=w_spread, w_equal=w_equal)
    if prefer_kernel:
        if peer_bound is None:
            peer_bound = peer_bound_of(inp)
        if pallas_solver.eligible(inp, p, gangs, peer_bound):
            # solve_device re-checks eligibility plus the mode/backend
            # gate and is the authority on kernel-vs-scan; this branch
            # only decides one-device-vs-mesh
            chosen, scores = solve_device(inp, p, gangs, peer_bound)
            return np.asarray(chosen), np.asarray(scores)

    mesh = mesh or make_mesh()
    placed, _nbytes = place_on_mesh(inp, mesh)
    # donate=False: the caller owns inp, and device_put of an
    # already-placed array aliases it — donation would delete the
    # caller's buffers. The daemon's mesh executor owns its transfers
    # and is the donating caller.
    fn = sharded_program(mesh, p, gangs, donate=False)
    chosen, scores = fn(*split_inputs(placed))
    chosen = np.asarray(chosen)
    scores = np.asarray(scores)
    # padded nodes are infeasible, so indices never point past n; no remap
    assert chosen.max(initial=-1) < int(inp.cap.shape[0])
    return chosen, scores


def place_on_mesh(inp: SolverInputs, mesh: Mesh) -> Tuple[SolverInputs, int]:
    """One wave's planes onto the mesh, every one anew — the cold path:
    pad the node axis to the mesh, ``device_put`` every plane under its
    sharding. -> (the placed SolverInputs, bytes placed — the padded
    planes' sizes, a replicated plane counted once). Who keeps planes
    between waves places them here once and patches them after: the wave
    loop through models/resident.py, the daemon through MeshExecutor."""
    padded, _n = pad_inputs_for_mesh(inp, mesh)
    placed = SolverInputs(*(jax.device_put(a, sh) for a, sh in
                            zip(padded, input_shardings(mesh))))
    return placed, sum(int(a.nbytes) for a in padded)


def split_inputs(inp: SolverInputs) -> Tuple[tuple, tuple]:
    """-> (resident tuple, wave tuple): ``sharded_program``'s arguments."""
    return (tuple(getattr(inp, f) for f in RESIDENT_FIELDS),
            tuple(getattr(inp, f) for f in WAVE_FIELDS))


@functools.lru_cache(maxsize=64)
def sharded_program(mesh: Mesh, pol, gangs: bool, donate: bool = True):
    """One compiled GSPMD program family per (mesh, policy, gangs): the
    sequential-commit scan jitted with pre-partitioned in/out shardings
    (SNIPPETS.md [1-3] — matching specs between back-to-back waves means
    already-placed inputs are never resharded on entry) and the per-wave
    pod planes donated (``donate_argnums``): the scan carry reuses their
    buffers, while the RESIDENT node/group/zone planes are an undonated
    argument and stay valid — the device-resident plane cache in
    solver/mesh_exec depends on exactly that split.

    Signature: ``fn(resident_tuple, wave_tuple) -> (chosen, scores)`` with
    the tuples in RESIDENT_FIELDS / WAVE_FIELDS order; outputs are
    replicated (one [P] vector each, readable with a single host copy)."""
    shardings = input_shardings(mesh)
    res_sh = tuple(getattr(shardings, f) for f in RESIDENT_FIELDS)
    wave_sh = tuple(getattr(shardings, f) for f in WAVE_FIELDS)
    rep = NamedSharding(mesh, P())

    # the function's name is the program's in a device trace (``jit_run``):
    # benchmarks/metrics/sharded_scan_ms.json finds it by that
    def run(resident, wave):
        kw = dict(zip(RESIDENT_FIELDS, resident))
        kw.update(zip(WAVE_FIELDS, wave))
        return solve_jit(SolverInputs(**kw), pol=pol, gangs=gangs)

    return jax.jit(run, in_shardings=(res_sh, wave_sh),
                   out_shardings=(rep, rep),
                   donate_argnums=(1,) if donate else ())
