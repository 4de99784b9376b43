"""Resident node planes of the in-process solve.

Between two waves of one scheduler only the node rows that received or lost
a pod change, and the pod-axis planes. ``batch_solver.snapshot_to_host_inputs``
and the ship behind it nevertheless rebuilt and re-sent every node plane each
wave — O(nodes) numpy calls and O(nodes) bytes round a device program that
is a rounding error (PERF.md). ``ResidentPlanes`` keeps the
``parallel.mesh.RESIDENT_FIELDS`` of a wave's ``SolverInputs`` alive between
waves, in two forms, and patches both along the node axis:

- **host form**: the scaled, narrowed, bit-packed numpy planes. A wave
  recomputes the rows the encoder says it touched (``ClusterSnapshot.
  touched_since``) and nothing else; the pod-axis planes are built whole.
- **the group rows are the wave's own** (``group_counts``, ``ROW_FIELDS``):
  one row for each group the wave's pending pods name, made by the encoder
  from its sparse peer counts, on an axis that follows the pod bucket. No
  row outlives its wave, so nothing of them is kept or patched: a wave
  that names a group ships its rows whole, as a transfer of their own
  beside the packed buffer (megabytes of int32 inside the byte buffer cost
  the apply program minutes of compile for the bitcast); a wave that names
  none takes a plane of zeros placed once a shape and never written.
- **device form**: the same planes on the solve's device(s) — one device, or
  sharded over a mesh under ``input_shardings`` — patched by ONE jitted
  apply program a wave, which takes one packed buffer (the pod planes, the
  dirty row indices and their values) and returns the patched planes
  (the old ones donated) and the wave's planes. It stands where
  ``_unpack_device`` stood: a wave is still one transfer, one apply, one
  solve, one readback.

**When the planes are patched, and when they are built anew.** What selects
the path is in the input: the snapshot's epoch (models/incremental.py: it
changes with the node set, the services, a grown column, a ``restore``, the
preemption gate, a full ``encode()``; a snapshot of the full encoder has
none), the resource scales, the resource dtype, the planes' shapes, the
share of rows that are dirty, and the arm (one device or the mesh) the wave
takes. Anything but "same epoch, scales still divide, same dtype, same
shapes, few rows, same arm" rebuilds with ``host_inputs_scaled`` and places
whole (``place_whole``), counted by reason in
``solver_resident_waves_total``. No flag and no environment variable.

**The scale stays exact.** The planes are divided by a per-dimension common
divisor (``_resource_scales``). A divisor of the old planes that also divides
this wave's requests and dirty rows is still a common divisor of everything,
so every comparison and floor division of the solve stays what it was; the
scale only ever shrinks, and a value it does not divide rebuilds. ``_fits_i32``
needs the planes' maxima: upper bounds are kept per column and only grow, so
int32 is never chosen where a fresh reduction would refuse it.

**Ownership.** One instance a scheduler (never module state): the prewarm
thread's ``warm`` works on planes of its own and shares only the compiled
programs. The host form owns its memory (a snapshot aliases the encoder's
live arrays). The donation rule is ``parallel/mesh.py``'s: planes fresh
from ``device_put`` may alias host memory on the CPU backend and are not
donated; what an apply program returned is.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from kubernetes_tpu.models import batch_solver as bs
from kubernetes_tpu.models.batch_solver import SolverInputs
from kubernetes_tpu.parallel import mesh as pmesh
from kubernetes_tpu.util import metrics

__all__ = ["ResidentPlanes", "PATCH_FIELDS", "SMALL_FIELDS", "ROW_FIELDS",
           "ROW_LADDER",
           "resident_waves", "resident_rows"]

# node-axis planes a bind or a delete changes: patched by rows
PATCH_FIELDS = ("fit_used", "fit_exceeded", "score_used", "node_ports",
                "node_pds", "evict_cap", "evict_cnt")
# resident planes with no node axis: small, they ride with the pod planes
SMALL_FIELDS = ("zone_counts0", "band_prio")
# the wave's own group rows: a transfer of their own, or the zeros placed
# once a shape where the wave names no group
ROW_FIELDS = ("group_counts",)
# node planes only an epoch changes: placed once
STATIC_FIELDS = tuple(f for f in pmesh.RESIDENT_FIELDS
                      if f not in PATCH_FIELDS + SMALL_FIELDS + ROW_FIELDS)
# dirty-row buckets of the apply program: one compile a pod bucket and
# entry; more rows than the last re-place whole. One entry, the scheduler's
# default wave size: on a v5e the ship and the apply take 1.9-2.1 ms at 32,
# 256 and 1,024 rows alike (PERF.md, PR 30), so a finer ladder buys
# nothing but compiles
ROW_LADDER = (1024,)

_DEBUG = os.environ.get("KTPU_DEBUG", "") not in ("", "0")


def resident_waves() -> metrics.Counter:
    return metrics.default_registry().counter(
        "solver_resident_waves_total",
        "Waves of the in-process solve by what became of its resident node "
        "planes: patched by the dirty rows, rebuilt and placed whole (why), "
        "or bypassed (the router's host route)", ("outcome", "reason"))


def resident_rows() -> metrics.Counter:
    return metrics.default_registry().counter(
        "solver_resident_rows_total",
        "Node rows patched into the device's resident planes")


def _snap_shapes(snap) -> tuple:
    return tuple(None if a is None else a.shape for a in (
        snap.cap, snap.node_ports, snap.node_sel, snap.node_pds,
        snap.node_aff_vals, snap.node_zone,
        snap.evict_cap, snap.evict_cnt, snap.band_prio))


def _col_max(a: np.ndarray) -> np.ndarray:
    """[R] largest magnitude of each resource column of an [.., R] plane."""
    flat = np.abs(a.reshape(-1, a.shape[-1])).astype(np.int64)
    return flat.max(axis=0, initial=0)


@dataclasses.dataclass
class _Device:
    """The device form: where it lives and what it holds."""

    mesh: object          # None: the default device
    static: dict          # name -> array: what no wave patches
    patch: dict           # name -> array: PATCH_FIELDS, but the empty ones
    #                       (no bands: no evictable planes)
    # the donation rule (parallel/mesh.py): whether ``patch`` came out of
    # an XLA program, and so may be donated to the next
    xla_owned: bool


class ResidentPlanes:
    """The node planes one scheduler's waves share (module docstring).
    ``host_inputs(snap)`` is the wave's hostprep; ``ship(host, mesh)`` its
    transfer, for the host it handed out last."""

    def __init__(self):
        self._lock = threading.Lock()
        self._host: Optional[dict] = None   # name -> owned plane
        self._epoch = None
        self._seq = 0
        self._scales: Optional[np.ndarray] = None
        self._rdt = None
        self._shapes = None
        self._bounds: dict = {}             # plane -> [R] column maxima
        self._handed: Optional[SolverInputs] = None
        self._dev: Optional[_Device] = None
        # rows the host form patched since the device form was last
        # brought level; None: the device form must be placed whole
        self._dev_rows: Optional[np.ndarray] = None
        self._dev_why = "first"

    # -- host form ----------------------------------------------------------
    def host_inputs(self, snap) -> SolverInputs:
        """``snapshot_to_host_inputs(snap)``, the node planes patched where
        the snapshot allows it. The planes are this object's: they are
        written again by the next call."""
        with self._lock:
            host, why = None, self._why_not_patch(snap)
            if not why:
                host, why = self._patch(snap)
            if host is None:
                host = self._rebuild(snap, why)
            elif _DEBUG:
                self._assert_equals_cold(snap, host)
            self._handed = host if self._host is not None else None
            return host

    def _why_not_patch(self, snap) -> str:
        if snap.resident_epoch is None:
            return "no_epoch"
        if self._host is None:
            return "first"
        if snap.resident_epoch != self._epoch:
            return snap.resident_why or "epoch"
        if _snap_shapes(snap) != self._shapes:
            return "shape"
        return ""

    def _rebuild(self, snap, why: str) -> SolverInputs:
        host, scales = bs.host_inputs_scaled(snap)
        self._dev_rows, self._dev_why = None, why
        if snap.resident_epoch is None:
            # the full encoder's snapshot: nothing says what the next one
            # changes, so nothing is kept (and nothing shipped from here)
            self._host = None
            resident_waves().inc("rebuilt", why)
            return host
        # own the memory: a snapshot aliases the encoder's live arrays
        self._host = {f: np.array(getattr(host, f))
                      for f in pmesh.RESIDENT_FIELDS}
        self._epoch, self._seq = snap.resident_epoch, snap.resident_seq
        self._scales, self._rdt = scales, host.cap.dtype
        self._shapes = _snap_shapes(snap)
        self._bounds = {f: _col_max(self._host[f]) for f in
                        ("cap", "fit_used", "score_used", "evict_cap")}
        return host._replace(**self._host)

    def _patch(self, snap) -> Tuple[Optional[SolverInputs], str]:
        touched = snap.touched_since(self._seq)
        if touched is None:
            return None, "sequence"
        rows = np.unique(np.asarray(touched, np.int64))
        k, H, g = len(rows), self._host, self._scales
        R = H["cap"].shape[1]
        B = H["evict_cap"].shape[1]
        P = snap.req.shape[0]
        # everything this wave brings in resource units, under one divmod:
        # the requests and the dirty rows
        parts = [snap.req, snap.fit_used[rows], snap.score_used[rows]]
        if B:
            parts.append(snap.evict_cap[rows].reshape(-1, R))
        units, left = np.divmod(np.concatenate(parts), g)
        if left.any():
            return None, "scale"
        req, fit_used, score_used = units[:P], units[P:P + k], \
            units[P + k:P + 2 * k]
        evict_cap = units[P + 2 * k:].reshape(k, B, R)
        bounds = dict(self._bounds)
        for f, vals in (("fit_used", fit_used), ("score_used", score_used),
                        ("evict_cap", evict_cap)):
            bounds[f] = np.maximum(bounds[f], _col_max(vals))
        req_total = np.abs(req).sum(axis=0)
        top = max(int(a.max(initial=0)) for a in (
            bounds["cap"] + req_total, bounds["score_used"] + req_total,
            bounds["fit_used"], bounds["evict_cap"]))
        rdt = np.int32 if top <= bs._I32_HEADROOM else np.int64
        if rdt != self._rdt:
            return None, "dtype"
        self._bounds = bounds
        if k:
            H["fit_used"][rows] = fit_used
            H["score_used"][rows] = score_used
            H["fit_exceeded"][rows] = snap.fit_exceeded[rows]
            H["node_ports"][rows] = bs._pack_bits(snap.node_ports[rows])
            H["node_pds"][rows] = bs._pack_bits(snap.node_pds[rows])
            if B:
                H["evict_cap"][rows] = evict_cap
                H["evict_cnt"][rows] = snap.evict_cnt[rows]
        # the group rows are this wave's own (the snapshot made them anew)
        H["group_counts"] = np.ascontiguousarray(snap.group_counts)
        H.update(bs.host_small_planes(snap, H["zone_idx"]))
        self._seq = snap.resident_seq
        if self._dev_rows is not None:
            self._dev_rows = np.union1d(self._dev_rows, rows)
        wave = bs.host_wave_planes(snap, req.astype(rdt),
                                   H["group_counts"].shape[0])
        return SolverInputs(**H, **wave), ""

    def _assert_equals_cold(self, snap, host: SolverInputs) -> None:
        """KTPU_DEBUG: the patched planes against the cold path's, where
        the two divide by the same scales (a running scale may lag a
        fresh gcd that a delete let grow; the solve is exact either way)."""
        cold, scales = bs.host_inputs_scaled(snap)
        if np.array_equal(scales, self._scales):
            for f, a, b in zip(host._fields, host, cold):
                assert a.dtype == b.dtype and np.array_equal(a, b), \
                    f"resident host plane {f} diverged from the cold path"

    # -- device form --------------------------------------------------------
    def ship(self, host: SolverInputs, mesh=None
             ) -> Optional[Tuple[SolverInputs, int]]:
        """The wave's planes on the device(s): the resident ones patched,
        the pod planes new. -> (SolverInputs of device arrays, bytes that
        crossed), or None when ``host`` is not the one ``host_inputs``
        handed out last (the caller ships it the cold way; nothing here
        is touched)."""
        with self._lock:
            if host is not self._handed or not host.cap.shape[0]:
                return None
            rows, why = self._dev_rows, self._dev_why
            if self._dev is not None and self._dev.mesh is not mesh:
                rows, why = None, "arm"
            if rows is not None and not _worth_patching(self._host, rows):
                rows, why = None, "dirty_share"
            nbytes = 0
            if rows is None:
                self._dev, nbytes = place_whole(self._host, mesh)
                rows = np.zeros(0, np.int64)
            inp, crossed = apply_rows(self._dev, host, rows)
            self._dev_rows, self._dev_why = np.zeros(0, np.int64), ""
            if why:
                resident_waves().inc("rebuilt", why)
            else:
                resident_waves().inc("patched", "")
                resident_rows().inc(by=len(rows))
            return inp, nbytes + crossed

    def bypass(self, host: SolverInputs) -> None:
        """The wave went another way (the router's host route): the device
        form waits, the rows it owes kept."""
        with self._lock:
            if host is self._handed:
                resident_waves().inc("bypassed", "host_route")

    @staticmethod
    def warm(host: SolverInputs, mesh=None) -> SolverInputs:
        """The prewarm's ship: ``host`` (its own exemplar, never a live
        plane) placed whole on planes of its own, then through the apply
        program of every ladder bucket, so that a live wave of this shape
        finds each compiled. -> the device inputs of the last."""
        planes = {f: getattr(host, f) for f in pmesh.RESIDENT_FIELDS}
        dev, _ = place_whole(planes, mesh)
        inp = None
        # the first bucket twice: after a fresh placement on the CPU
        # backend the first apply may not donate, a live one mostly does
        for want in ROW_LADDER[:1] + ROW_LADDER:
            inp, _ = apply_rows(dev, host, np.zeros(0, np.int64), want=want)
        return inp


def _worth_patching(planes: dict, rows: np.ndarray) -> bool:
    """solver/client._delta_plan's rule over the patched planes together:
    rows plus their indices must weigh less than the planes; and no more
    rows than the ladder's last bucket."""
    whole = sum(planes[f].nbytes for f in PATCH_FIELDS)
    a_row = whole // max(1, planes["cap"].shape[0])
    return len(rows) <= ROW_LADDER[-1] and len(rows) * (a_row + 4) < whole


def place_whole(planes: dict, mesh=None) -> Tuple[_Device, int]:
    """The cold path of the device form: every resident node plane placed
    anew — on the default device, or padded to the mesh and placed under
    ``input_shardings``. -> (the device form, bytes placed)."""
    pad, sh, first = 0, None, jax.devices()[0]
    if mesh is not None:
        pad = pmesh._pad_width(int(planes["cap"].shape[0]),
                               mesh.shape["nodes"])
        sh, first = pmesh.input_shardings(mesh), mesh.devices.flat[0]
    put = {f: jax.device_put(pmesh.pad_plane(f, planes[f], pad),
                             sh and getattr(sh, f))
           for f in STATIC_FIELDS + PATCH_FIELDS}
    patch = {f: put.pop(f) for f in PATCH_FIELDS if put[f].size}
    dev = _Device(mesh, put, patch,
                  xla_owned=not pmesh.may_alias_host(first.platform))
    return dev, sum(int(a.nbytes) for a in (*dev.static.values(),
                                            *patch.values()))


def apply_rows(dev: _Device, host: SolverInputs, rows: np.ndarray,
               want: Optional[int] = None) -> Tuple[SolverInputs, int]:
    """One wave onto the device form: pack the pod planes, the small
    planes and ``rows`` of every patched plane (from ``host``, whose node
    planes are already level) into one buffer, ship it, run the apply
    program; the wave's group rows go beside it (``ship_group_rows``).
    -> (the wave's device SolverInputs, bytes shipped). ``dev.patch`` is
    replaced by the program's outputs."""
    if not len(rows):
        rows = np.zeros(1, np.int64)    # row 0 onto itself: nothing changes
    want = want or next(b for b in ROW_LADDER if b >= len(rows))
    n = int(host.cap.shape[0])
    names = tuple(dev.patch)
    buf, spec = bs.pack_arrays(wave_arrays(host, names, rows, want))
    program = _apply_program(spec, names, n, dev.mesh, dev.xla_owned)
    placed = jax.device_put(buf, dev.mesh and NamedSharding(
        dev.mesh, PartitionSpec()))
    with pmesh.donation_warnings_scoped():
        patched, rest = program(tuple(dev.patch[f] for f in names), placed)
    group_rows, crossed = ship_group_rows(host, dev.mesh)
    dev.patch = dict(zip(names, patched))
    dev.xla_owned = True
    return SolverInputs(**dev.static, **dev.patch, group_counts=group_rows,
                        **dict(zip(pmesh.WAVE_FIELDS + SMALL_FIELDS, rest))
                        ), int(buf.nbytes) + crossed


def ship_group_rows(host: SolverInputs, mesh) -> Tuple[jax.Array, int]:
    """The wave's ``group_counts`` on the device(s), padded to the mesh and
    replicated there as ``input_shardings`` has it -> (the plane, bytes
    that crossed). A wave in which some pod has a service ships its rows;
    every row of a wave that names no group is zeros, and that plane is
    placed once a shape and handed to every such wave (no program writes
    or donates a ``group_counts`` it is given)."""
    n = int(host.cap.shape[0])
    pad = 0 if mesh is None else pmesh._pad_width(n, mesh.shape["nodes"])
    if not (host.pod_gid >= 0).any():
        return _zero_rows(host.group_counts.shape[0], n + 1 + pad, mesh), 0
    rows = pmesh.pad_plane("group_counts", host.group_counts, pad)
    return _place_rows(rows, mesh), int(rows.nbytes)


def _place_rows(rows: np.ndarray, mesh) -> jax.Array:
    return jax.device_put(rows, mesh and pmesh.input_shardings(
        mesh).group_counts)


@functools.lru_cache(maxsize=64)
def _zero_rows(G: int, width: int, mesh) -> jax.Array:
    return _place_rows(np.zeros((G, width), np.int32), mesh)


def wave_arrays(host: SolverInputs, names: tuple, rows: np.ndarray,
                want: int) -> list:
    """What one wave packs, in the order the apply program unpacks it:
    the pod planes, the small planes, the values of ``rows`` in each plane
    of ``names``, and ``rows`` — brought to ``want`` by repeating the last
    (``pad_rows_to``)."""
    arrays = [getattr(host, f) for f in pmesh.WAVE_FIELDS + SMALL_FIELDS]
    rows = pmesh.pad_rows_to(rows, rows, want)[0]
    arrays += [np.take(getattr(host, f), rows, axis=pmesh.PAD_SPEC[f][0])
               for f in names]
    arrays.append(rows.astype(np.int32))
    return arrays


@functools.lru_cache(maxsize=256)
def _apply_program(spec: tuple, names: tuple, n: int, mesh, donate: bool):
    """The apply program of one (pod bucket, row bucket, node-plane shapes,
    arm): ``fn(patch planes, packed buffer) -> (patched planes, pod planes
    + small planes)``. ``spec`` lays the buffer out as ``apply_rows``
    packs it; ``n`` is the real node count (part of the key: the planes'
    shapes are not in ``spec``). On a mesh the planes come in and go out
    under ``input_shardings``: nothing is resharded on entry to
    ``sharded_program``."""
    n_rest = len(pmesh.WAVE_FIELDS + SMALL_FIELDS)

    def apply(patch, buf):
        parts = bs.unpack_arrays(buf, spec)
        rest, vals, rows = parts[:n_rest], parts[n_rest:-1], parts[-1]
        return tuple(pmesh.scatter_rows(base, rows, v, pmesh.PAD_SPEC[f][0])
                     for f, base, v in zip(names, patch, vals)), rest

    if mesh is None:
        return jax.jit(apply, donate_argnums=(0,) if donate else ())
    sh = pmesh.input_shardings(mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    patch_sh = tuple(getattr(sh, f) for f in names)
    rest_sh = tuple(getattr(sh, f)
                    for f in pmesh.WAVE_FIELDS + SMALL_FIELDS)
    return jax.jit(apply, in_shardings=(patch_sh, rep),
                   out_shardings=(patch_sh, rest_sh),
                   donate_argnums=(0,) if donate else ())
