"""MeshExecutor — the device-mesh production solve inside kube-solverd.

This is the piece that lifts ``parallel/mesh.py`` from a dryrun artifact
into the daemon's default multi-device dispatch. The daemon's resident
plane cache (the delta-wire v2 reconstruction target, solver/service.py)
gains a device half: the node/group/zone planes live on the mesh as
sharded/replicated jax buffers placed per ``parallel.mesh.input_shardings``,
and consecutive waves of one (worker, shape-bucket) pair touch the device
only O(changed rows + pod planes) per wave:

- **identity-anchored residency**: the service's copy-on-write delta
  reconstruction means an unchanged plane is the SAME numpy object wave
  to wave — the executor keys its device buffers on that object identity,
  so an "S" plane costs zero transfer and zero reshard;
- **deltas apply copy-on-write onto sharded planes**: a changed plane
  arrives as (base, rows, vals); when the resident buffer matches
  ``base`` by identity, the rows are scattered into the device array
  (``base.at[rows].set``) — the old buffer is donated, the result keeps
  the plane's NamedSharding, and only the rows cross the host boundary;
- **exact-shape programs**: waves run at the client's resident shape
  padded only to the mesh's node multiple (``pad_inputs_for_mesh``, pad
  widths memoized per (N, shards)) instead of the vmap fallback's pow-2
  node bucket — at the 50k/10k contract shape that alone removes a
  16384-vs-10000 node-axis scan waste;
- **donated pod planes, pre-partitioned outs**: the compiled program
  (``parallel.mesh.sharded_program``) donates the per-wave pod planes and
  pins in/out shardings, so back-to-back waves never reshard or copy the
  resident state (SNIPPETS.md [1-3]).

**Dispatch is a measured crossover, not a blind shard.** On real
multi-chip hardware the GSPMD scan is the capacity path (node planes
beyond one chip's HBM); on a CPU sub-mesh
(--xla_force_host_platform_device_count) the per-step tie-break
collectives make the fully-sharded scan SLOWER than one device (measured
3.1s vs 0.83s at 10k nodes x 1024 pods on the 24-core build box, matching
the 4k-node measurement in solve_sharded's docstring). The executor
therefore times both layouts once per (backend, device count, pods_axis,
plane shape) — the probe doubles as a live bit-identity check — picks the
winner, and persists the calibration in the warm-start dir
(``util/warmstart.mesh_cal_path``) so restarts skip the probe. The loser
layout stays armed: ``dispatch="shard"`` forces the full mesh (the
capacity story and the MULTICHIP live record), ``"single"`` pins the
1x1 submesh.

Decisions are bit-identical to the single-device and serial paths by the
same argument as ``solve_sharded`` (layout changes, arithmetic does not),
and the executor keeps that claim *live*: the first mesh wave of a run
(and every wave under ``probe="all"``) is re-solved on one device and
compared bitwise, counted in ``solverd_mesh_parity_*``.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

from kubernetes_tpu.util import metrics, tracing, warmstart

__all__ = ["MeshExecutor"]

_log = logging.getLogger("kubernetes_tpu.solver.mesh_exec")


class MeshExecutor:
    """Owns the mesh, the dispatch calibration, and the device-resident
    plane cache. One instance per SolverService; all device work happens
    on the daemon's single solver thread."""

    def __init__(self, pods_axis: int = 1,
                 min_nodes: Optional[int] = None,
                 dispatch: str = "auto",
                 probe: str = "first",
                 cache_entries: int = 64):
        import jax

        from kubernetes_tpu.parallel import mesh as pm

        if dispatch not in ("auto", "shard", "single"):
            raise ValueError(
                f"mesh dispatch={dispatch!r}: expected auto|shard|single")
        if probe not in ("first", "all", "off"):
            raise ValueError(
                f"mesh probe={probe!r}: expected first|all|off")
        self.mesh = pm.make_mesh(pods_axis=pods_axis)
        self.submesh = pm.make_mesh(jax.devices()[:1], pods_axis=1)
        self.pods_axis = pods_axis
        self.min_nodes = (pm.DEFAULT_MESH_MIN_NODES
                          if min_nodes is None else int(min_nodes))
        self.dispatch = dispatch
        self.probe = probe
        self.cache_entries = cache_entries
        self._pm = pm
        # (wid, bucket) -> {"mesh": Mesh,
        #                   "planes": {name: (src, dev, xla_owned)}}
        # src: the host numpy object (identity chain anchor); dev: the
        # device buffer; xla_owned: True only when dev came out of an
        # XLA program (scatter output) — a device_put-established dev
        # may ALIAS src on the CPU backend and must NEVER be donated
        # (parallel/mesh.py, the donation rule)
        self._resident: "OrderedDict[tuple, dict]" = OrderedDict()
        self._resident_bytes = 0
        # keys whose residency was LRU-evicted: their next wave's full
        # re-transfer counts as reshard (lost residency), not cold
        # first-contact transfer. Bounded: cleared when it outgrows the
        # cache several times over (stale entries only ever over-report).
        self._evicted: set = set()
        self._cal: Dict[str, dict] = {}
        self._cal_lock = threading.Lock()
        self._probed_once = False
        self._submesh_probed = False
        self._m = metrics.solverd_mesh_metrics()
        self._sm = metrics.solverd_submesh_metrics()
        self._m.devices.set(jax.device_count())
        self._m.pods_axis.set(pods_axis)
        self._load_cal()
        # exposed for tests and the startup banner
        self.mesh_waves = 0
        self.parity_checks = 0
        self.parity_divergent = 0
        self.submesh_waves = 0
        self.submesh_parity_divergent = 0

    # -- calibration persistence (warm start, keyed by mesh shape) ---------
    def _load_cal(self) -> None:
        if not warmstart.enabled():
            return
        try:
            with open(warmstart.mesh_cal_path()) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            return
        if isinstance(data, dict) and data.get("v") == 1 \
                and isinstance(data.get("cals"), dict):
            self._cal.update(data["cals"])

    def _save_cal(self) -> None:
        if not warmstart.enabled():
            return
        path = warmstart.mesh_cal_path()
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with self._cal_lock:
                blob = json.dumps({"v": 1, "cals": self._cal})
            with open(tmp, "w") as fh:
                fh.write(blob)
            os.replace(tmp, path)
        except OSError:
            pass

    def _cal_key(self, inp, pol, gangs: bool) -> str:
        import jax

        from kubernetes_tpu.solver import protocol
        fp = protocol.solver_fingerprint(pol, bool(gangs))[:8]
        return (f"{jax.default_backend()}x{jax.device_count()}"
                f"|pods_axis{self.pods_axis}"
                f"|N{inp.cap.shape[0]}|P{inp.req.shape[0]}"
                f"|R{inp.cap.shape[1]}|{inp.cap.dtype.str}|{fp}")

    # -- eligibility --------------------------------------------------------
    def eligible(self, inp, pol, gangs: bool) -> bool:
        """Kernel-vs-mesh-vs-single, the daemon half: waves below the
        node floor (or inside the Pallas kernel's domain on a
        kernel-capable backend) keep the padded vmap fallback — the
        measured numbers in solve_sharded's docstring say sharding buys
        them nothing. Everything else takes the mesh executor."""
        if int(inp.cap.shape[0]) < self.min_nodes:
            return False
        import jax

        from kubernetes_tpu.models.batch_solver import peer_bound_of
        from kubernetes_tpu.models.policy import BatchPolicy
        from kubernetes_tpu.ops import pallas_solver
        mode = os.environ.get("KTPU_PALLAS", "auto")
        if mode in ("auto", "interpret"):
            kernel_capable = (mode == "interpret"
                              or jax.default_backend() == "tpu")
            if kernel_capable and pallas_solver.eligible(
                    inp, pol or BatchPolicy(), gangs, peer_bound_of(inp)):
                return False
        return True

    @property
    def node_shards(self) -> int:
        return int(self.mesh.shape["nodes"])

    # -- the solve ----------------------------------------------------------
    def _active_mesh(self, inp, pol, gangs: bool):
        """The layout this wave runs under, probing the crossover once
        per calibration key when dispatch is auto. Returns
        (mesh, probe_result_or_None): a probe already solved the wave in
        both layouts, so its winner's answer is returned for reuse."""
        if self.dispatch == "single":
            return self.submesh, None
        if self.dispatch == "shard" or self.node_shards == 1:
            return self.mesh, None
        key = self._cal_key(inp, pol, gangs)
        with self._cal_lock:
            cal = self._cal.get(key)
        if cal is not None:
            return (self.mesh if cal.get("winner") == "shard"
                    else self.submesh), None
        single_res, single_s = self._time_layout(self.submesh, inp, pol,
                                                 gangs)
        shard_res, shard_s = self._time_layout(self.mesh, inp, pol, gangs)
        divergent = not (np.array_equal(single_res[0], shard_res[0])
                         and np.array_equal(single_res[1], shard_res[1]))
        # this probe IS a bitwise both-layouts comparison: the separate
        # first-wave parity probe would only repeat it
        self._probed_once = True
        self.parity_checks += 1
        self._m.parity_checks.inc()
        self._m.single_probe_s.observe(single_s)
        if divergent:
            # must never happen (the bit-identity contract); refuse to
            # cache a winner and serve the single-device answer
            self.parity_divergent += 1
            self._m.parity_divergent.inc()
            _log.error("mesh dispatch probe DIVERGED at %s "
                       "(sharded != single-device); pinning single", key)
            return self.submesh, single_res
        winner = "shard" if shard_s < single_s else "single"
        with self._cal_lock:
            self._cal[key] = {"winner": winner,
                              "sharded_s": round(shard_s, 4),
                              "single_s": round(single_s, 4)}
        self._save_cal()
        _log.info("mesh dispatch probe %s: sharded %.3fs vs single %.3fs "
                  "-> %s", key, shard_s, single_s, winner)
        return (self.mesh if winner == "shard" else self.submesh), (
            shard_res if winner == "shard" else single_res)

    def _time_layout(self, mesh, inp, pol, gangs: bool):
        """One full placed solve in ``mesh``'s layout -> (result, steady
        seconds). Compile + first run are untimed (warm start covers
        them across restarts); the timed run is the steady per-wave
        cost the dispatch decision is about."""
        import jax.numpy as jnp

        fn = self._pm.sharded_program(mesh, pol, gangs, donate=False)
        res, wav = self._pm.split_inputs(
            self._pm.place_on_mesh(inp, mesh)[0])
        chosen, scores = fn(res, wav)
        both = np.asarray(jnp.stack([chosen, scores]))
        t0 = time.perf_counter()
        chosen, scores = fn(res, wav)
        both = np.asarray(jnp.stack([chosen, scores]))
        return (both[0], both[1]), time.perf_counter() - t0

    def solve(self, inp, pol, gangs: bool, cache_key: Optional[tuple] = None,
              delta: Optional[dict] = None
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Solve one wave from (mostly) device-resident planes.

        ``inp`` is the service's reconstructed host-side SolverInputs;
        ``cache_key`` is the delta-wire (wid, bucket) pair the resident
        device planes are keyed under (None = no residency, e.g. a v1
        client); ``delta`` maps field name -> (base, rows, vals) for
        planes this wave changed, enabling the on-device scatter apply
        when the resident buffer matches ``base``."""
        import jax
        import jax.numpy as jnp

        t_wave = time.perf_counter()
        # kube-trace: the service's mesh path installs the wave's ambient
        # span before calling in; tctx None = untraced (free)
        tctx = tracing.current()
        t_pl0 = time.monotonic_ns()
        mesh, probed = self._active_mesh(inp, pol, gangs)
        self.mesh_waves += 1
        self._m.waves.inc()
        self._m.node_shards.set(mesh.shape["nodes"])
        pm = self._pm
        sh = pm.input_shardings(mesh)
        pad = int(pm._pad_width(int(inp.cap.shape[0]), mesh.shape["nodes"]))
        transfer = 0
        reshard = 0
        was_new = cache_key is not None and cache_key not in self._resident
        entry = self._resident.get(cache_key) if cache_key else None
        # freed covers the entry as it WAS, so a layout flip (same key
        # rebuilt under the other mesh) can't leak resident_bytes upward
        freed = sum(rec[1].nbytes for rec in entry["planes"].values()) \
            if entry is not None else 0
        lost_layout = entry is not None and entry["mesh"] is not mesh
        # residency lost wholesale (layout flip, or this key was LRU-
        # evicted since its last wave): every re-establish below is
        # reshard traffic, the signal back-to-back waves must keep near
        # zero — NOT cold first-contact transfer
        lost_residency = lost_layout or (was_new
                                         and cache_key in self._evicted)
        if entry is None or lost_layout:
            entry = {"mesh": mesh, "planes": {}}
        resident_dev = []
        for name in pm.RESIDENT_FIELDS:
            cur = getattr(inp, name)
            rec = entry["planes"].get(name)
            if rec is not None and rec[0] is cur:
                resident_dev.append(rec[1])
                continue
            d = delta.get(name) if delta else None
            if rec is not None and d is not None and d[0] is rec[0]:
                _src, base_dev, base_xla_owned = rec
                rows, vals = d[1], d[2]
                vals = self._pad_vals(name, vals, pad)
                rows, vals = pm.pow2_rows(np.ascontiguousarray(rows),
                                          np.ascontiguousarray(vals))
                # donate only XLA-owned bases: a device_put-established
                # base may alias the cached host array (parallel/mesh.py,
                # the donation rule)
                with pm.donation_warnings_scoped():
                    dev = pm.scatter_fn(getattr(sh, name),
                                        donate=base_xla_owned)(base_dev,
                                                               rows, vals)
                transfer += rows.nbytes + vals.nbytes
                xla_owned = True
            else:
                # host-side single-plane pad (PAD_SPEC): only THIS plane
                # is re-established — never a full padded input set.
                # The device buffer may ALIAS arr on the CPU backend
                # (zero-copy device_put): xla_owned=False keeps it out of
                # every donation path
                arr = pm.pad_plane(name, cur, pad)
                dev = jax.device_put(np.ascontiguousarray(arr),
                                     getattr(sh, name))
                transfer += arr.nbytes
                xla_owned = False
                if rec is not None or lost_residency:
                    # had residency, lost the identity chain (out-of-
                    # order base, eviction, layout flip): the cost this
                    # path must keep near zero between back-to-back waves
                    reshard += arr.nbytes
            entry["planes"][name] = (cur, dev, xla_owned)
            resident_dev.append(dev)
        if cache_key is not None:
            self._resident[cache_key] = entry
            self._resident.move_to_end(cache_key)
            self._evicted.discard(cache_key)
            self._resident_bytes += sum(
                rec[1].nbytes for rec in entry["planes"].values()) - freed
            while len(self._resident) > self.cache_entries:
                _k, old = self._resident.popitem(last=False)
                if len(self._evicted) > 16 * self.cache_entries:
                    self._evicted.clear()
                self._evicted.add(_k)
                self._resident_bytes -= sum(
                    rec[1].nbytes for rec in old["planes"].values())
            self._m.resident_bytes.set(self._resident_bytes)
            if was_new:
                # once per bucket: the per-device footprint evidence
                # (HBM headroom) the churn record scrapes
                self.memory_report(inp)
        if probed is not None:
            # the dispatch probe already solved this wave in BOTH layouts
            # (and compared them bitwise); residency was still installed
            # above so the NEXT wave rides the identity chain instead of
            # paying a full re-transfer
            self._m.transfer_bytes.inc(by=transfer)
            self._m.reshard_bytes.inc(by=reshard)
            return probed
        # kube-horizon active sub-mesh (models/submesh.py): on the
        # single-device layout — the measured winner at the contract
        # shape (r15: node_shards 1) — compact the node axis to the
        # nodes that could possibly place this wave before the dense
        # scan. Bit-identical by the keep-rule argument in the module
        # docstring, and probed live against the full plane below. The
        # gather runs ON DEVICE over the same resident planes, so
        # residency and the delta identity chain are untouched.
        plan = None
        zone_bf16 = False
        if int(mesh.shape["nodes"]) == 1:
            from kubernetes_tpu.models import submesh as sm
            t_k0 = time.perf_counter()
            plan = sm.plan_wave(inp, pol)
            if plan is not None:
                self._sm.compact_s.observe(time.perf_counter() - t_k0)
                self._sm.waves.inc()
                self._sm.nodes_kept.inc(by=plan.n_kept)
                self._sm.nodes_total.inc(by=plan.n_total)
                self.submesh_waves += 1
                zone_bf16 = sm.zone_bf16_ok(inp, pol)
            else:
                self._sm.full_waves.inc()
        wave_dev = []
        for name in pm.WAVE_FIELDS:
            arr = getattr(inp, name)
            if plan is not None and name == "pod_host_idx":
                # host pins move to compact indices host-side (pinned
                # nodes are kept by construction, so no pin is lost)
                from kubernetes_tpu.models import submesh as sm
                arr = sm.remap_pod_host_idx(arr, plan)
            wave_dev.append(jax.device_put(np.ascontiguousarray(arr),
                                           getattr(sh, name)))
            transfer += arr.nbytes
        if tctx is not None:
            # plane residency/transfer leg vs the device program itself —
            # the split the reshard-bytes wall analysis had to infer
            tracing.record("mesh.planes", t_pl0, time.monotonic_ns(),
                           parent=tctx, transfer=transfer, reshard=reshard)
        t_dv0 = time.monotonic_ns()
        # donate=False: every wave plane above came from device_put of a
        # request-owned host array and may alias it on the CPU backend —
        # donating an aliased buffer hands numpy-owned memory to XLA's
        # allocator and corrupts the native heap (the malloc() abort that
        # killed the daemon mid-churn until flightrec pinned the timing).
        # The wave planes are [P]-scale; forgoing their reuse costs ~KBs.
        if plan is not None:
            from kubernetes_tpu.models import submesh as sm
            fn = sm.submesh_program(pol, gangs, zone_bf16)
            chosen, scores = fn(tuple(resident_dev), tuple(wave_dev),
                                plan.keep_idx, plan.valid)
            both = np.asarray(jnp.stack([chosen, scores]))
        else:
            fn = pm.sharded_program(mesh, pol, gangs, donate=False)
            with pm.donation_warnings_scoped():
                chosen, scores = fn(tuple(resident_dev), tuple(wave_dev))
                both = np.asarray(jnp.stack([chosen, scores]))
        if tctx is not None:
            tracing.record("mesh.device_solve", t_dv0, time.monotonic_ns(),
                           parent=tctx,
                           node_shards=int(mesh.shape["nodes"]),
                           submesh=plan.n_kept if plan is not None else 0)
        self._m.transfer_bytes.inc(by=transfer)
        self._m.reshard_bytes.inc(by=reshard)
        self._m.solve_s.observe(time.perf_counter() - t_wave)
        out = (both[0], both[1])
        if plan is not None and (self.probe == "all"
                                 or not self._submesh_probed):
            self._submesh_probed = True
            self._submesh_parity_probe(inp, pol, gangs, mesh, out)
        if self.probe == "all" or (self.probe == "first"
                                   and not self._probed_once):
            self._probed_once = True
            self._parity_probe(inp, pol, gangs, mesh, out)
        return out

    def _submesh_parity_probe(self, inp, pol, gangs, mesh, out) -> None:
        """Re-solve a compacted wave on the FULL node plane (same mesh,
        no compaction) and compare bitwise — the live evidence that the
        keep rule, the index remap, and any gated precision downgrade
        (zone_bf16) changed the layout and nothing else. Runs on the
        first submesh wave of a run, every wave under probe='all';
        never under probe='off'."""
        if self.probe == "off":
            return
        try:
            res, _t = self._time_layout(mesh, inp, pol, gangs)
        except Exception as e:  # noqa: BLE001 — a probe must never kill a wave
            _log.warning("submesh parity probe failed to run: %s", e)
            return
        self._sm.parity_checks.inc()
        if not (np.array_equal(res[0], out[0])
                and np.array_equal(res[1], out[1])):
            self.submesh_parity_divergent += 1
            self._sm.parity_divergent.inc()
            _log.error("submesh parity probe DIVERGED: compacted vs full "
                       "plane — keep rule or remap violated bit-identity")

    def _parity_probe(self, inp, pol, gangs, active_mesh, out) -> None:
        """Re-solve the same wave in the OTHER layout (single-device
        submesh, or the full mesh when the active layout already is the
        submesh) and compare bitwise — the live every-run evidence behind
        the 'layout changes, decisions do not' contract."""
        other = self.submesh if active_mesh is not self.submesh else self.mesh
        try:
            res, t = self._time_layout(other, inp, pol, gangs)
        except Exception as e:  # noqa: BLE001 — a probe must never kill a wave
            _log.warning("mesh parity probe failed to run: %s", e)
            return
        self.parity_checks += 1
        self._m.parity_checks.inc()
        self._m.single_probe_s.observe(t)
        if not (np.array_equal(res[0], out[0])
                and np.array_equal(res[1], out[1])):
            self.parity_divergent += 1
            self._m.parity_divergent.inc()
            _log.error("mesh parity probe DIVERGED: %s vs %s layout",
                       active_mesh.shape, other.shape)

    def _pad_vals(self, name: str, vals: np.ndarray, pad: int) -> np.ndarray:
        """Row-delta values padded to the resident (mesh-padded) row
        width. Only planes whose node axis is NOT axis 0 need this: their
        delta rows span the full padded row. Fills match
        pad_inputs_for_mesh exactly (zone pads unlabeled, counts pad
        zero)."""
        if pad == 0:
            return vals
        if name == "zone_idx":          # [k, N] -> [k, N+pad], unlabeled
            return np.pad(vals, ((0, 0), (0, pad)), constant_values=-1)
        if name == "group_counts":      # [k, N+1] -> [k, N+1+pad], empty
            return np.pad(vals, ((0, 0), (0, pad)), constant_values=0)
        return vals

    def memory_report(self, inp) -> dict:
        """shard_memory_report under the full mesh, surfaced to the
        ``solverd_mesh_shard_bytes_per_device`` gauge by the service."""
        rep = self._pm.shard_memory_report(inp, self.mesh)
        self._m.shard_bytes_per_device.set(rep["total_bytes_per_device"])
        return rep
