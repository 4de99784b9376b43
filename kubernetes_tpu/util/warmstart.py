"""Warm-start: skip the O(minutes) once-per-shape costs across restarts.

A cold scheduler (or solverd, or bench run) pays two once-per-shape bills
before its first fast wave: the XLA compile of every pow-2 wave bucket
(``compile_s`` — up to minutes per shape on a TPU) and the
wave router's host-vs-device calibration (``router_cal_s``,
models/batch_solver.WaveRouter). Both are pure functions of
(shape bucket, policy, backend), so a restarted process on the same
machine can reuse them:

- the JAX **persistent compilation cache** is turned on with the
  minimum-compile-time threshold dropped to 0, so every solver program
  is eligible. Where ``JAX_COMPILATION_CACHE_DIR`` is set, that is the
  cache and no other directory is set here; otherwise it is the fixed
  ``<cache_dir()>/jax`` (the path is part of the cache key, so a
  directory that moves never hits);
- the **WaveRouter calibrations** load from / save to a JSON store in
  ``cache_dir()`` (WaveRouter.load_calibrations / save_calibrations).

``enable()`` is idempotent and wired into the binaries that own a solver
runtime: ``kube-scheduler --algorithm tpu-batch``, ``kube-solverd``, and
the bench child. Environment knobs:

- ``KTPU_WARM_START=off``  disable entirely (fresh-cold measurements);
- ``KTPU_CACHE_DIR=DIR``   override the cache location (default:
  ``<repo>/.ktpu_cache``, which is gitignored).

An unwritable dir is not fatal: the process re-pays the cold costs,
loudly in the log.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

__all__ = ["cache_dir", "enable", "enabled", "router_cal_path",
           "mesh_cal_path"]

_log = logging.getLogger("kubernetes_tpu.util.warmstart")

_active_dir: Optional[str] = None


def enabled() -> bool:
    return os.environ.get("KTPU_WARM_START", "auto").strip().lower() \
        not in ("off", "0", "false")


def cache_dir() -> str:
    override = os.environ.get("KTPU_CACHE_DIR", "").strip()
    if override:
        return override
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo, ".ktpu_cache")


def router_cal_path(base: Optional[str] = None) -> str:
    return os.path.join(base or cache_dir(), "router_cal.json")


def mesh_cal_path(base: Optional[str] = None) -> str:
    """Mesh-dispatch calibration store (solver/mesh_exec.MeshExecutor):
    sharded-vs-single-device timings keyed by (backend, device count,
    pods_axis, plane shape), so a restarted daemon skips the one-time
    crossover probe the same way the router skips its host-vs-device
    calibration."""
    return os.path.join(base or cache_dir(), "mesh_cal.json")


def enable(base: Optional[str] = None) -> Optional[str]:
    """Turn on the JAX persistent compilation cache and point the default
    wave router's calibration store at the data dir. Idempotent; returns
    the active data dir, or None when warm-start is disabled."""
    global _active_dir
    if not enabled():
        return None
    base = base or cache_dir()
    if _active_dir == base:
        return base
    own_jax_dir = not os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        os.makedirs(os.path.join(base, "jax") if own_jax_dir else base,
                    exist_ok=True)
    except OSError as e:
        _log.warning("warm-start cache dir %r unusable (%s); cold start",
                     base, e)
        return None

    import jax
    if own_jax_dir:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(base, "jax"))
    # every solver program is worth caching: the threshold exists for
    # notebooks full of tiny throwaway jits, not for a scheduler whose
    # whole compile surface is a bounded set of pow-2 wave buckets
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from kubernetes_tpu.models.batch_solver import default_router
    n = default_router.load_calibrations(router_cal_path(base))
    if n:
        _log.info("warm start: %d router calibration(s) restored from %s",
                  n, router_cal_path(base))
    _active_dir = base
    return base
