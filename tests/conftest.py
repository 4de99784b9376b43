"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(kubernetes_tpu.parallel) is exercised without TPU hardware, mirroring how the
reference tests "multi-node" behavior in one process with fakes
(ref: cmd/integration/integration.go:67-117).

The platform is forced twice: through the environment, which the
subprocesses tests spawn inherit, and through jax.config, which holds in
this process even where the environment already named another platform.
The one file that touches the TPU's compiler, test_tpu_compile.py,
describes its chip inside a fixture (see its docstring).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # for any subprocesses
# kube-slipstream fill-trigger prewarm is default-ON in production; in
# the suite it would queue background XLA compiles of doubled buckets
# behind nearly every scheduler construction, taxing every test for
# programs the test never uses. Tests that exercise prewarm construct
# PrewarmController (or monkeypatch this) explicitly.
os.environ.setdefault("KTPU_PREWARM", "off")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

# Wire-version matrix: hack/test.sh exports KUBE_TEST_API_VERSION per run;
# the override lives in the test harness so production clients never read
# the environment (advisor r1 #4).
_v = os.environ.get("KUBE_TEST_API_VERSION", "")
if _v:
    from kubernetes_tpu.client import http as _client_http

    _client_http.test_version_override = _v

# Race-probe mode (hack/test.sh --race): the Go race detector analog
# (ref: hack/test-go.sh:50 -race). A near-zero switch interval forces the
# interpreter to preempt threads between nearly every bytecode, so lock
# ordering bugs and unsynchronized check-then-act windows in the
# threading-heavy core (memstore watch fan-out, remote store, proxy, pod
# workers, keep-alive transport) surface as real failures instead of
# staying improbable. hack/test.sh --race repeats the concurrency suites
# under this regime.
if os.environ.get("KTPU_RACE"):
    import sys as _sys

    _sys.setswitchinterval(1e-6)

    # Lock-order sanitizer (util/locksmith.py): every threading.Lock/
    # RLock created from here on records per-thread acquisition chains
    # into a global order graph; a cycle = a potential deadlock the
    # switch-interval regime made probable but not necessarily fatal.
    # pytest_sessionfinish below turns any cycle into a failed run.
    from kubernetes_tpu.util import locksmith as _locksmith

    _locksmith.arm()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running e2e (tier-1 excludes via -m 'not slow'; the "
        "--race rounds and full hack/test.sh runs include it)")


def pytest_sessionfinish(session, exitstatus):
    """--race rounds fail loudly on any lock-order cycle locksmith saw,
    even if no schedule actually deadlocked during the run."""
    if not os.environ.get("KTPU_RACE"):
        return
    import sys

    from kubernetes_tpu.util import locksmith

    reps = locksmith.reports()
    if reps:
        print("\n=== locksmith: potential deadlocks (lock-order cycles) "
              "===", file=sys.stderr)
        for r in reps:
            print(locksmith.format_report(r), file=sys.stderr)
        session.exitstatus = 1
    else:
        print(f"\n[locksmith] armed={locksmith.armed()} "
              f"lock-order cycles: 0 "
              f"(order edges observed: {len(locksmith.edges())})",
              file=sys.stderr)
    if os.environ.get("KTPU_LOCK_EDGES"):
        # dump the measured order table (docs/design/invariants.md)
        for (a, b), n in sorted(locksmith.edges().items(),
                                key=lambda kv: -kv[1]):
            print(f"[locksmith] edge {n:>8} {a} -> {b}", file=sys.stderr)
