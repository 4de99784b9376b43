#!/usr/bin/env bash
# Run the unit/integration suite (ref: hack/test-go.sh). Like the
# reference's KUBE_TEST_API_VERSIONS loop, the suite can be run once per
# external API version: TEST_API_VERSIONS=v1,v1beta1 hack/test.sh
#
# --race: the Go race detector analog (ref: hack/test-go.sh:50). Runs the
# concurrency-heavy suites RACE_ROUNDS times (default 3) with the
# interpreter switch interval forced to ~1us (tests/conftest.py), so
# thread preemption lands between nearly every bytecode and
# check-then-act races become probable instead of theoretical. Under
# KTPU_RACE the lock-order sanitizer (util/locksmith.py) is armed too:
# every Lock/RLock records per-thread acquisition chains into a global
# order graph, and any cycle (an A->B / B->A inversion — a potential
# deadlock even if no schedule hung) fails the round with both stacks.
# Latest full run: hack/race-report.md.
set -euo pipefail
cd "$(dirname "$0")/.."

RACE=0
ARGS=()
for a in "$@"; do  # --race is recognized anywhere in the argument list
    if [[ "$a" == "--race" ]]; then RACE=1; else ARGS+=("$a"); fi
done
set -- ${ARGS+"${ARGS[@]}"}

# Collection smoke: a single SyntaxError anywhere silently disabled 13
# test modules once (util/metrics.py f-string, seed state). compileall is
# ~2s and makes that class of failure loud before any suite runs.
echo "=== compile smoke (python -m compileall) ==="
python -m compileall -q kubernetes_tpu tests bench.py hack

# kube-vet: the govet analog (ref: hack/test-go.sh gating on govet).
# Invariant rules in kubernetes_tpu/analysis (donation-safety, clone-
# mutation, thread-discipline, metrics-sync, unused) over
# the whole tree; waivers require a rule id + reason. Also enforced as
# a tier-1 test (tests/test_vet.py::test_tree_is_vet_clean).
echo "=== kube-vet (hack/vet.py) ==="
python hack/vet.py

if [[ "$RACE" == 1 ]]; then
    ROUNDS="${RACE_ROUNDS:-3}"
    SUITES=(tests/test_contention.py tests/test_storage.py
            tests/test_storeshard.py
            tests/test_remote_store.py tests/test_cache.py
            tests/test_http.py tests/test_apiserver.py
            tests/test_stale_wave.py
            tests/test_websocket_pprof.py tests/test_cloudprovider.py
            tests/test_envvars.py tests/test_capabilities.py
            tests/test_kubelet.py tests/test_process_runtime.py
            tests/test_controllers.py tests/test_scheduler.py
            tests/test_integration.py tests/test_solverd.py
            tests/test_incremental.py tests/test_parallel.py
            tests/test_tracing.py tests/test_flightrec.py
            tests/test_vet.py tests/test_preempt.py
            tests/test_explain.py tests/test_record.py
            tests/test_chaos.py tests/test_fairshed.py
            tests/test_defrag.py tests/test_share.py
            tests/test_submesh.py
            tests/test_slipstream.py)
    rc=0
    for ((i = 1; i <= ROUNDS; i++)); do
        echo "=== race round ${i}/${ROUNDS} (switchinterval=1e-6) ==="
        KTPU_RACE=1 python -m pytest "${SUITES[@]}" -q "$@" || rc=$?
    done
    exit "$rc"
fi

VERSIONS="${TEST_API_VERSIONS:-v1,v1beta1,v1beta2}"
rc=0
for v in ${VERSIONS//,/ }; do
    echo "=== test run with KUBE_TEST_API_VERSION=${v} ==="
    KUBE_TEST_API_VERSION="$v" python -m pytest tests/ -q "$@" || rc=$?
done

# Tier-2: the solver suites again on an 8-way CPU sub-mesh. conftest
# already forces 8 virtual devices for every run above; this step pins
# the flag EXPLICITLY (immune to a pre-set XLA_FLAGS in the environment)
# so the mesh executor, delta-onto-sharded-planes, and
# wave-loop-through-mesh suites always see the multi-device topology the
# production solverd --mesh path ships with.
echo "=== tier-2: solver suites under xla_force_host_platform_device_count=8 ==="
XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
    python -m pytest tests/test_parallel.py tests/test_solverd.py \
    tests/test_batch_solver.py tests/test_submesh.py -q "$@" || rc=$?

# perfgate: every committed CHURN_MP record from r08 on must still gate
# green against its own best prior — the sustained-rate trajectory
# (182/s r04 -> 496.8/s r10) can never silently regress in-tree.
echo "=== perfgate over committed records ==="
python hack/perfgate.py --check-committed || rc=$?
exit "$rc"
