"""The plain reference under a Policy (``benchmarks/references/
serial_policy.py``), held in the tier-1 suite: the tests of
``benchmarks/tests/test_serial_policy.py``, collected here as they stand
there, so that the reference the policy cell is decided by, and its
agreement with the program (``encode_snapshot`` with both compiled solves)
under every entry of the Policy vocabulary, are checked on every change —
not only when the benchmark's own tests run."""

# ktpu-vet: ok unused — pytest collects these from this module's namespace
from benchmarks.tests.test_serial_policy import (  # noqa: F401
    test_an_unlabelled_node_scores_nothing_for_anti_affinity,
    test_it_raises_on_what_it_does_not_model,
    test_its_control_comes_out_different,
    test_the_first_peer_pins_the_region_and_zones_spread_within_it,
    test_the_policies_use_every_entry_of_the_vocabulary,
    test_under_a_policy_it_answers_as_the_program_pod_by_pod,
    test_with_no_policy_it_answers_as_serial_default,
)
