"""Backend parity: the same unmodified oracle case functions
(tests/cases_parity.py) must pass under the emulated shard_map backend AND
under real multi-process jobs — at n=2 and n=4, over both wire transports.

Each (transport, nprocs) job runs the whole case module once (cached in
:func:`repro.transport.testing.module_results_multiproc`); the parametrized
tests then assert per-case slices, mirroring the emulated harness.  CI's
multiproc smoke lane selects the socket/n=2 slice with ``-k "sock-2"``.

Every rank runs the case bodies on its own, so the property cases draw
from the seeded shim under multiproc (``repro.testing.property_testing``):
the same examples on every rank, or the ranks would call collectives with
different shapes and wait on each other forever.  Each job has a 120 s
deadline; a job that hangs fails the case it hung in, by name, and the
cases after it as "not reached".
"""

from __future__ import annotations

import pytest

from repro.testing import assert_case
from repro.transport.testing import assert_case_multiproc

MODULE = "tests.cases_parity"

# Cases meaningful at any world size >= 2.
CASES = [
    "case_allreduce_logical",
    "case_allreduce_operators",
    "case_alltoall_reduce_scatter",
    "case_barrier_and_token_sequencing",
    "case_bucketed_overlap_ordering",
    "case_compressed_rejects_integer_payloads",
    "case_disable_jit_debug_mode",
    "case_ef_determinism_bitwise",
    "case_ef_residual_norm_bounded",
    "case_ef_telescoping_identity_grid",
    "case_err_truncate_three_paths",
    "case_listing5_exchange",
    "case_p2p_datatype_payloads",
    "case_p2p_err_truncate",
    "case_property_collectives_match_oracle",
    "case_property_permute_roundtrip",
    "case_scatter_gather_allgather",
    "case_sendrecv_ring_all_dtypes",
    "case_view_strided_send_recv",
    "case_vvariant_requests_and_plans",
    "case_wire_bytes_compressed",
    "case_vvariant_validation_errors",
    "case_wtime",
]

# Join only at n >= 4 (rank schedules / error-path shapes need the room).
CASES_N4_ONLY = [
    "case_bcast_all_dtypes",
    "case_p2p_tag_matching",
    "case_p2p_trace_time_topology_errors",
    "case_view_transposed_fortran_analogue",
]

CONFIGS = [("sock", 2), ("shm", 2), ("sock", 4), ("shm", 4)]


@pytest.mark.parametrize("case", CASES + CASES_N4_ONLY)
def test_parity_emulated(case):
    assert_case(MODULE, case, n_devices=8)


@pytest.mark.parametrize("transport,nprocs", CONFIGS,
                         ids=[f"{t}-{n}" for t, n in CONFIGS])
@pytest.mark.parametrize("case", CASES + CASES_N4_ONLY)
def test_parity_multiproc(case, transport, nprocs):
    if nprocs < 4 and case in CASES_N4_ONLY:
        pytest.skip("case needs a world size >= 4")
    assert_case_multiproc(MODULE, case, nprocs, transport)


@pytest.mark.parametrize("nprocs", [2, 4])
def test_shm_ring_exchange_rounds(nprocs):
    """Twenty rounds of back-to-back ring ``sendrecv``s over the shm wire
    (tests/cases_shm_rounds.py) deliver the right bytes and finish well
    inside a 60 s deadline.  Before the ring read and wrote its counters
    as whole u64s, most such jobs returned wrong data or hung."""
    assert_case_multiproc("tests.cases_shm_rounds",
                          "case_ring_exchange_rounds", nprocs, "shm",
                          timeout=60.0)
