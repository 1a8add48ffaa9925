"""Back-to-back ring exchanges, repeated in one multiproc job.

``case_ring_exchange_rounds`` runs the bodies of
``case_sendrecv_ring_all_dtypes`` and ``case_property_permute_roundtrip``
``ROUNDS`` times without a case boundary in between, so thousands of small
frames cross each wire while its peer is reading.  Over the shm ring this
guards the head/tail counters: a counter read while the peer was halfway
through writing it used to move the ring backwards, which returned stale
bytes or lost frame sync and hung the job.
"""

from __future__ import annotations

import tests.cases_core as core

ROUNDS = 20


def case_ring_exchange_rounds():
    for _ in range(ROUNDS):
        core.case_sendrecv_ring_all_dtypes()
        core.case_property_permute_roundtrip()
