"""A run with the timed path broken underneath comes out not correct.

Each case drives a whole CPU rehearsal of the cell (``fault_run.py``, in a
child process so that the four-device cell gets its virtual devices) with
one fault planted, and reads ``correct`` from the result line; ``none``
is the unbroken run, which has to come out correct.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

CASES = [
    ("mpdata.16k.1chip", "none", True),
    ("mpdata.16k.1chip", "state_unchanged", False),
    ("mpdata.16k.1chip", "answer_altered", False),
    ("mpdata.16k.2x2", "none", True),
    ("mpdata.16k.2x2", "exchange_left_out", False),
    ("yi6b.chat", "none", True),
    ("yi6b.chat", "state_unchanged", False),
    ("yi6b.chat", "token_altered", False),
    ("yi6b.chat", "half_batch", False),
    ("yi6b.docqa", "none", True),
    ("yi6b.docqa", "token_altered", False),
]


@pytest.mark.parametrize("cell,fault,correct", CASES)
def test_fault_is_caught(cell, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), "--fault",
         fault, "--", "--workload", cell, "--seed", "2147483659",
         "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] == "cpu rehearsal"
    assert result["correct"] is correct, out.stderr[-3000:]
