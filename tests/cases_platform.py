"""Child processes of the test harness and the transport launcher run on
the CPU backend: on a host with a chip, the parent may hold it."""

import os

import jax


def case_child_runs_on_cpu():
    assert os.environ.get("JAX_PLATFORMS") == "cpu", os.environ.get(
        "JAX_PLATFORMS")
    assert jax.default_backend() == "cpu", jax.default_backend()
