"""Pytest wrappers for the multi-rank PDE cases (8 emulated devices)."""

import pytest

from repro.testing import assert_case

pytestmark = pytest.mark.multidev

CASES = [
    "case_halo_exchange_matches_roll",
    "case_cahn_hilliard_matches_oracle",
    "case_mpdata_matches_oracle_all_layouts",
    "case_mpdata_conservation_and_positivity",
    "case_cahn_hilliard_conserves_mass_when_k0",
    "case_cahn_hilliard_diagnostics_mass",
    "case_halo_strips_match_padded_block",
    "case_mpdata_fused_decomposed_matches_oracle",
    "case_mpdata_fused_conservation_and_positivity",
]


@pytest.mark.parametrize("case", CASES)
def test_pde_case(case):
    assert_case("tests.cases_pde", case, n_devices=8)
