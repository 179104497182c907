"""Work counts and the peak table."""

import json
import pathlib

import jax
import numpy as np
import pytest

from bench import reference, samplers, workcount

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_descent_bytes_are_two_int32_per_slot():
    assert workcount.descent_bytes(1) == 8
    assert workcount.descent_bytes(80_576_064) == 644_608_512


def test_magm_candidates_are_blocks_squared_times_budget():
    # the paper's main line at n = 2^16: 64 block pairs of 1,259,001
    assert workcount.candidates_per_graph(_config("magm_theta1_n16")) == 64 * 1_259_001


def test_graph500_candidates_are_the_expected_draws_to_the_target():
    cfg = _config("graph500_s20")
    slots = workcount.candidates_per_graph(cfg)
    # 16 x 2^20 distinct cells need a few per cent more draws
    assert 16_777_216 < slots < 18_874_368
    assert workcount.kpgm_expected_distinct(cfg["initiator"], 20, slots) >= 16_777_216
    assert workcount.kpgm_expected_distinct(cfg["initiator"], 20, slots - 1) < 16_777_216


def test_kpgm_slots_match_the_reference_at_a_small_scale():
    theta = np.asarray([[0.57, 0.19], [0.19, 0.05]], np.float32)
    d, edges = 12, 16 << 12
    expected = workcount.kpgm_slots(theta, d, edges)
    thetas = np.broadcast_to(theta, (d, 2, 2))
    base = samplers.base_key(3)
    drawn = [
        reference.kpgm_first_distinct(thetas, edges, samplers.graph_key(base, i))[1]
        for i in range(3)
    ]
    assert all(abs(s - expected) < 0.02 * expected for s in drawn), (drawn, expected)


def test_peak_table_knows_the_v5e_and_refuses_others():
    p = workcount.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        workcount.peak("cpu")


def test_graph_keys_take_seeds_past_32_bits():
    a = samplers.base_key(5)
    b = samplers.base_key(5 + (1 << 32))
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
