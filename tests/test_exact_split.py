"""An exact-cell round over ``kpgm.DEVICE_MAX_CANDIDATES`` runs as
sub-rounds of whole block pairs, and gives the same edges, in the same
order, as one round and as the plain reference of the law
(``bench/reference.magm_exact``).

The paper's main line (Theta_1, mu = 0.5, d = log2 n) at n = 2^8 to 2^10
with B = 5 (25 block pairs a graph), the cap lowered so that a sub-round
holds ``per_round`` block pairs.  The counter stream is keyed by the global
block-pair id, so the split moves no variate.
"""

import jax
import numpy as np
import pytest

from bench import reference, samplers
from repro import tracing
from repro.api import MAGMSampler, SamplerConfig
from repro.core import kpgm, magm, quilt

THETA1 = np.array([[0.15, 0.7], [0.7, 0.85]], np.float32)
B = 5


def _rise(before):
    return {k: v - before.get(k, 0) for k, v in tracing.snapshot().items()}


@pytest.mark.parametrize(
    "d,per_round,rounds,last",
    [
        (8, 25, 1, 25),  # the cap holds the whole round: today's one round
        (9, 13, 2, 12),  # two sub-rounds, the last one row of padding
        (10, 9, 3, 7),  # three, the last two rows of padding
    ],
)
def test_split_round_is_the_one_round_law(monkeypatch, d, per_round, rounds, last):
    F = samplers.attributes(1 << d, d, 0.5, B, seed=d)
    params = magm.make_params(THETA1, mu=0.5, d=d)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    G = sampler.plan.exact_budget
    key = jax.random.PRNGKey(d)
    ref, slots = reference.magm_exact(F, np.broadcast_to(THETA1, (d, 2, 2)), key)
    assert slots == B * B * G
    whole_batch = [gs.edges for gs in sampler.sample_batch(2, key)]

    monkeypatch.setattr(kpgm, "DEVICE_MAX_CANDIDATES", per_round * G)
    assert quilt.split_graphs(B * B, G) == (rounds, per_round)
    assert B * B - (rounds - 1) * per_round == last
    before = tracing.snapshot()
    run = sampler._run(key)
    rise = _rise(before)
    assert [p.gid0 for p in run.parts] == [i * per_round for i in range(rounds)]
    assert all(p.keep.size == per_round * G for p in run.parts)
    assert rise["quilt.dispatch.device_rounds"] == rounds
    assert rise["quilt.dispatched_slots"] == rounds * per_round * G
    assert rise["quilt.candidate_slots"] == B * B * G
    for name in quilt.FALLBACK_COUNTERS:
        assert rise[f"quilt.dispatch.{name}"] == 0, name

    np.testing.assert_array_equal(run.edges(), ref)
    np.testing.assert_array_equal(sampler.sample(key).edges, ref)
    stream = list(sampler.sample_stream(key, chunk_edges=1000))
    assert all(c.shape[0] == 1000 for c in stream[:-1])
    np.testing.assert_array_equal(np.concatenate(stream), ref)

    before = tracing.snapshot()
    batch = sampler.sample_batch(2, key)
    assert _rise(before)["quilt.dispatch.device_rounds"] == quilt.split_graphs(
        2 * B * B, G
    )[0] > 1
    for got, want in zip(batch, whole_batch, strict=True):
        np.testing.assert_array_equal(got.edges, want)

    on_mesh = MAGMSampler(SamplerConfig(params=params, F=F, mesh="auto"))
    np.testing.assert_array_equal(on_mesh.sample(key).edges, ref)
