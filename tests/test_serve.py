"""GraphServer: typed responses for garbage payloads, bounded-queue
load-shedding with a bounded p99 for accepted requests, per-request
deadlines, and retry-after-fault — the serving half of the resilience
layer (tests/test_resilience.py covers the sampling half)."""

import numpy as np
import pytest

import jax

from repro.api import MAGMSampler, SamplerConfig
from repro.core import magm
from repro.dist import chaos
from repro.launch.serve import GraphServer, ServeResponse, _validate_chunk

THETA = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)


@pytest.fixture(scope="module")
def sampler():
    return MAGMSampler(
        SamplerConfig(
            params=magm.make_params(THETA, 0.5, 6), num_nodes=128
        )
    )


def test_ok_response_carries_validated_edges(sampler):
    with GraphServer(sampler, chunk_edges=64) as srv:
        resp = srv.submit(key=jax.random.PRNGKey(1)).result()
    assert resp.ok and resp.status == "ok" and resp.code == 0
    assert resp.edges.shape[1] == 2 and resp.chunks >= 1
    _validate_chunk(resp.edges, sampler.n)
    # deterministic: same key -> same edges through the server
    with GraphServer(sampler, chunk_edges=64) as srv:
        again = srv.submit(key=jax.random.PRNGKey(1)).result()
    np.testing.assert_array_equal(resp.edges, again.edges)


def test_garbage_payloads_get_typed_errors_and_server_survives(sampler):
    garbage = [
        None,
        42,
        [1, 2, 3],
        "sample please",
        {"kind": "train"},
        {"bogus_field": 1},
        {"chunk_edges": 0},
        {"chunk_edges": -4},
        {"chunk_edges": "many"},
        {"seed": "not-a-seed"},
        {"deadline_s": -1.0},
        {"num_edges": 10},  # MAGM session: the edge count is the model's
        {"num_edges": -1},
    ]
    with GraphServer(sampler, chunk_edges=64) as srv:
        for payload in garbage:
            resp = srv.handle(payload).result()
            assert isinstance(resp, ServeResponse), payload
            assert resp.status == "bad_request" and resp.code == 400, payload
            assert resp.message, payload  # says WHAT was wrong
        # the loop survived all of it: a well-formed request still works
        resp = srv.handle({"kind": "sample", "seed": 3}).result()
        assert resp.ok
        assert srv.stats["errors"] == 0  # bad requests are not errors


def test_overload_sheds_with_typed_response_and_bounded_p99(sampler):
    """Submits beyond the queue bound shed immediately with 'overloaded';
    the p99 latency of ACCEPTED requests stays bounded by the queue
    depth x service time — never by the arrival rate."""
    max_queue = 2
    n_requests = 24
    with GraphServer(sampler, max_queue=max_queue, chunk_edges=64) as srv:
        futures = [
            srv.submit(key=jax.random.PRNGKey(i)) for i in range(n_requests)
        ]
        responses = [f.result() for f in futures]
        stats = dict(srv.stats)

    shed = [r for r in responses if r.status == "overloaded"]
    ok = [r for r in responses if r.ok]
    assert len(shed) + len(ok) == n_requests
    for r in shed:
        assert r.code == 429 and "queue full" in r.message
    # a burst of 24 against a depth-2 queue MUST shed (the worker can hold
    # at most 1 in service + 2 queued at any submit instant)
    assert stats["shed"] == len(shed) > 0
    assert stats["accepted"] == len(ok) >= 1
    assert stats["completed"] == len(ok)

    # p99 bound: every accepted request waited behind at most
    # max_queue in-flight requests plus its own service time
    service_max = max(r.service_s for r in ok)
    latencies = sorted(r.wait_s + r.service_s for r in ok)
    p99 = latencies[min(len(latencies) - 1, int(0.99 * len(latencies)))]
    assert p99 <= (max_queue + 2) * max(service_max, 1e-3), (
        p99,
        service_max,
    )


def test_expired_deadline_skips_service(sampler):
    with GraphServer(sampler, chunk_edges=64) as srv:
        resp = srv.submit(deadline_s=1e-9).result()
    assert resp.status == "deadline_exceeded" and resp.code == 408
    assert resp.service_s == 0.0  # never sampled
    assert srv.stats["deadline_expired"] == 1


def test_transient_fault_is_retried_to_success(sampler):
    sched = chaos.FaultSchedule([chaos.FaultSpec("serve.request", (0,))])
    with GraphServer(sampler, chunk_edges=64) as srv:
        with chaos.active(sched):
            resp = srv.submit(key=jax.random.PRNGKey(5)).result()
        assert resp.ok
        assert srv.stats["retries"] == 1
        assert srv.stats["errors"] == 0
    # the retried response is the SAME sample an unfaulted server returns
    with GraphServer(sampler, chunk_edges=64) as srv:
        clean = srv.submit(key=jax.random.PRNGKey(5)).result()
    np.testing.assert_array_equal(resp.edges, clean.edges)


def test_exhausted_retries_return_typed_error_and_loop_survives(sampler):
    sched = chaos.FaultSchedule(
        [chaos.FaultSpec("serve.request", (0, 1, 2, 3, 4))]
    )
    with GraphServer(sampler, chunk_edges=64) as srv:
        with chaos.active(sched):
            resp = srv.submit(key=jax.random.PRNGKey(5)).result()
        assert resp.status == "error" and resp.code == 500
        assert "InjectedFault" in resp.message
        assert srv.stats["errors"] == 1
        # next request (no fault) is served normally by the same worker
        resp = srv.submit(key=jax.random.PRNGKey(6)).result()
        assert resp.ok


def test_submit_after_close_is_refused(sampler):
    srv = GraphServer(sampler, chunk_edges=64)
    srv.close()
    resp = srv.submit().result()
    assert resp.status == "error" and "closed" in resp.message
    srv.close()  # idempotent


def test_validate_chunk_rejects_malformed():
    with pytest.raises(AssertionError, match="shape"):
        _validate_chunk(np.zeros((3, 3), np.int64), 10)
    with pytest.raises(AssertionError, match="empty"):
        _validate_chunk(np.zeros((0, 2), np.int64), 10)
    with pytest.raises(AssertionError, match="dtype"):
        _validate_chunk(np.zeros((3, 2), np.float32), 10)
    with pytest.raises(AssertionError, match="outside"):
        _validate_chunk(np.full((3, 2), 99, np.int64), 10)


def test_serve_graphs_exits_nonzero_on_failed_request(monkeypatch, capsys):
    """The graph-serving launcher fails its process when any request is
    not ``ok`` — a compile error must not pass as a served run."""
    import argparse

    from repro.launch import serve

    def broken(self, *a, **kw):
        raise RuntimeError("device program failed")

    monkeypatch.setattr(MAGMSampler, "sample_stream", broken)
    args = argparse.Namespace(
        graph_d=4, seed=0, mesh=False, max_queue=8, deadline_s=None,
        chunk_edges=64, requests=2,
    )
    with pytest.raises(SystemExit) as exc:
        serve.serve_graphs(args)
    assert exc.value.code not in (0, None)
    assert "2 of 2 requests not ok" in str(exc.value.code)
    assert "error (500)" in capsys.readouterr().out
