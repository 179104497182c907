"""Serving driver: LM decode loop, or MAGM graph sampling as a service.

LM mode (prefill a prompt batch, then greedy-decode tokens):

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Graph mode (--magm): build ONE sampler session and serve sample requests
from it through :class:`GraphServer` — a bounded-in-flight-queue service
with per-request deadlines, typed error responses and
retry-after-transient-fault, so the session's warm amortized latency is
what requests actually see and overload degrades into explicit shedding
instead of unbounded queue delay:

    PYTHONPATH=src python -m repro.launch.serve --magm --graph-d 12 \
        --requests 4 --chunk-edges 16384 [--mesh] \
        [--max-queue 8] [--deadline-s 30]

Response contract (``ServeResponse``): every request — well-formed or
garbage — gets exactly one typed response; the server loop never dies on
a request's account.  ``status``/``code`` pairs:

    ok                 0    edges attached
    bad_request      400    malformed payload (message says what)
    deadline_exceeded 408   deadline passed before service finished
    overloaded       429    in-flight queue full — request shed at submit
    error            500    fault survived the retry policy
"""

from __future__ import annotations

import argparse
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import chaos


def _validate_chunk(chunk, n: int) -> None:
    """Reject malformed streamed chunks loudly.

    The old check (``chunk.min(initial=0) >= 0``) was vacuous: with zero
    rows ``min(initial=0)`` IS 0, so an empty or even float chunk sailed
    through.  Streamed chunks must be non-empty (the stream contract emits
    no zero-row chunks), integer, (E, 2), and in ``[0, n)``.
    """
    if chunk.ndim != 2 or chunk.shape[1] != 2:
        raise AssertionError(f"chunk shape {chunk.shape}, want (E, 2)")
    if chunk.shape[0] == 0:
        raise AssertionError("stream emitted an empty chunk")
    if chunk.dtype.kind not in "iu":
        raise AssertionError(f"chunk dtype {chunk.dtype}, want integer")
    lo, hi = int(chunk.min()), int(chunk.max())
    if lo < 0 or hi >= n:
        raise AssertionError(f"edge ids [{lo}, {hi}] outside [0, {n})")


class ServeResponse(NamedTuple):
    """One typed answer per request; ``edges`` only on ``status == "ok"``."""

    status: str  # ok | bad_request | deadline_exceeded | overloaded | error
    code: int  # 0 | 400 | 408 | 429 | 500
    message: str = ""
    edges: Optional[np.ndarray] = None
    chunks: int = 0
    wait_s: float = 0.0  # submit -> service start (queue delay)
    service_s: float = 0.0  # sampling wall time

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class _Request(NamedTuple):
    future: Future
    key: Optional[Any]
    chunk_edges: int
    num_edges: Optional[int]
    t_submit: float
    t_deadline: Optional[float]


class GraphServer:
    """Bounded-queue sampling service over one sampler session.

    One worker thread drains a ``Queue(maxsize=max_queue)`` of requests
    against the (single-threaded, dispatch-owning) session.  The three
    resilience behaviours the paper-scale service needs:

    - **Load-shedding**: a submit against a full queue gets an immediate
      typed ``overloaded`` response instead of a slot — so the p99 of the
      requests the server DOES accept is bounded by
      ``(max_queue + 1) x max service time``, never by arrival rate.
    - **Deadlines**: each request carries a deadline (per-request
      ``deadline_s`` or the server default); one that expires while
      queued is answered ``deadline_exceeded`` without sampling, and the
      retry loop inherits the remaining budget.
    - **Retry-after-fault**: each service attempt passes the
      ``serve.request`` chaos site and runs under ``retry_policy``
      (transient :class:`repro.dist.chaos.InjectedFault` dispatches are
      retried with backoff; exhaustion or a fatal fault returns a typed
      ``error`` response).  The worker loop survives every response.

    ``stats`` counts submitted/accepted/shed/completed/deadline_expired/
    errors/retries.  Use as a context manager, or call :meth:`close`.
    """

    def __init__(
        self,
        sampler,
        *,
        max_queue: int = 8,
        deadline_s: Optional[float] = None,
        chunk_edges: int = 1 << 14,
        retry_policy: Optional[chaos.RetryPolicy] = None,
    ) -> None:
        self.sampler = sampler
        self.chunk_edges = int(chunk_edges)
        self.deadline_s = deadline_s
        self.retry_policy = (
            retry_policy
            if retry_policy is not None
            else chaos.RetryPolicy(max_attempts=3, base_delay=0.01)
        )
        self._q: "queue.Queue[Optional[_Request]]" = queue.Queue(
            maxsize=max(int(max_queue), 1)
        )
        self.stats: Dict[str, int] = {
            "submitted": 0,
            "accepted": 0,
            "shed": 0,
            "completed": 0,
            "deadline_expired": 0,
            "errors": 0,
            "retries": 0,
        }
        self._lock = threading.Lock()
        self._closed = False
        self._worker = threading.Thread(
            target=self._drain, name="graph-server", daemon=True
        )
        self._worker.start()

    # -- submission ----------------------------------------------------

    def _bump(self, stat: str, by: int = 1) -> None:
        with self._lock:
            self.stats[stat] += by

    def _resolved(self, resp: ServeResponse) -> Future:
        f: Future = Future()
        f.set_result(resp)
        return f

    def submit(
        self,
        *,
        key=None,
        chunk_edges: Optional[int] = None,
        num_edges: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> Future:
        """Enqueue one sample request; always returns a Future holding a
        :class:`ServeResponse` (shed/invalid requests resolve at once)."""
        self._bump("submitted")
        if self._closed:
            return self._resolved(
                ServeResponse("error", 500, "server is closed")
            )
        ce = self.chunk_edges if chunk_edges is None else chunk_edges
        dl = self.deadline_s if deadline_s is None else deadline_s
        try:
            ce = int(ce)
            if ce <= 0:
                raise ValueError(f"chunk_edges must be positive, got {ce}")
            if num_edges is not None:
                num_edges = int(num_edges)
                if num_edges < 0:
                    raise ValueError(
                        f"num_edges must be >= 0, got {num_edges}"
                    )
                if not hasattr(self.sampler, "params"):
                    raise ValueError(
                        "num_edges override is only valid for KPGM "
                        "sessions (the MAGM edge count is the model's "
                        "own draw)"
                    )
            if dl is not None:
                dl = float(dl)
                if dl <= 0:
                    raise ValueError(
                        f"deadline_s must be positive, got {dl}"
                    )
        except (TypeError, ValueError) as exc:
            return self._resolved(ServeResponse("bad_request", 400, str(exc)))
        now = time.monotonic()
        req = _Request(
            Future(), key, ce, num_edges, now,
            None if dl is None else now + dl,
        )
        try:
            self._q.put_nowait(req)
        except queue.Full:
            self._bump("shed")
            return self._resolved(
                ServeResponse(
                    "overloaded",
                    429,
                    f"in-flight queue full ({self._q.maxsize}); retry later",
                )
            )
        self._bump("accepted")
        return req.future

    def handle(self, payload) -> Future:
        """Dict-payload front door (the HTTP-shaped surface): parse
        ``{"kind": "sample", "seed"/"chunk_edges"/"num_edges"/
        "deadline_s": ...}`` and submit.  Garbage payloads of any shape
        resolve to typed ``bad_request`` responses — never an escaped
        exception, so one bad client cannot kill the loop."""
        if not isinstance(payload, dict):
            return self._resolved(
                ServeResponse(
                    "bad_request", 400,
                    f"payload must be a dict, got {type(payload).__name__}",
                )
            )
        known = {"kind", "seed", "chunk_edges", "num_edges", "deadline_s"}
        unknown = set(payload) - known
        if unknown:
            return self._resolved(
                ServeResponse(
                    "bad_request", 400,
                    f"unknown field(s) {sorted(unknown)}; known: "
                    f"{sorted(known)}",
                )
            )
        kind = payload.get("kind", "sample")
        if kind != "sample":
            return self._resolved(
                ServeResponse(
                    "bad_request", 400, f"unknown kind {kind!r}"
                )
            )
        key = None
        seed = payload.get("seed")
        if seed is not None:
            try:
                key = jax.random.PRNGKey(int(seed))
            except (TypeError, ValueError) as exc:
                return self._resolved(
                    ServeResponse("bad_request", 400, f"bad seed: {exc}")
                )
        return self.submit(
            key=key,
            chunk_edges=payload.get("chunk_edges"),
            num_edges=payload.get("num_edges"),
            deadline_s=payload.get("deadline_s"),
        )

    # -- worker --------------------------------------------------------

    def _drain(self) -> None:
        while True:
            req = self._q.get()
            if req is None:
                return
            try:
                resp = self._serve_one(req)
            except BaseException as exc:  # noqa: B036 - loop must survive
                self._bump("errors")
                resp = ServeResponse("error", 500, repr(exc))
            req.future.set_result(resp)

    def _serve_one(self, req: _Request) -> ServeResponse:
        t_start = time.monotonic()
        wait = t_start - req.t_submit
        if req.t_deadline is not None and t_start > req.t_deadline:
            self._bump("deadline_expired")
            return ServeResponse(
                "deadline_exceeded", 408,
                f"deadline passed {t_start - req.t_deadline:.3f}s before "
                "service started",
                wait_s=wait,
            )

        def attempt():
            chaos.maybe_fail("serve.request")
            kwargs = {"chunk_edges": req.chunk_edges}
            if req.num_edges is not None:
                kwargs["num_edges"] = req.num_edges
            parts = []
            for chunk in self.sampler.sample_stream(req.key, **kwargs):
                _validate_chunk(chunk, self.sampler.n)
                parts.append(chunk)
            return parts

        policy = self.retry_policy
        if req.t_deadline is not None:
            budget = req.t_deadline - t_start
            policy = policy._replace(
                deadline=budget
                if policy.deadline is None
                else min(policy.deadline, budget)
            )
        try:
            parts = chaos.with_retries(
                attempt,
                policy,
                on_retry=lambda *_: self._bump("retries"),
            )
        except chaos.DeadlineExceeded as exc:
            self._bump("deadline_expired")
            return ServeResponse(
                "deadline_exceeded", 408, str(exc), wait_s=wait,
                service_s=time.monotonic() - t_start,
            )
        except Exception as exc:
            self._bump("errors")
            return ServeResponse(
                "error", 500, repr(exc), wait_s=wait,
                service_s=time.monotonic() - t_start,
            )
        service = time.monotonic() - t_start
        if req.t_deadline is not None and time.monotonic() > req.t_deadline:
            self._bump("deadline_expired")
            return ServeResponse(
                "deadline_exceeded", 408,
                f"service finished {time.monotonic() - req.t_deadline:.3f}s "
                "past the deadline",
                wait_s=wait, service_s=service,
            )
        edges = (
            np.concatenate(parts)
            if parts
            else np.zeros((0, 2), dtype=self.sampler.config.dtype)
        )
        self._bump("completed")
        return ServeResponse(
            "ok", 0, edges=edges, chunks=len(parts),
            wait_s=wait, service_s=service,
        )

    # -- lifecycle -----------------------------------------------------

    def close(self) -> None:
        """Stop accepting, drain in-flight requests, join the worker."""
        with self._lock:
            # two racing close() calls must not both enqueue the drain
            # sentinel (the worker would exit after the first and leave
            # the second blocked on a full queue)
            if self._closed:
                return
            self._closed = True
        self._q.put(None)  # blocks until a slot frees; sentinel drains last
        self._worker.join()

    def __enter__(self) -> "GraphServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def serve_graphs(args) -> None:
    from repro.api import MAGMSampler, SamplerConfig
    from repro.configs.magm_paper import DEFAULT_MU, THETA_1
    from repro.core import magm

    d = args.graph_d
    config = SamplerConfig(
        params=magm.make_params(THETA_1, mu=DEFAULT_MU, d=d),
        num_nodes=2**d,
        attribute_key=jax.random.PRNGKey(args.seed),
        mesh="auto" if args.mesh else None,
    )
    t0 = time.perf_counter()
    sampler = MAGMSampler(config, key=jax.random.PRNGKey(args.seed + 1))
    t_build = time.perf_counter() - t0
    print(
        f"[serve] session up in {t_build:.2f}s: n={sampler.n} "
        f"B={sampler.plan.B} mesh={sampler.mesh}"
    )

    total = empty = failed = 0
    with GraphServer(
        sampler,
        max_queue=args.max_queue,
        deadline_s=args.deadline_s,
        chunk_edges=args.chunk_edges,
    ) as server:
        futures = [server.submit() for _ in range(args.requests)]
        for r, fut in enumerate(futures):
            resp = fut.result()
            if not resp.ok:
                failed += 1
                print(
                    f"[serve] request {r}: {resp.status} ({resp.code}) "
                    f"{resp.message}"
                )
                continue
            nedges = int(resp.edges.shape[0])
            total += nedges
            if nedges == 0:
                # a 0-edge draw is a legal sample (the |E| target can be
                # 0), not a silent "0 chunks" — say so explicitly
                empty += 1
                print(
                    f"[serve] request {r}: EMPTY sample (0 edges), "
                    f"{resp.service_s:.3f}s"
                )
            else:
                print(
                    f"[serve] request {r}: {nedges} edges in "
                    f"{resp.chunks} chunks, {resp.service_s:.3f}s "
                    f"({nedges / max(resp.service_s, 1e-9):.0f} edges/s, "
                    f"waited {resp.wait_s:.3f}s)"
                )
        stats = dict(server.stats)
    if failed:
        # a served run that dropped requests did not pass, whatever the
        # server survived
        raise SystemExit(
            f"[serve] FAILED: {failed} of {args.requests} requests not ok "
            f"(stats={stats})"
        )
    if total == 0:
        print(f"[serve] WARNING: all {args.requests} requests were empty")
    print(
        f"[serve] OK ({total} edges over {args.requests} requests, "
        f"{empty} empty; stats={stats})"
    )


def serve_lm(args) -> None:
    from repro import configs
    from repro.models.model import build as build_model
    from repro.train import steps as steps_lib

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)

    b, s = args.batch, args.prompt_len
    prompts = jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (b, s), 0, cfg.vocab_size
    )
    context = None
    if cfg.family == "vlm":
        context = jnp.zeros((b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        context = jnp.zeros((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)

    max_len = s + args.gen
    prefill = jax.jit(steps_lib.make_prefill_step(model, max_len=max_len))
    decode = jax.jit(steps_lib.make_decode_step(model))

    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": prompts, "context": context})
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    out = [next_tok]
    for i in range(args.gen - 1):
        batch = {
            "cache": cache,
            "tokens": next_tok[:, None],
            "cache_len": jnp.int32(s + i),
            "context": context,
        }
        next_tok, _, cache = decode(params, batch)
        out.append(next_tok)
    toks = jnp.stack(out, axis=1)
    dt = time.perf_counter() - t0
    print(f"[serve] {cfg.name}: generated {toks.shape} in {dt:.2f}s")
    print("[serve] sample row:", toks[0].tolist())
    assert bool(jnp.isfinite(logits).all()), "non-finite prefill logits"
    print("[serve] OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--magm", action="store_true", help="serve MAGM graphs")
    ap.add_argument("--graph-d", type=int, default=12)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--chunk-edges", type=int, default=1 << 14)
    ap.add_argument("--mesh", action="store_true", help="shard over devices")
    ap.add_argument(
        "--max-queue",
        type=int,
        default=8,
        help="in-flight request bound; submits beyond it are shed with a "
        "typed 'overloaded' response",
    )
    ap.add_argument(
        "--deadline-s",
        type=float,
        default=None,
        help="per-request deadline in seconds (default: none)",
    )
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.magm:
        serve_graphs(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
