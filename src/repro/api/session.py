"""Session-based sampler facade: build device state once, sample many times.

The paper's headline scale (8M nodes, 20B edges, < 6h) makes the legacy
"free function returning one ndarray" contract the wrong shape twice over:
every call re-pays plan construction (partition + lookup tables + content
digest) and program compilation, and the full edge list must materialize on
one host.  A session fixes both:

- :class:`MAGMSampler` / :class:`KPGMSampler` resolve a frozen
  :class:`repro.api.SamplerConfig` into OWNED device state — the
  :class:`repro.core.quilt.QuiltPlan` (or Section-5
  :class:`repro.core.quilt.SplitPlan`), the resolved mesh placement, and a
  PRNG key stream — exactly once, at construction.  Repeated ``.sample()``
  calls run only the fused per-round dispatches (the compiled round
  programs are cached by static shape, so warm calls skip tracing too).
- ``.sample_stream()`` emits fixed-size deduped edge chunks straight off
  the per-round device buffers without ever materializing the full edge
  list — the per-host answer to "should partial edge lists stay resident".
- ``.sample_batch()`` fuses many independent draws into the SAME device
  rounds (sample s's block pair g' is graph ``s * B^2 + g'`` of the
  segmented dedup), the session-native form of ``kpgm_sample_many``'s
  shared batching.

For a fixed key, ``.sample()``, the deprecated free-function shims, and the
concatenation of ``.sample_stream()`` chunks are all bit-identical, on any
mesh (tests pin this).
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import numpy as np

from repro import tracing
from repro.api.config import SamplerConfig
from repro.api.result import GraphSample, KPGMStats
from repro.core import dedup, kpgm, magm, quilt
from repro.dist import chaos, checkpoint as _ckpt

# identity plans materialize the 2^d config space; past this the host
# reference path is the only sane KPGM backend
KPGM_PLAN_MAX_NODES = 1 << 20


def _resolve_mesh(spec):
    from repro.launch import mesh as mesh_mod

    return mesh_mod.resolve_sampler_mesh(spec)


class _Session:
    """Shared session plumbing: config validation, mesh, key stream."""

    def __init__(self, config: SamplerConfig, *, key=None):
        self.config = config
        self.mesh = _resolve_mesh(config.mesh)
        self._key = key if key is not None else jax.random.PRNGKey(0)

    def _next_key(self) -> jax.Array:
        """Advance the session's key stream (used when sample(key=None))."""
        self._key, sub = jax.random.split(self._key)
        return sub

    def _check_dtype(self, n: int) -> None:
        if n > 0 and np.iinfo(np.dtype(self.config.dtype)).max < n - 1:
            raise ValueError(
                f"dtype {np.dtype(self.config.dtype)} cannot hold node ids "
                f"up to {n - 1}"
            )

    def _cast(self, edges: np.ndarray) -> np.ndarray:
        return edges.astype(self.config.dtype, copy=False)

    # -- resumable streaming (shared) ----------------------------------

    def _digest_parts(self) -> list:
        """Stream-identity config parts (see _stream_config_digest)."""
        raise NotImplementedError

    def _stream_raw(
        self, key, chunk_edges: int, num_edges: Optional[int] = None
    ) -> Iterator[np.ndarray]:
        raise NotImplementedError

    def _stream_config_digest(
        self, chunk_edges: int, num_edges: Optional[int]
    ) -> np.ndarray:
        """Digest of everything the chunk sequence depends on — EXCEPT the
        mesh: layout invariance (per-graph ``fold_in`` keys, shared slot
        counts) means a stream checkpointed on one device layout resumes
        bit-identically on any other, including a degraded one."""
        from repro.api import stream as _stream

        c = self.config
        return _stream.digest_parts(
            [
                type(self).__name__,
                *self._digest_parts(),
                c.backend,
                c.oversample,
                c.max_rounds,
                c.use_kernel,
                str(np.dtype(c.dtype)),
                int(chunk_edges),
                None if num_edges is None else int(num_edges),
            ]
        )

    def _checkpointed_stream(
        self,
        key,
        chunk_edges: int,
        checkpoint_dir: str,
        num_edges: Optional[int] = None,
    ) -> Iterator[np.ndarray]:
        from repro.api import stream as _stream

        state = _stream.initial_state(
            self._stream_config_digest(chunk_edges, num_edges),
            key,
            chunk_edges,
            num_edges,
        )
        return _stream.emit(
            self._stream_raw(key, chunk_edges, num_edges=num_edges),
            checkpoint_dir,
            state,
            slots=lambda: getattr(self, "_last_run_slots", 0),
        )

    def resume_stream(self, checkpoint_dir: str) -> Iterator[np.ndarray]:
        """Continue a checkpointed ``sample_stream`` after an interruption.

        Loads the newest StreamCheckpoint under ``checkpoint_dir``, re-runs
        the deterministic engine from the persisted key, digest-verifies
        the replay of the chunks already delivered, and yields the rest —
        the concatenation [chunks delivered before the fault ‖ resumed
        chunks] is bit-identical to an uninterrupted run (pinned by test).
        Resume is valid on ANY mesh (including a degraded one): the
        config digest deliberately excludes device layout.  Raises
        ValueError when the directory holds no checkpoint or one written
        by a different sampler config; a finished stream yields nothing.
        """
        from repro.api import stream as _stream

        step = _ckpt.latest_step(checkpoint_dir)
        if step is None:
            raise ValueError(
                f"no stream checkpoint under {checkpoint_dir!r}"
            )
        state = _stream.load_state(checkpoint_dir, step, self._key)
        chunk_edges = int(state["chunk_edges"])
        num_edges_i = int(state["num_edges"])
        num_edges = None if num_edges_i < 0 else num_edges_i
        mine = self._stream_config_digest(chunk_edges, num_edges)
        if not np.array_equal(mine, state["config_digest"]):
            raise ValueError(
                f"stream checkpoint in {checkpoint_dir!r} was written by a "
                "different sampler config (config digest mismatch); build "
                "the session from the original config to resume"
            )
        if int(state["done"]):
            return iter(())
        key = _stream.key_from_data(
            state["key_data"], int(state["key_typed"])
        )
        return _stream.emit(
            self._stream_raw(key, chunk_edges, num_edges=num_edges),
            checkpoint_dir,
            state,
            slots=lambda: getattr(self, "_last_run_slots", 0),
        )


class MAGMSampler(_Session):
    """Session for MAGM graphs (quilting, Algorithm 2 / Section 5).

    Construction resolves the config once: the attribute matrix (explicit
    ``F`` or Bernoulli(mu) rows from ``attribute_key``), the owned
    :class:`~repro.core.quilt.QuiltPlan` (``split=False``) or
    :class:`~repro.core.quilt.SplitPlan` (``split=True``), and the mesh.
    ``quilt.clear_plan_cache()`` never touches a session's plan.

    Examples
    --------
    >>> import numpy as np, jax
    >>> from repro.api import MAGMSampler, SamplerConfig
    >>> from repro.core import magm
    >>> theta = np.array([[0.3, 0.6], [0.6, 0.9]], dtype=np.float32)
    >>> params = magm.make_params(theta, mu=0.5, d=5)
    >>> sampler = MAGMSampler(SamplerConfig(params=params, num_nodes=24))
    >>> gs = sampler.sample(jax.random.PRNGKey(1))
    >>> gs.edges.shape[1], gs.edges.dtype, gs.n
    (2, dtype('int64'), 24)
    >>> gs.stats.B == sampler.plan.B and gs.num_edges == gs.stats.kept_edges
    True
    >>> chunks = list(sampler.sample_stream(jax.random.PRNGKey(1), chunk_edges=16))
    >>> all(c.shape[0] == 16 for c in chunks[:-1])  # fixed-shape chunks
    True
    >>> bool(np.array_equal(np.concatenate(chunks), gs.edges))  # bit-identical
    True
    """

    def __init__(self, config: SamplerConfig, *, key=None):
        super().__init__(config, key=key)
        params = config.params
        if not hasattr(params, "mu"):
            raise TypeError(
                "MAGMSampler needs magm.MAGMParams (with mu); for plain "
                "KPGM graphs use KPGMSampler"
            )
        self.F = magm.resolve_attributes(
            params,
            config.F,
            num_nodes=config.num_nodes,
            attribute_key=config.attribute_key,
        )
        self.n = int(self.F.shape[0])
        self._check_dtype(self.n)
        self.split_plan: Optional[quilt.SplitPlan] = None
        self.plan: Optional[quilt.QuiltPlan] = None
        if self.F.size == 0:
            return  # empty source: sample()/sample_stream() emit nothing
        if config.split:
            self.split_plan = quilt.build_split_plan(
                self.F, params, config.bprime
            )
            self.plan = self.split_plan.light_plan
        else:
            self.plan = quilt.build_quilt_plan(self.F, params.thetas)
        if (
            config.backend == "balldrop"
            and self.plan is not None
            and self.plan.bd_cost is None
        ):
            # fail at session build, not on the first sample() call
            raise ValueError(
                "backend='balldrop' needs the plan's ball-dropping "
                f"moments, unavailable at d={self.plan.d} (2^d exceeds "
                "kron.MOMENT_CAP); use backend='auto' or 'host'"
            )

    # -- single sample -------------------------------------------------

    def _run(self, key: jax.Array, *, num_samples: int = 1) -> quilt.QuiltRun:
        c = self.config
        return quilt.quilt_run(
            key,
            self.plan,
            num_samples=num_samples,
            max_rounds=c.max_rounds,
            oversample=c.oversample,
            backend=c.backend,
            use_kernel=c.use_kernel,
            mesh=self.mesh,
            exact_cells=c.exact_cells,
        )

    def _split_sample(self, key: jax.Array):
        """One Section-5 draw from the owned SplitPlan: light quilt + the
        device-resident heavy round, both keyed from ``key`` alone."""
        return quilt.split_run(
            key,
            self.split_plan,
            max_rounds=self.config.max_rounds,
            oversample=self.config.oversample,
            backend=self.config.backend,
            use_kernel=self.config.use_kernel,
            mesh=self.mesh,
        )

    @tracing.traced("sampler.sample", is_root=True)
    def sample(self, key: Optional[jax.Array] = None) -> GraphSample:
        """Draw one MAGM graph; bit-identical to the legacy free functions
        for the same key.  ``key=None`` consumes the session key stream."""
        key = self._next_key() if key is None else key
        if self.F.size == 0:
            return GraphSample(
                np.zeros((0, 2), dtype=self.config.dtype), 0,
                quilt.QuiltStats(0, 0, 0, 0, 0, 0, None), key,
            )
        if self.split_plan is not None:
            edges, stats = self._split_sample(key)
            return GraphSample(self._cast(edges), self.n, stats, key)
        run = self._run(key)
        edges = run.edges()
        return GraphSample(
            self._cast(edges), self.n, run.stats(edges.shape[0]), key
        )

    # -- streaming -----------------------------------------------------

    def _digest_parts(self) -> list:
        return [self.F, self.config.split, self.config.bprime]

    def _stream_raw(
        self, key, chunk_edges: int, num_edges: Optional[int] = None
    ) -> Iterator[np.ndarray]:
        """The undecorated chunk sequence (``num_edges`` unused here —
        the MAGM edge count is always the model's own draw)."""
        if self.F.size == 0:
            return
        if self.split_plan is not None:
            edges, _ = self._split_sample(key)
            chunks = dedup.rechunk_edges([edges], chunk_edges)
        else:
            run = self._run(key)
            self._last_run_slots = run.slots_per_graph
            chunks = run.iter_chunks(chunk_edges)
        for chunk in chunks:
            chaos.maybe_fail("stream.chunk")
            yield self._cast(chunk)

    def sample_stream(
        self,
        key: Optional[jax.Array] = None,
        *,
        chunk_edges: int = 1 << 16,
        checkpoint_dir: Optional[str] = None,
    ) -> Iterator[np.ndarray]:
        """Draw one graph, emitted as fixed-size deduped edge chunks.

        Yields ``(chunk_edges, 2)`` arrays (the final chunk may be
        shorter); their concatenation is bit-identical to
        ``sample(key).edges``.  On the quilt path the chunks are gathered
        window-by-window from the per-round device buffers, so the full
        edge list never materializes on the host — downstream consumers
        (writers, per-host partial lists) stream it instead.  The
        Section-5 split path materializes per-piece (its ER blocks are
        host-side) and only re-chunks.

        ``checkpoint_dir=`` persists a small StreamCheckpoint (atomically,
        via ``repro.dist.checkpoint``) after every delivered chunk; a run
        killed mid-stream then continues bit-identically from the cursor
        via :meth:`resume_stream` — on any mesh (see repro.api.stream).
        """
        key = self._next_key() if key is None else key
        with tracing.root("sampler.stream"):
            if checkpoint_dir is None:
                yield from self._stream_raw(key, chunk_edges)
            else:
                yield from self._checkpointed_stream(
                    key, chunk_edges, checkpoint_dir
                )

    # -- batching ------------------------------------------------------

    def sample_batch(
        self, num_graphs: int, key: Optional[jax.Array] = None
    ) -> List[GraphSample]:
        """Draw ``num_graphs`` independent MAGM graphs.

        On the device backend the whole batch shares the SAME fused
        per-round dispatches (kpgm_sample_many-style shared batching,
        generalised to quilting: sample s's block pair g' is graph
        ``s * B^2 + g'`` of the segmented dedup) and shards across the
        session mesh like any other run.  Host backend / split configs /
        over-budget batches fall back to a per-sample loop with
        ``fold_in(key, s)`` keys.
        """
        num_graphs = int(num_graphs)
        key = self._next_key() if key is None else key
        if num_graphs <= 0:
            return []
        if self.split_plan is None and self.F.size:
            try:
                run = self._run(key, num_samples=num_graphs)
            except quilt.DeviceBatchUnavailable:
                pass
            else:
                per = run.edges_per_sample()
                stats = run.stats_per_sample([e.shape[0] for e in per])
                # key=None: fused-batch members share one device run, so no
                # single-sample key reproduces them (GraphSample contract)
                return [
                    GraphSample(self._cast(e), self.n, st, None)
                    for e, st in zip(per, stats)
                ]
        return [
            self.sample(jax.random.fold_in(key, s))
            for s in range(num_graphs)
        ]


class KPGMSampler(_Session):
    """Session for plain KPGM graphs (Algorithm 1) with quilting parity.

    Runs the draw as the trivial B = 1 quilt over an identity
    config -> node lookup (:func:`repro.core.quilt.build_kpgm_plan`), so
    the fused device rounds, the on-device top-up, and bit-identical
    ``mesh=`` sharding all apply to KPGM too — the ``backend=`` / ``mesh=``
    parity the free functions never had.  For d past ~20 attributes (or
    ``backend="host"``) the classic host rejection loop is used instead.

    Examples
    --------
    >>> import numpy as np, jax
    >>> from repro.api import KPGMSampler, SamplerConfig
    >>> from repro.core import kpgm
    >>> theta = np.array([[0.3, 0.6], [0.6, 0.9]], dtype=np.float32)
    >>> sampler = KPGMSampler(SamplerConfig(params=kpgm.make_params(theta, d=6)))
    >>> gs = sampler.sample(jax.random.PRNGKey(0), num_edges=50)
    >>> gs.num_edges, gs.n, gs.stats.target_edges
    (50, 64, 50)
    >>> flat = gs.edges[:, 0] * 64 + gs.edges[:, 1]
    >>> int(np.unique(flat).size) == gs.num_edges  # deduped
    True
    """

    def __init__(self, config: SamplerConfig, *, key=None):
        super().__init__(config, key=key)
        params = config.params
        if hasattr(params, "mu"):
            raise TypeError(
                "KPGMSampler needs kpgm.KPGMParams; for attribute graphs "
                "use MAGMSampler"
            )
        self.params = params
        self.n = int(params.num_nodes)
        self._check_dtype(self.n)
        self.plan: Optional[quilt.QuiltPlan] = None
        if config.backend != "host" and self.n <= KPGM_PLAN_MAX_NODES:
            self.plan = quilt.build_kpgm_plan(params.thetas)
        elif config.backend in ("device", "balldrop"):
            # an explicit device request that cannot be honored must not
            # silently degrade to the host reference loop
            raise ValueError(
                f"backend={config.backend!r} needs n <= "
                f"{KPGM_PLAN_MAX_NODES} (got n={self.n}); use "
                "backend='auto' or 'host'"
            )

    def _run(
        self,
        key: jax.Array,
        *,
        num_samples: int = 1,
        targets=None,
    ) -> quilt.QuiltRun:
        c = self.config
        return quilt.quilt_run(
            key,
            self.plan,
            num_samples=num_samples,
            targets=targets,
            max_rounds=c.max_rounds,
            oversample=c.oversample,
            backend=c.backend,
            use_kernel=c.use_kernel,
            mesh=self.mesh,
            # KPGM sessions report/honor a drawn edge-count target
            # (KPGMStats.target_edges, num_edges=): the legacy ranked
            # rounds are that contract, so exact-cell stays off unless the
            # config explicitly opts in
            exact_cells=(
                False if c.exact_cells is None else c.exact_cells
            ),
        )

    def _host_sample(self, key, num_edges) -> GraphSample:
        edges = kpgm._kpgm_sample_host(
            key,
            self.params,
            max_rounds=self.config.max_rounds,
            oversample=self.config.oversample,
            num_edges=num_edges,
        )
        return GraphSample(self._cast(edges), self.n, None, key)

    def _engine_run(
        self, key: jax.Array, num_edges: Optional[int]
    ) -> Optional[quilt.QuiltRun]:
        """The one fallback decision: a QuiltRun via the engine, or None
        when the classic host loop must run instead (no plan at this d /
        backend, or an explicit num_edges over the device budget — the
        host loop honors the target, the engine's host path would not)."""
        if self.plan is None:
            self._no_plan_fallback()
            return None
        targets = None if num_edges is None else np.array([num_edges])
        try:
            return self._run(key, targets=targets)
        except quilt.DeviceBatchUnavailable as exc:
            self._host_fallback(str(exc))
            return None

    def _no_plan_fallback(self) -> None:
        if self.config.backend != "host":
            self._host_fallback(
                f"n={self.n} is over KPGM_PLAN_MAX_NODES="
                f"{KPGM_PLAN_MAX_NODES}, so there is no device plan"
            )

    def _host_fallback(self, why: str) -> None:
        quilt.fallback(
            quilt.DISPATCH_COUNTERS,
            "host_fallbacks",
            f"KPGM sampling on the host loop: {why}",
        )

    @tracing.traced("sampler.sample", is_root=True)
    def sample(
        self,
        key: Optional[jax.Array] = None,
        *,
        num_edges: Optional[int] = None,
    ) -> GraphSample:
        """Draw one KPGM graph (``num_edges`` overrides the X ~ N(m, m-v)
        draw); bit-identical across meshes for the same key."""
        key = self._next_key() if key is None else key
        run = self._engine_run(key, num_edges)
        if run is None:
            return self._host_sample(key, num_edges)
        edges = run.edges()
        # stats=None when the engine itself fell back to its host path: its
        # targets draw was never used there, so reporting it would fabricate
        # a target_edges the sample does not obey.  The balldrop host path
        # DOES honor its target, so its stats stay.
        stats = (
            None
            if run.host_edges is not None and run.sampler != "balldrop"
            else KPGMStats(
                num_nodes=self.n,
                target_edges=int(run.targets[0]),
                sampled_edges=int(edges.shape[0]),
            )
        )
        return GraphSample(self._cast(edges), self.n, stats, key)

    def _digest_parts(self) -> list:
        return [np.asarray(self.params.thetas), self.n]

    def _stream_raw(
        self, key, chunk_edges: int, num_edges: Optional[int] = None
    ) -> Iterator[np.ndarray]:
        run = self._engine_run(key, num_edges)
        if run is None:
            gs = self._host_sample(key, num_edges)
            chunks = dedup.rechunk_edges([gs.edges], chunk_edges)
        else:
            self._last_run_slots = run.slots_per_graph
            chunks = run.iter_chunks(chunk_edges)
        for chunk in chunks:
            chaos.maybe_fail("stream.chunk")
            yield self._cast(chunk)

    def sample_stream(
        self,
        key: Optional[jax.Array] = None,
        *,
        chunk_edges: int = 1 << 16,
        num_edges: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
    ) -> Iterator[np.ndarray]:
        """One KPGM graph as fixed-size chunks (see MAGMSampler; the
        ``checkpoint_dir=`` / :meth:`resume_stream` resume contract —
        including the ``num_edges`` override — is shared)."""
        key = self._next_key() if key is None else key
        with tracing.root("sampler.stream"):
            if checkpoint_dir is None:
                yield from self._stream_raw(key, chunk_edges, num_edges)
            else:
                yield from self._checkpointed_stream(
                    key, chunk_edges, checkpoint_dir, num_edges=num_edges
                )

    def sample_batch(
        self, num_graphs: int, key: Optional[jax.Array] = None
    ) -> List[GraphSample]:
        """``num_graphs`` independent KPGM graphs through SHARED fused
        device rounds (one segmented dedup over the whole batch), sharded
        across the session mesh; host fallback loops per sample."""
        num_graphs = int(num_graphs)
        key = self._next_key() if key is None else key
        if num_graphs <= 0:
            return []
        if self.plan is None:
            self._no_plan_fallback()
        else:
            try:
                run = self._run(key, num_samples=num_graphs)
            except quilt.DeviceBatchUnavailable as exc:
                self._host_fallback(str(exc))
            else:
                per = run.edges_per_sample()
                # key=None: see MAGMSampler.sample_batch — fused members
                # have no single-sample provenance key
                return [
                    GraphSample(
                        self._cast(e),
                        self.n,
                        KPGMStats(self.n, int(run.targets[s]), e.shape[0]),
                        None,
                    )
                    for s, e in enumerate(per)
                ]
        return [
            self._host_sample(jax.random.fold_in(key, s), None)
            for s in range(num_graphs)
        ]
