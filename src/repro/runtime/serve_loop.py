"""Serve-step factory + small continuous-batching serving loops.

``serve_step`` is the unit the decode dry-run shapes lower: one new token
for every sequence in the batch against a seq_len KV cache.  The
``Server`` driver adds slot management (requests join/leave the batch
between steps) for the serving example.

``CAMSearchServer`` is the CAM-side counterpart: a micro-batching
front-end over the store-once / search-many simulators.  Search requests
accumulate into fixed-size query batches (padded so the jit cache stays
warm at a single shape) and every step drives ONE fused batched search —
on the sharded simulator that is one grid pass per device plus the
cross-device merge, regardless of how many requests rode the batch.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig


def make_serve_step(cfg: ModelConfig, *, moe_mode: str = "tp",
                    greedy: bool = True):
    """serve_step(params, cache, inputs, pos) -> (next_token/logits, cache)."""

    def serve_step(params, cache, inputs: Dict, pos: jax.Array):
        logits, cache = models.forward_decode(params, cfg, inputs, pos,
                                              cache, moe_mode=moe_mode)
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
        return logits, cache

    return serve_step


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False


@dataclass
class Server:
    """Minimal continuous-batching server over a fixed slot batch."""
    cfg: ModelConfig
    params: Any
    batch_slots: int
    max_seq: int

    def __post_init__(self):
        self.cache = models.init_cache(self.cfg, self.batch_slots,
                                       self.max_seq)
        self.step_fn = jax.jit(make_serve_step(self.cfg))
        self.slot_req: List[Optional[Request]] = [None] * self.batch_slots
        self.slot_pos = np.zeros(self.batch_slots, np.int32)
        self.slot_next = np.zeros(self.batch_slots, np.int32)
        self.queue: List[Request] = []
        self.finished: List[Request] = []

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for i in range(self.batch_slots):
            if self.slot_req[i] is None and self.queue:
                req = self.queue.pop(0)
                self.slot_req[i] = req
                self.slot_pos[i] = 0
                self.slot_next[i] = req.prompt[0]

    def step(self) -> int:
        """One decode step across all active slots; returns #active."""
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        tokens = jnp.asarray(self.slot_next)
        pos = jnp.asarray(self.slot_pos)
        next_tok, self.cache = self.step_fn(
            self.params, self.cache, {"token": tokens}, pos)
        next_np = np.asarray(next_tok)
        for i in active:
            req = self.slot_req[i]
            p = int(self.slot_pos[i])
            if p + 1 < len(req.prompt):       # still consuming the prompt
                self.slot_next[i] = req.prompt[p + 1]
            else:
                tok = int(next_np[i])
                req.out.append(tok)
                self.slot_next[i] = tok
            self.slot_pos[i] = p + 1
            if (len(req.out) >= req.max_new
                    or self.slot_pos[i] >= self.max_seq - 1):
                req.done = True
                self.finished.append(req)
                self.slot_req[i] = None
        return len(active)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.finished


# ---------------------------------------------------------------------------
# CAM search serving
# ---------------------------------------------------------------------------
class QueueFull(RuntimeError):
    """Admission control: the server's bounded queue rejected a submit."""


@dataclass
class SearchRequest:
    """One in-memory-search request against the resident CAM store."""
    rid: int
    query: np.ndarray
    indices: Optional[np.ndarray] = None   # (k,) matched entries, -1 padded
    slo: str = "default"                   # latency-percentile bucket
    t_submit: float = 0.0                  # perf_counter seconds
    t_start: float = 0.0                   # its step's dispatch began
    t_done: float = 0.0
    step: int = -1                         # search step that served it
    # the fetched (exact/threshold) or assigned mask row, and for a
    # best-match answer the (padded_K, dtype) of the row to rebuild
    _row: Optional[np.ndarray] = field(default=None, init=False, repr=False)
    _rebuild: Optional[Tuple[int, Any]] = field(default=None, init=False,
                                                repr=False)

    @property
    def done(self) -> bool:
        return self.indices is not None

    @property
    def mask(self) -> Optional[np.ndarray]:
        """(padded_K,) match lines.  For a best-match store it is derived
        on each read from ``indices`` (1.0 at every index >= 0): the same
        values, dtype and shape as the row ``sim.query`` returns, with no
        per-request (padded_K,) array held.  Assigning it (``None``
        included) replaces whatever the request holds."""
        if self._rebuild is None:
            return self._row
        width, dtype = self._rebuild
        row = np.zeros(width, dtype)
        row[self.indices[self.indices >= 0]] = 1
        return row

    @mask.setter
    def mask(self, row: Optional[np.ndarray]) -> None:
        self._row, self._rebuild = row, None


@dataclass
class MutationRequest:
    """One store mutation riding the serve loop's continuous batch.

    ``kind`` is 'insert' / 'delete' / 'update'; consecutive requests of
    the same kind coalesce into ONE engine call per step.  After an
    insert completes, ``ids`` holds the caller-order row indices the new
    rows answer to in search results.
    """
    rid: int
    kind: str
    rows: Optional[np.ndarray] = None      # insert/update payload
    ids: Optional[np.ndarray] = None       # delete/update target ids
    slo: str = "mutation"
    t_submit: float = 0.0
    t_start: float = 0.0                   # its coalesced run began
    t_done: float = 0.0
    step: int = -1                         # search step index at the run
    done: bool = False


@dataclass
class CAMSearchServer:
    """Continuous-batching CAM serve engine (store once, serve *and
    mutate* many).

    ``sim`` is a ``CAMASim`` facade, ``FunctionalSimulator``, or
    ``ShardedCAMSimulator``; ``state`` its written — and, for the sharded
    backend, mesh-placed — store.  Search requests are answered in
    submission order in groups of up to ``batch`` queries; ``batch``
    defaults to the simulator config's ``sim.serve_batch``.  Per-batch
    C2C keys are folded from ``key`` by search-step index, matching the
    simulator's one-draw-per-search-cycle model.

    Mutations (``submit_insert`` / ``submit_delete`` / ``submit_update``)
    ride the same queue: each ``step`` first applies the queue's leading
    mutation requests (consecutive same-kind requests coalesce into ONE
    engine call) and then serves one search batch, so a mutation is
    visible to every search submitted after it.  Mutation programming
    keys fold from a separate lane (``fold_in(key, 'muta')`` then by
    mutation-step index), so the search key schedule is untouched by
    interleaved mutations and the whole trace replays deterministically.

    Admission control: ``max_queue`` bounds the pending queue (default
    ``sim.serve_queue``; 0 = unbounded) — submits beyond it raise
    ``QueueFull`` (backpressure).  Malformed requests (wrong query length
    or non-numeric dtype against the written store) are rejected at
    submit with a ``ValueError`` and never enter the queue; if a step
    fails anyway, its popped requests are restored to the queue front
    before the error propagates, so no request is ever silently lost.

    Every request carries an ``slo`` tag, the index of the search step
    that served it (``step``) and three timestamps: ``t_submit``,
    ``t_start`` (its step's dispatch, or its mutation run, began) and
    ``t_done``.  ``latency_stats()`` reports per-tag p50/p99 of the whole
    latency (submit to done) and of the queueing part (submit to start).
    ``counters`` holds cumulative counts, always kept: ``steps`` (search
    steps served), ``searches`` (search requests answered),
    ``fetch_bytes`` (bytes of the host arrays each step copied back) and
    ``mask_steps`` (search steps that copied the dense (Q, padded_K)
    mask: 0 on a best-match store, ``steps`` on an exact or threshold
    one).

    What a step copies to the host follows the store's match type
    (``config.app.match_type``).  A best-match mask holds nothing but the
    top-k indices scattered over the rows, so a best-match step copies
    only the (Q, k) indices and each request's ``mask`` is rebuilt from
    its own indices when read, equal to the row ``sim.query`` returns.
    An exact or threshold mask carries every match, not just the first
    k, so those steps copy the indices and the mask.

    Under ``jax.profiler`` a step records the host spans
    ``cam.serve.step`` (all of ``step()``, with ``step_num`` = the search
    step index, the same number as its requests' ``step``),
    ``cam.serve.mutate`` (each coalesced mutation run), and nested in the
    step, in order, ``cam.serve.dispatch`` (stack, pad, key fold and the
    enqueue of the search), ``cam.serve.wait`` (until the device has
    finished) and ``cam.serve.fetch`` (the device-to-host copy of the
    indices, and of the mask on an exact or threshold store, with
    ``fetch_bytes`` = the step's addition to ``counters["fetch_bytes"]``).
    With no profiler running they record nothing.

    ``autoscale=False`` (default) pads every step to exactly ``batch``
    queries, so each step hits one compiled search shape.  With
    ``autoscale=True`` the padded width is instead picked per step from
    the fixed power-of-two ladder {1, 2, 4, ..., batch} by queue depth —
    a mostly-idle server stops streaming the full serve_batch through the
    grid for a 1-request tail, at the cost of at most log2(batch)+1
    compiled shapes.  Request grouping and the fold_in(key, step) key
    schedule are identical to fixed-batch serving, so (absent C2C noise,
    whose per-cycle draw count is the padded width) answers are bit-exact
    either way.  Pad queries are excluded from the cascade's bank routing
    (the ``valid_count`` knob), so answers are also bit-exact across pad
    widths and queue depths when the search cascade is on.
    """
    sim: Any
    state: Any
    batch: Optional[int] = None
    key: Optional[jax.Array] = None
    autoscale: bool = False
    max_queue: Optional[int] = None

    def __post_init__(self):
        cfg = getattr(self.sim, "config", None)
        scfg = getattr(cfg, "sim", None)
        if self.batch is None:
            self.batch = getattr(scfg, "serve_batch", 32)
        if self.batch < 1:
            raise ValueError("batch must be >= 1")
        if self.max_queue is None:
            self.max_queue = getattr(scfg, "serve_queue", 0)
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        if self.key is None:
            self.key = jax.random.PRNGKey(0)
        # separate RNG lane for mutation programming noise, so interleaved
        # mutations never shift the search steps' fold_in(key, step) keys
        self._mut_key = jax.random.fold_in(self.key, 0x6D757461)  # 'muta'
        self.queue: List[Any] = []
        self.finished: List[Any] = []
        self._next_rid = 0
        self._steps = 0
        self.counters = {"steps": 0, "searches": 0, "fetch_bytes": 0,
                         "mask_steps": 0}
        app = getattr(cfg, "app", None)
        self._best_match = getattr(app, "match_type", None) == "best"
        self._mut_steps = 0
        self._ticks = 0      # reliability: serve steps = drift age units

    # ----------------------------------------------------------- submit
    def _admit(self, req):
        if self.max_queue and len(self.queue) >= self.max_queue:
            raise QueueFull(
                f"serve queue full ({self.max_queue} pending); retry "
                "after a step() drains it")
        self.queue.append(req)
        return req

    def _spec(self):
        return getattr(self.state, "spec", None)

    def _functional(self):
        """The innermost single-chip simulator (validation helpers)."""
        inner = getattr(self.sim, "backend", self.sim)
        return getattr(inner, "sim", inner)

    def _validate_query(self, q: np.ndarray):
        if not np.issubdtype(q.dtype, np.number):
            raise ValueError(
                f"query dtype {q.dtype} is not numeric — request rejected")
        spec = self._spec()
        if spec is not None and q.shape != (spec.N,):
            raise ValueError(
                f"query shape {q.shape} does not match the written "
                f"store's ({spec.N},) — request rejected")

    def _validate_rows(self, rows: np.ndarray):
        if not np.issubdtype(rows.dtype, np.number):
            raise ValueError(
                f"row dtype {rows.dtype} is not numeric — request rejected")
        sim = self._functional()
        if hasattr(sim, "_check_mutable"):
            sim._check_mutable()
            sim._check_rows(self.state, jnp.asarray(rows))

    def submit(self, query, slo: str = "default") -> SearchRequest:
        """Queue one search; rejects malformed queries at the door (a bad
        request must fail alone, not poison the batch it would ride)."""
        q = np.asarray(query)
        self._validate_query(q)
        req = SearchRequest(self._next_rid, q, slo=slo,
                            t_submit=time.perf_counter())
        self._next_rid += 1
        return self._admit(req)

    def submit_insert(self, rows, slo: str = "mutation") -> MutationRequest:
        """Queue an insert of ``rows`` (M, N[, 2]); ``req.ids`` holds the
        new rows' search ids once the request completes."""
        rows = np.asarray(rows)
        self._validate_rows(rows)
        req = MutationRequest(self._next_rid, "insert", rows=rows, slo=slo,
                              t_submit=time.perf_counter())
        self._next_rid += 1
        return self._admit(req)

    def submit_delete(self, ids, slo: str = "mutation") -> MutationRequest:
        req = MutationRequest(self._next_rid, "delete",
                              ids=np.asarray(ids).reshape(-1), slo=slo,
                              t_submit=time.perf_counter())
        self._next_rid += 1
        return self._admit(req)

    def submit_update(self, ids, rows,
                      slo: str = "mutation") -> MutationRequest:
        rows = np.asarray(rows)
        ids = np.asarray(ids).reshape(-1)
        self._validate_rows(rows)
        if ids.size != rows.shape[0]:
            raise ValueError(f"{ids.size} ids but {rows.shape[0]} rows")
        req = MutationRequest(self._next_rid, "update", rows=rows, ids=ids,
                              slo=slo, t_submit=time.perf_counter())
        self._next_rid += 1
        return self._admit(req)

    # ------------------------------------------------------------- step
    def _padded_width(self, n_reqs: int) -> int:
        """Step width: ``batch`` fixed, or the smallest ladder rung that
        fits the step's requests AND the sharded query-axis divisibility
        contract (padded width % (query_shards * c2c_tile) == 0)."""
        if not self.autoscale:
            return self.batch
        rung = 1
        while rung < n_reqs:
            rung <<= 1
        backend = getattr(self.sim, "backend", self.sim)
        mult = getattr(backend, "n_query", 1)
        if mult > 1:
            inner = getattr(backend, "sim", backend)
            if inner.config.device.variation in ("c2c", "both"):
                mult *= inner.c2c_query_tile
        while rung < self.batch and rung % mult:
            rung <<= 1
        return self.batch if rung > self.batch or rung % mult else rung

    def _apply_mutations(self, run: List[MutationRequest]) -> None:
        """One coalesced engine call for a same-kind mutation run."""
        kind = run[0].kind
        mkey = jax.random.fold_in(self._mut_key, self._mut_steps)
        if kind == "insert":
            rows = np.concatenate([r.rows for r in run])
            self.state, ids = self.sim.insert(self.state,
                                              jnp.asarray(rows), key=mkey)
            ids = np.asarray(ids)
            off = 0
            for r in run:
                r.ids = ids[off: off + r.rows.shape[0]]
                off += r.rows.shape[0]
        elif kind == "delete":
            self.state = self.sim.delete(
                self.state, np.concatenate([r.ids for r in run]))
        elif kind == "update":
            self.state = self.sim.update(
                self.state, np.concatenate([r.ids for r in run]),
                jnp.asarray(np.concatenate([r.rows for r in run])),
                key=mkey)
        else:
            raise ValueError(f"unknown mutation kind {kind!r}")
        self._mut_steps += 1
        now = time.perf_counter()
        for r in run:
            r.done, r.t_done = True, now
            self.finished.append(r)

    def _reliability_tick(self) -> None:
        """Advance the store's drift clock by one serve step and, every
        ``scrub_every`` steps, re-program the most-drifted rows through
        the mutation RNG lane — scrub keys fold exactly like coalesced
        mutations, so the search key schedule is untouched."""
        cfg = getattr(self.sim, "config", None)
        rel = getattr(cfg, "reliability", None)
        if (rel is None or not rel.enabled
                or getattr(self.state, "rel", None) is None
                or not hasattr(self.sim, "age_tick")):
            return
        self.state = self.sim.age_tick(self.state)
        self._ticks += 1
        if rel.scrub_every > 0 and self._ticks % rel.scrub_every == 0:
            mkey = jax.random.fold_in(self._mut_key, self._mut_steps)
            self.state = self.sim.scrub(self.state, key=mkey)
            self._mut_steps += 1

    def step(self) -> int:
        """Apply the queue's leading mutation runs, then serve one search
        batch; returns #requests completed.  A failing unit restores its
        popped requests to the queue front before re-raising.  With
        reliability enabled the store ages (and is scrubbed) every step,
        queue empty or not — drift does not wait for traffic."""
        with jax.profiler.StepTraceAnnotation("cam.serve.step",
                                              step_num=self._steps):
            return self._step()

    def _step(self) -> int:
        self._reliability_tick()
        if not self.queue:
            return 0
        served = 0
        # continuous batching: drain leading mutations first so every
        # search in this step sees the store state its submission order
        # implies
        while self.queue and isinstance(self.queue[0], MutationRequest):
            run = [self.queue.pop(0)]
            while (self.queue
                   and isinstance(self.queue[0], MutationRequest)
                   and self.queue[0].kind == run[0].kind):
                run.append(self.queue.pop(0))
            t_start = time.perf_counter()
            for r in run:
                r.step, r.t_start = self._steps, t_start
            try:
                with jax.profiler.TraceAnnotation("cam.serve.mutate"):
                    self._apply_mutations(run)
            except Exception:
                self.queue[:0] = run
                raise
            served += len(run)
        n = 0
        while (n < len(self.queue) and n < self.batch
               and isinstance(self.queue[n], SearchRequest)):
            n += 1
        if n == 0:
            return served
        reqs = self.queue[:n]
        del self.queue[:n]
        with jax.profiler.TraceAnnotation("cam.serve.dispatch"):
            t_start = time.perf_counter()
            try:
                qs = np.stack([r.query for r in reqs]).astype(np.float32)
                pad = self._padded_width(len(reqs)) - len(reqs)
                if pad:
                    qs = np.concatenate(
                        [qs, np.zeros((pad, qs.shape[1]), qs.dtype)])
                step_key = jax.random.fold_in(self.key, self._steps)
                # pad queries are real rows of the padded batch but NOT
                # real requests: valid_count keeps them out of the
                # cascade's shared bank routing
                idx, mask = self.sim.query(self.state, jnp.asarray(qs),
                                           key=step_key,
                                           valid_count=len(reqs))
            except Exception:
                self.queue[:0] = reqs
                raise
        step = self._steps
        self._steps += 1
        with jax.profiler.TraceAnnotation("cam.serve.wait"):
            jax.block_until_ready((idx, mask))
        # a best-match mask is the indices scattered over padded_K rows:
        # copy the indices alone and let each request rebuild its row
        fetched = (idx,) if self._best_match else (idx, mask)
        fetch_bytes = sum(a.nbytes for a in fetched)
        with jax.profiler.TraceAnnotation("cam.serve.fetch",
                                          fetch_bytes=fetch_bytes):
            host = [np.asarray(a) for a in fetched]
        self.counters["steps"] += 1
        self.counters["searches"] += len(reqs)
        self.counters["fetch_bytes"] += fetch_bytes
        self.counters["mask_steps"] += int(not self._best_match)
        rebuild = (mask.shape[-1], mask.dtype) if self._best_match else None
        now = time.perf_counter()
        for i, req in enumerate(reqs):
            req.indices = host[0][i]
            req.mask = None if rebuild else host[1][i]
            req._rebuild = rebuild
            req.step, req.t_start, req.t_done = step, t_start, now
            self.finished.append(req)
        return served + len(reqs)

    def run(self, max_steps: int = 10_000) -> List[Any]:
        steps = 0
        while self.queue and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # ------------------------------------------------------------ stats
    def latency_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-SLO-tag request latency percentiles over finished requests,
        in microseconds: ``{tag: {'n', 'p50_us', 'p99_us', 'queue_p50_us',
        'queue_p99_us'}}``.  ``p*_us`` is submit to done (the whole
        latency), ``queue_p*_us`` submit to the start of the step (or
        mutation run) that served the request: the wait in the queue, which
        tells queueing apart from service."""
        by: Dict[str, List[Tuple[float, float]]] = {}
        for r in self.finished:
            by.setdefault(r.slo, []).append(
                ((r.t_done - r.t_submit) * 1e6,
                 (r.t_start - r.t_submit) * 1e6))
        out = {}
        for slo, v in by.items():
            total, queued = np.asarray(v).T
            out[slo] = {"n": float(len(v)),
                        "p50_us": float(np.percentile(total, 50)),
                        "p99_us": float(np.percentile(total, 99)),
                        "queue_p50_us": float(np.percentile(queued, 50)),
                        "queue_p99_us": float(np.percentile(queued, 99))}
        return out
