"""Functional simulator (paper Fig. 1b): write simulation + query simulation.

Write:  stored data --quantize--> codes --map--> subarray grid --D2D-->
        CAM data (what the physical cells actually hold).
Query:  query data --quantize(shared scale)--> segments; per query cycle the
        CAM data sees fresh C2C noise; each subarray searches in parallel;
        merge produces application-level match indices.

Everything is jit-able.  Queries follow the CAM usage model — store once,
search many — as ONE fused batched search: the whole (Q, nh, C) segment
block is evaluated against the resident grid in a single
``subarray_query_batched`` call (on the kernel path that is one Pallas pass
that streams each stored tile from HBM once for the entire batch, with the
sense amplifier fused in), then one batched merge.  The per-query vmap of
the old pipeline — which re-streamed the full (nv, nh, R, C) grid once per
query and re-traced the sense/merge stages Q times — is gone.

C2C variation is the one place a per-cycle axis survives: each search cycle
must see fresh array noise, so the batch is processed as a vmap over
Q-tiles of ``c2c_query_tile`` cycles, drawing one noise instance per
tile (a tile models the queries issued within one search cycle).  The
default tile of 1 reproduces the historical per-query noise draw
bit-exactly; larger tiles trade noise granularity for amortizing the noisy
grid construction and search across the tile.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mapping, merge, prefilter, quantize, reliability, subarray, \
    variation
from .config import CAMConfig
from .reliability import ReliabilityState
from .results import SearchResult


def resolve_sim_overrides(config: CAMConfig, **overrides) -> CAMConfig:
    """Fold deprecated constructor kwargs into ``config.sim``.

    ``None`` means "not given" (take the config value); anything else is a
    legacy override — honored for one release with a DeprecationWarning,
    validated by ``SimConfig`` itself.
    """
    given = {k: v for k, v in overrides.items() if v is not None}
    if not given:
        return config
    warnings.warn(
        f"constructor kwargs {sorted(given)} are deprecated; set them in "
        "the config's sim section (SimConfig) instead",
        DeprecationWarning, stacklevel=3)
    return config.replace(sim=given)


@dataclass
class CAMState:
    """State produced by write simulation (a pytree).

    The last three fields exist only when the search cascade is configured
    (``sim.prefilter != 'off'``): bit-packed per-row signatures for the
    stage-1 bank prefilter, their binarization threshold, and — for the
    'ivf' prefilter — the clustered placement permutation
    (``placed[i] = orig[perm[i]]``) that the query path inverts so returned
    indices always refer to the caller's original row order.
    """
    grid: jax.Array          # (nv, nh, R, C) noisy stored codes
    lo: jax.Array            # quantization range (shared with queries)
    hi: jax.Array
    spec: mapping.GridSpec   # static partition spec
    col_valid: jax.Array     # (nh, C)
    row_valid: jax.Array     # (nv, R)
    sigs: Optional[jax.Array] = None      # (nv, R, W) uint32 signatures
    sig_thr: Optional[jax.Array] = None   # scalar binarization threshold
    perm: Optional[jax.Array] = None      # (padded_K,) placement perm
    codes: Optional[jax.Array] = None     # (nv, nh, R, C[, 2]) CLEAN placed
                                          # codes (pre-D2D) — the mutable
                                          # store's source of truth, so
                                          # ``compact`` can re-place live
                                          # rows bit-identically to a
                                          # fresh write
    rel: Optional[ReliabilityState] = None  # reliability bookkeeping (age,
                                            # wear, retired/failed flags);
                                            # only when config.reliability
                                            # is enabled


jax.tree_util.register_pytree_node(
    CAMState,
    lambda s: ((s.grid, s.lo, s.hi, s.col_valid, s.row_valid, s.sigs,
                s.sig_thr, s.perm, s.codes, s.rel), s.spec),
    lambda spec, leaves: CAMState(leaves[0], leaves[1], leaves[2], spec,
                                  leaves[3], leaves[4], leaves[5],
                                  leaves[6], leaves[7], leaves[8],
                                  leaves[9]),
)


def _replace_state(state: CAMState, **kw) -> CAMState:
    """CAMState copy with the given fields replaced."""
    fields = dict(grid=state.grid, lo=state.lo, hi=state.hi,
                  spec=state.spec, col_valid=state.col_valid,
                  row_valid=state.row_valid, sigs=state.sigs,
                  sig_thr=state.sig_thr, perm=state.perm,
                  codes=state.codes, rel=state.rel)
    fields.update(kw)
    return CAMState(**fields)


class FunctionalSimulator:
    """Automated in-memory search simulation (accuracy path of CAMASim).

    Execution knobs come from ``config.sim`` (use_kernel, c2c_query_tile,
    c2c_fold); the constructor kwargs of the same names are deprecated
    overrides kept for one release.
    """

    def __init__(self, config: CAMConfig,
                 use_kernel: Optional[bool] = None,
                 c2c_query_tile: Optional[int] = None,
                 c2c_fold: Optional[str] = None):
        config = resolve_sim_overrides(config, use_kernel=use_kernel,
                                       c2c_query_tile=c2c_query_tile,
                                       c2c_fold=c2c_fold)
        config.validate()
        self.config = config
        self.use_kernel = config.sim.use_kernel
        self.c2c_query_tile = config.sim.c2c_query_tile
        self.q_tile = config.sim.q_tile
        self.pipeline = config.sim.pipeline
        # Narrow-int / bit-packed kernel fast paths need the stored grid to
        # hold exact small integers: quantized point codes (data_bits wide)
        # with no device variation folded in.  ACAM range grids and analog
        # noise keep the float path.  0 disables; else the code width in
        # bits (threaded to kernels.ops as ``int_codes``).
        app, dev, circ = config.app, config.device, config.circuit
        # (reliability faults/drift turn the sensed grid into floats, so
        # the exact-integer fast path is also gated on reliability off)
        self.int_codes = (
            app.data_bits
            if (self.pipeline and app.data_bits and app.data_bits <= 7
                and app.distance in ("hamming", "l1", "l2", "dot")
                and dev.variation == "none" and circ.cell_type != "acam"
                and not config.reliability.enabled)
            else 0)
        # 'grid': one normal draw over the whole (nv, nh, R, C) grid per
        # cycle (the historical single-device draw).  'bank': one draw per
        # nv bank from fold_in(cycle_key, bank index) — bit-identical no
        # matter how the nv axis is split across devices, so the sharded
        # simulator (core.sharded) always runs its reference in this mode.
        self.c2c_fold = config.sim.c2c_fold
        # measured-model overrides: fitted constants from
        # benchmarks/calibrate_kernel_model.py, pinned in the config
        if (config.sim.step_overhead_s is not None
                or config.sim.bcast_budget_bytes is not None):
            from repro.kernels.cam_search import set_kernel_model
            set_kernel_model(
                step_overhead_s=config.sim.step_overhead_s,
                bcast_budget_bytes=config.sim.bcast_budget_bytes)
        self._arch = None          # perf.ArchSpecifics, set by write()/plan()

    # ------------------------------------------------------------- perf
    def plan(self, entries: int, dims: int):
        """Estimator-only planning: derive ``ArchSpecifics`` from shapes
        alone so ``eval_perf`` works *before* (or without) ``write``."""
        from .perf import estimate_arch
        self._arch = estimate_arch(self.config, entries, dims)
        return self._arch

    def arch_specifics(self):
        if self._arch is None:
            raise RuntimeError(
                "call write() or plan() before querying arch specifics")
        return self._arch

    def eval_perf(self, n_queries: int = 1, include_write: bool = False,
                  ops_per_query: int = 1,
                  clock_hz: Optional[float] = None,
                  mesh=None, queries_per_batch: int = 1,
                  searched_fraction: Optional[float] = None,
                  prefilter_bits: Optional[int] = None):
        """Hardware performance prediction for the written (or planned)
        store; see ``perf.perf_report`` for the report shape.  The cascade
        knobs default to what ``config.sim`` implies (``cascade_billing``);
        pass them explicitly to sweep the routing budget pre-write."""
        from .perf import perf_report
        return perf_report(self.config, self.arch_specifics(), mesh=mesh,
                           n_queries=n_queries, include_write=include_write,
                           ops_per_query=ops_per_query, clock_hz=clock_hz,
                           queries_per_batch=queries_per_batch,
                           searched_fraction=searched_fraction,
                           prefilter_bits=prefilter_bits)

    # ------------------------------------------------------------- write
    def write(self, stored: jax.Array, key: Optional[jax.Array] = None
              ) -> CAMState:
        """Write simulation: quantize + map + D2D variation.

        ACAM accepts ``stored`` of shape (K, N, 2) holding per-cell
        [lo, hi] ranges (X-TIME-style); other cells take (K, N) values."""
        cfg = self.config
        if stored.ndim == 3:
            assert cfg.circuit.cell_type == "acam",                 "range stores need cell_type='acam'"
            if cfg.app.distance != "range":
                # fail loudly at write time: the jnp path used to compute
                # range violations silently mislabeled as the configured
                # distance, while the kernel path rejected the combination
                # deep in dispatch
                raise ValueError(
                    "ACAM [lo, hi] range stores require distance='range' "
                    f"(got {cfg.app.distance!r})")
        elif cfg.app.distance == "range":
            raise ValueError(
                "distance='range' requires a (K, N, 2) range store "
                f"(got shape {tuple(stored.shape)})")
        K, N = stored.shape[:2]
        self.plan(K, N)            # record arch specifics for eval_perf
        spec = mapping.grid_spec(K, N, cfg.circuit.rows, cfg.circuit.cols,
                                 cfg.sim.capacity)
        key = key if key is not None else jax.random.PRNGKey(0)
        return self._heal_failed(self._write_jit(stored, spec, key), key)

    @partial(jax.jit, static_argnums=(0, 2))
    def _write_jit(self, stored, spec, key):
        cfg = self.config
        if stored.ndim == 3:        # ACAM ranges: no quantization
            codes, lo, hi = stored, jnp.zeros(()), jnp.ones(())
        else:
            codes, lo, hi = quantize.quantize_for_cell(
                stored, cfg.circuit.cell_type, cfg.app.data_bits)
        return self._place_codes(codes, lo, hi, spec, key)

    def _place_codes(self, codes, lo, hi, spec, key):
        """Place already-quantized code rows: prefilter signatures /
        clustered permutation, partition, D2D programming noise.  Shared
        by ``write`` (fresh data) and ``compact`` (the live rows' resident
        clean codes with the store's frozen scale)."""
        cfg = self.config
        sigs = sig_thr = perm = None
        if cfg.sim.prefilter != "off":
            cvals = prefilter.signature_values(codes)
            if cfg.sim.prefilter == "ivf":
                # clustered placement: reorder rows so similar entries
                # colocate in the same nv bank; the query path maps
                # indices back through perm so callers never see it
                perm = mapping.placement_perm(cvals, spec)
                codes = jnp.take(codes, perm[:spec.K], axis=0)
                cvals = jnp.take(cvals, perm[:spec.K], axis=0)
            # signatures come from the clean placed codes, BEFORE the D2D
            # programming noise below: stage 1 models a separate 1-bit
            # TCAM slab programmed from the same source data
            sig_thr = prefilter.signature_threshold(
                cvals, cfg.circuit.cell_type, cfg.app.data_bits)
            sigs = prefilter.row_signatures(cvals, sig_thr, spec,
                                            cfg.sim.signature_bits)
        clean = mapping.partition_stored(codes, spec)
        relcfg = cfg.reliability
        rel = None
        if relcfg.enabled:
            # verified programming over every slot: attempt 0 draws the
            # legacy per-slot noise, so with verify/faults all zero the
            # grid is bit-identical to apply_d2d_rowfold
            nv, nh, R, C = clean.shape[:4]
            extra = clean.shape[4:]
            rows = jnp.moveaxis(clean, 2, 1).reshape(nv * R, nh, C, *extra)
            slots = jnp.arange(nv * R, dtype=jnp.int32)
            live = slots < spec.K
            prog, attempts, ok = reliability.program_rows_verified(
                rows, jnp.zeros_like(rows), slots, dev=cfg.device,
                rel=relcfg, bits=cfg.app.data_bits, key=key,
                col_valid=mapping.col_valid_mask(spec),
                code_hi=reliability.code_ceiling(cfg), R=R, live=live)
            grid = jnp.moveaxis(prog.reshape(nv, R, nh, C, *extra), 1, 2)
            rel = ReliabilityState(
                age=jnp.zeros((), jnp.int32),
                prog_age=jnp.zeros((nv, R), jnp.int32),
                writes=jnp.where(live, attempts, 0).reshape(nv, R),
                retired=jnp.zeros((nv, R), bool),
                failed=(~ok & live).reshape(nv, R))
        elif cfg.sim.d2d_fold == "row":
            grid = variation.apply_d2d_rowfold(clean, cfg.device,
                                               cfg.app.data_bits, key)
        else:
            grid = variation.apply_d2d(clean, cfg.device, cfg.app.data_bits,
                                       key)
        return CAMState(grid=grid, lo=lo, hi=hi, spec=spec,
                        col_valid=mapping.col_valid_mask(spec),
                        row_valid=mapping.row_valid_mask(spec),
                        sigs=sigs, sig_thr=sig_thr, perm=perm, codes=clean,
                        rel=rel)

    # --------------------------------------------------------- mutations
    # Online edits of the resident store (free-list allocation over the
    # existing row_valid masks): deletes flip validity bits, inserts claim
    # free row slots, updates re-program live slots in place.  Grid shape,
    # signatures block, and placement permutation never change — only the
    # touched rows' cells/signatures are re-derived — so a sharded store
    # mutates without a re-shard.
    def _check_mutable(self):
        cfg = self.config
        if (cfg.device.variation in ("d2d", "both")
                and cfg.sim.d2d_fold != "row"):
            # the grid-level D2D draw cannot be reproduced for a single
            # row, so incremental writes could never match a fresh write
            raise ValueError(
                "online insert/update with D2D variation requires "
                "sim.d2d_fold='row' (per-row-slot RNG fold)")

    def _check_rows(self, state: CAMState, rows: jax.Array):
        cfg = self.config
        want_range = cfg.app.distance == "range"
        if want_range and (rows.ndim != 3 or rows.shape[-1] != 2):
            raise ValueError(
                "range stores take (M, N, 2) [lo, hi] rows "
                f"(got shape {tuple(rows.shape)})")
        if not want_range and rows.ndim != 2:
            raise ValueError(
                f"expected (M, N) rows (got shape {tuple(rows.shape)})")
        if rows.shape[1] != state.spec.N:
            raise ValueError(
                f"row width {rows.shape[1]} != stored dims {state.spec.N}")

    def free_slots(self, state: CAMState) -> np.ndarray:
        """Global row slots currently free.  Only slots below
        ``spec.padded_K`` count — a sharded state's all-invalid padding
        banks are not allocatable capacity.  Without reliability the
        order is ascending; with it the allocator is wear-aware: retired
        slots never come back, and the least-worn (fewest programming
        pulses) free slot is claimed first (ascending slot id breaks
        ties, so an unworn store allocates exactly like the legacy
        free list)."""
        padded_K = state.spec.padded_K
        rv = np.asarray(state.row_valid).reshape(-1)[:padded_K]
        free = np.where(rv == 0)[0]
        if state.rel is not None and self.config.reliability.enabled:
            retired = np.asarray(state.rel.retired).reshape(-1)[:padded_K]
            free = free[~retired[free]]
            writes = np.asarray(state.rel.writes).reshape(-1)[:padded_K]
            free = free[np.argsort(writes[free], kind="stable")]
        return free

    def _slots_of(self, state: CAMState, ids) -> jax.Array:
        """Map caller-order row ids to global row slots (inverse of the
        placement permutation); every id must name a live row."""
        ids = np.asarray(ids).reshape(-1)
        padded_K = state.spec.padded_K
        if ids.size and (ids.min() < 0 or ids.max() >= padded_K):
            raise ValueError(f"row ids must be in [0, {padded_K})")
        if state.perm is not None:
            inv = np.empty(padded_K, np.int64)
            inv[np.asarray(state.perm)] = np.arange(padded_K)
            slots = inv[ids]
        else:
            slots = ids
        rv = np.asarray(state.row_valid).reshape(-1)
        dead = ids[rv[slots] == 0]
        if dead.size:
            raise ValueError(f"row ids {dead.tolist()} are not live rows")
        return jnp.asarray(slots, jnp.int32)

    def insert(self, state: CAMState, rows: jax.Array,
               key: Optional[jax.Array] = None
               ) -> Tuple[CAMState, jax.Array]:
        """Claim free row slots for ``rows`` (M, N[, 2]) and program them.

        Returns ``(new_state, ids)`` where ``ids`` (M,) are the caller-order
        row indices the inserted rows will report in search results.  With
        ``sim.d2d_fold='row'`` the programmed cells (noise included) are
        bit-identical to the slots' rows under a fresh ``write`` with the
        same key.  Raises when the store lacks free slots — size head-room
        with ``sim.capacity`` (``perf_report``'s inserts/sec figure prices
        it)."""
        rows = jnp.asarray(rows)
        self._check_mutable()
        self._check_rows(state, rows)
        free = self.free_slots(state)
        if rows.shape[0] > free.size:
            raise ValueError(
                f"store full: {rows.shape[0]} inserts but only {free.size} "
                "free slots — delete rows, compact(), or re-write with a "
                "larger sim.capacity")
        slots = jnp.asarray(free[:rows.shape[0]], jnp.int32)
        key = key if key is not None else jax.random.PRNGKey(0)
        new_state = self._heal_failed(
            self._write_rows(state, rows, slots, key, True), key)
        # ids come from the pre-heal perm: healing swaps a failed slot's
        # perm entry along with its data, so the returned NAME stays
        # valid wherever the row physically lands
        ids = (jnp.take(state.perm, slots) if state.perm is not None
               else slots)
        return new_state, ids

    def delete(self, state: CAMState, ids) -> CAMState:
        """Flip the validity bits of live rows ``ids`` (caller order).
        Deleted rows never match again (search and the bank prefilter both
        mask on ``row_valid``) and their slots return to the free list."""
        slots = self._slots_of(state, ids)
        v, r = slots // state.spec.R, slots % state.spec.R
        return _replace_state(state,
                              row_valid=state.row_valid.at[v, r].set(0.0))

    def update(self, state: CAMState, ids, rows: jax.Array,
               key: Optional[jax.Array] = None) -> CAMState:
        """Re-program live rows ``ids`` in place with new ``rows`` data
        (fresh programming noise from ``key``'s per-slot fold)."""
        rows = jnp.asarray(rows)
        self._check_mutable()
        self._check_rows(state, rows)
        slots = self._slots_of(state, ids)
        if slots.shape[0] != rows.shape[0]:
            raise ValueError(
                f"{slots.shape[0]} ids but {rows.shape[0]} rows")
        key = key if key is not None else jax.random.PRNGKey(0)
        return self._heal_failed(
            self._write_rows(state, rows, slots, key, False), key)

    @partial(jax.jit, static_argnums=(0, 5, 6))
    def _write_rows(self, state: CAMState, rows, slots, key, set_valid,
                    is_codes=False):
        """Program ``rows`` (M, N[, 2]) into global slots ``slots`` (M,):
        quantize with the store's frozen scale, scatter clean codes +
        per-slot-folded D2D noise, refresh only the touched rows'
        signatures.  ``is_codes`` skips quantization for rows already in
        the code domain (scrub and spare-heal re-program resident clean
        codes).  With reliability enabled, programming runs write-verify
        (``reliability.program_rows_verified``) and updates the wear
        counters / failed flags."""
        cfg = self.config
        bits = cfg.app.data_bits
        spec = state.spec
        if is_codes or rows.ndim == 3:   # ACAM ranges: no quantization
            codes = rows
        else:
            codes, _, _ = quantize.quantize_for_cell(
                rows, cfg.circuit.cell_type, bits, state.lo, state.hi)
        segs = mapping.partition_rows(codes, spec)       # (M, nh, C[, 2])
        v, r = slots // spec.R, slots % spec.R
        rel = state.rel
        relcfg = cfg.reliability
        if relcfg.enabled and rel is not None:
            old = state.grid[v, :, r]                    # (M, nh, C[, 2])
            worn = (rel.writes[v, r] >= relcfg.endurance_writes
                    if relcfg.endurance_writes > 0
                    else jnp.zeros(slots.shape, bool))
            noisy, attempts, ok = reliability.program_rows_verified(
                segs, old, slots, dev=cfg.device, rel=relcfg, bits=bits,
                key=key, col_valid=state.col_valid,
                code_hi=reliability.code_ceiling(cfg), R=spec.R,
                worn=worn)
            rel = ReliabilityState(
                age=rel.age,
                # worn cells never actually re-program, so their drift
                # clock keeps running from the last real program
                prog_age=rel.prog_age.at[v, r].set(
                    jnp.where(worn, rel.prog_age[v, r], rel.age)),
                writes=rel.writes.at[v, r].add(attempts),
                retired=rel.retired,
                failed=rel.failed.at[v, r].set(~ok))
        else:
            noisy = variation.apply_d2d_slots(segs, cfg.device, bits, key,
                                              slots)
        grid = state.grid.at[v, :, r].set(noisy)
        clean = (state.codes.at[v, :, r].set(segs)
                 if state.codes is not None else None)
        row_valid = (state.row_valid.at[v, r].set(1.0) if set_valid
                     else state.row_valid)
        sigs = state.sigs
        if sigs is not None:
            cvals = prefilter.signature_values(codes)
            sigs = prefilter.update_row_signatures(
                sigs, cvals, state.sig_thr, spec, cfg.sim.signature_bits,
                slots)
        return CAMState(grid=grid, lo=state.lo, hi=state.hi, spec=spec,
                        col_valid=state.col_valid, row_valid=row_valid,
                        sigs=sigs, sig_thr=state.sig_thr, perm=state.perm,
                        codes=clean, rel=rel)

    def compact(self, state: CAMState,
                key: Optional[jax.Array] = None) -> CAMState:
        """Re-place the live rows as a fresh store: gather their clean
        codes in caller order and re-run the full placement pipeline
        (signature threshold, IVF clustering, partition, D2D noise) with
        the store's frozen quantization scale.  Bit-identical to a fresh
        ``write`` of the live rows whenever that write derives the same
        scale (and the same ``key`` is used); the grid shrinks back to
        ``grid_spec(K_live, ..., sim.capacity)``.

        After compaction row ids are renumbered 0..K_live-1 in the old
        caller order (the usual consequence of compacting a free list)."""
        if state.codes is None:
            raise ValueError("state has no resident clean codes "
                             "(written by an older version?) — re-write "
                             "the store to enable compact()")
        cfg = self.config
        spec = state.spec
        rv = np.asarray(state.row_valid).reshape(-1)[:spec.padded_K]
        live = np.where(rv > 0)[0]
        if live.size == 0:
            raise ValueError("cannot compact an empty store")
        ids = (np.asarray(state.perm)[live] if state.perm is not None
               else live)
        slots = jnp.asarray(live[np.argsort(ids, kind="stable")], jnp.int32)
        rows = self._gather_code_rows(state, slots)
        new_spec = mapping.grid_spec(int(live.size), spec.N, spec.R, spec.C,
                                     cfg.sim.capacity)
        self.plan(int(live.size), spec.N)
        key = key if key is not None else jax.random.PRNGKey(0)
        # reliability note: compaction models a re-deployment onto a
        # fresh slab, so wear/age counters reset with the placement
        return self._heal_failed(
            self._place_jit(rows, state.lo, state.hi, new_spec, key), key)

    @partial(jax.jit, static_argnums=(0,))
    def _gather_code_rows(self, state: CAMState, slots) -> jax.Array:
        """Un-partition the clean codes of the given slots: (M, N[, 2])."""
        spec = state.spec
        c = state.codes
        extra = c.shape[4:]
        rows = jnp.moveaxis(c, 2, 1).reshape(
            c.shape[0] * spec.R, spec.nh * spec.C, *extra)
        return jnp.take(rows, slots, axis=0)[:, :spec.N]

    @partial(jax.jit, static_argnums=(0, 4))
    def _place_jit(self, codes, lo, hi, spec, key):
        return self._place_codes(codes, lo, hi, spec, key)

    # ------------------------------------------------------- reliability
    def _heal_failed(self, state: CAMState, key) -> CAMState:
        """Spare-row healing: remap live rows that failed write-verify
        (dead/stuck/worn slots) onto same-bank spare slots, re-programming
        their resident clean codes there.  The placement permutation
        swaps along with the data, so callers' row ids never change.
        Rounds repeat while verify still fails and spares remain (a spare
        can itself be dead — the next round retires it and tries the
        next-least-worn one); a row whose bank runs out of spare budget
        stays flagged ``failed`` in place (degraded, honestly reported)."""
        relcfg = self.config.reliability
        if (state.rel is None or not relcfg.enabled
                or relcfg.spares_per_bank < 1 or state.codes is None):
            return state
        # each round retires at least one slot, so this terminates; the
        # explicit bound is a backstop against pathological fault maps
        for _ in range(8):
            healed = self._heal_round(state, key)
            if healed is None:
                break
            state = healed
        return state

    def _heal_round(self, state: CAMState, key):
        relcfg = self.config.reliability
        spec = state.spec
        padded_K = spec.padded_K
        rv = np.asarray(state.row_valid).reshape(-1)[:padded_K]
        rel = state.rel
        src, dst = reliability.plan_spares(
            rv,
            np.asarray(rel.failed).reshape(-1)[:padded_K],
            np.asarray(rel.retired).reshape(-1)[:padded_K],
            np.asarray(rel.writes).reshape(-1)[:padded_K],
            spec.R, relcfg.spares_per_bank)
        if not src:
            return None
        src_j = jnp.asarray(src, jnp.int32)
        dst_j = jnp.asarray(dst, jnp.int32)
        rows = self._gather_code_rows(state, src_j)
        # the spare slots draw the same per-slot noise a direct write
        # with this key would, keeping insert/fresh-write parity intact
        state = self._write_rows(state, rows, dst_j, key, True, True)
        vs, rs = src_j // spec.R, src_j % spec.R
        rel = state.rel
        rel = ReliabilityState(
            age=rel.age, prog_age=rel.prog_age, writes=rel.writes,
            retired=rel.retired.at[vs, rs].set(True),
            failed=rel.failed.at[vs, rs].set(False))
        perm = (np.asarray(state.perm).copy() if state.perm is not None
                else np.arange(padded_K))
        perm[np.asarray(dst)], perm[np.asarray(src)] = \
            perm[np.asarray(src)], perm[np.asarray(dst)].copy()
        return _replace_state(
            state,
            row_valid=state.row_valid.at[vs, rs].set(0.0),
            perm=jnp.asarray(perm, jnp.int32), rel=rel)

    def age_tick(self, state: CAMState, steps: int = 1) -> CAMState:
        """Advance the logical store age (drift clock) by ``steps``.
        The serve engine calls this once per ``CAMSearchServer.step()``."""
        if state.rel is None:
            return state
        rel = state.rel
        return _replace_state(state, rel=ReliabilityState(
            age=(rel.age + jnp.int32(steps)).astype(jnp.int32),
            prog_age=rel.prog_age, writes=rel.writes,
            retired=rel.retired, failed=rel.failed))

    def scrub(self, state: CAMState,
              key: Optional[jax.Array] = None) -> CAMState:
        """Background scrub: re-program the ``scrub_rows`` most-drifted
        live rows from their resident clean codes (write-verify applies;
        a row that can no longer hold its data is spare-healed).  A
        no-op when nothing has drifted."""
        relcfg = self.config.reliability
        if not relcfg.enabled or state.rel is None:
            raise ValueError("scrub() requires config.reliability.enabled "
                             "and a reliability-tracked state")
        if state.codes is None:
            raise ValueError("state has no resident clean codes — re-write "
                             "the store to enable scrub()")
        self._check_mutable()
        spec = state.spec
        padded_K = spec.padded_K
        slots = reliability.pick_scrub_slots(
            np.asarray(state.row_valid).reshape(-1)[:padded_K],
            np.asarray(state.rel.prog_age).reshape(-1)[:padded_K],
            int(np.asarray(state.rel.age)), relcfg.scrub_rows)
        if slots.size == 0:
            return state
        key = key if key is not None else jax.random.PRNGKey(0)
        slots_j = jnp.asarray(slots, jnp.int32)
        rows = self._gather_code_rows(state, slots_j)
        return self._heal_failed(
            self._write_rows(state, rows, slots_j, key, False, True), key)

    # ------------------------------------------------------------- query
    def query(self, state: CAMState, queries: jax.Array,
              key: Optional[jax.Array] = None,
              valid_count: Optional[int] = None) -> SearchResult:
        """Query simulation.

        queries: (Q, N) application-domain query batch.
        Returns a ``SearchResult`` (indices (Q, k) padded with -1, mask
        (Q, padded_K)); it unpacks as the historical ``(idx, mask)`` tuple.

        ``valid_count`` marks only the first ``valid_count`` batch rows as
        real queries: the serve loop pads short batches to a fixed width,
        and the pad rows must not influence the cascade's shared bank
        routing (``select_banks``).  Passed as a traced scalar so varying
        counts at one batch width share a single compilation.  ``None``
        (every row real) is bit-identical to ``valid_count=Q``; non-cascade
        searches evaluate each row independently, so the knob only affects
        routed searches.
        """
        if queries.ndim == 1:
            idx, mask = self.query(state, queries[None],
                                   key)
            return SearchResult(idx[0], mask[0])
        idx, mask = self._query_jit(state, queries,
                                    key if key is not None
                                    else jax.random.PRNGKey(1),
                                    None if valid_count is None
                                    else jnp.asarray(valid_count, jnp.int32))
        return SearchResult(idx, mask)

    @partial(jax.jit, static_argnums=(0,))
    def _query_jit(self, state: CAMState, queries, key, valid_count=None):
        idx, mask = self._query_inner(state, queries, key, valid_count)
        return self._to_original(state, idx, mask)

    def _effective_state(self, state: CAMState) -> CAMState:
        """Read path: what a search senses.  Overlays drift decay and the
        deterministic fault maps on the stored grid (a no-op unless
        reliability is enabled — the off path touches nothing)."""
        cfg = self.config
        if not cfg.reliability.enabled or state.rel is None:
            return state
        return _replace_state(
            state, grid=reliability.effective_grid(state.grid, state.rel,
                                                   cfg))

    def _query_inner(self, state: CAMState, queries, key, valid_count=None):
        cfg = self.config
        state = self._effective_state(state)
        bits = cfg.app.data_bits
        qcodes = self.query_codes(state, queries)            # (Q, N)
        qseg = mapping.partition_query(qcodes, state.spec)   # (Q, nh, C)

        if cfg.sim.cascade_enabled() and state.sigs is not None:
            valid = (None if valid_count is None
                     else jnp.arange(queries.shape[0]) < valid_count)
            return self._query_cascade(state, qcodes, qseg, key, valid)

        if cfg.device.variation not in ("c2c", "both"):
            # store once, search many: one fused batched pass
            return self._search_batch(state.grid, qseg, state)

        if self.c2c_fold == "bank":
            # per-bank RNG fold (the shard-invariant draw): search the
            # whole batch through the shard-local entry with v_offset=0,
            # then one batched merge — the single-device reference for
            # the sharded simulator's parity guarantee.
            dist, match = self.search_shard(
                state.grid, qseg, col_valid=state.col_valid,
                row_valid=state.row_valid, key=key)
            return self.merge_rows(dist, match, state.spec.padded_K)

        # C2C: fresh array noise per search cycle; one Q-tile per cycle.
        # All cycle noises are drawn in one batched primitive and the cycles
        # run as a vmap (parallel, like the old per-query pipeline) — the
        # memory high-water mark (n_tiles noisy grids) matches the old path
        # at the default tile of 1 and shrinks as the tile grows.
        Q = qseg.shape[0]
        tile = min(self.c2c_query_tile, Q)
        pad = (-Q) % tile
        qt = jnp.pad(qseg, ((0, pad), (0, 0), (0, 0)))
        n_tiles = qt.shape[0] // tile
        qt = qt.reshape(n_tiles, tile, *qseg.shape[1:])
        keys = variation.split_for_queries(key, n_tiles)
        noisy = variation.apply_c2c_batched(state.grid, cfg.device, bits,
                                            keys)

        idx, mask = jax.vmap(
            lambda g, q: self._search_batch(g, q, state))(noisy, qt)
        idx = idx.reshape(n_tiles * tile, *idx.shape[2:])[:Q]
        mask = mask.reshape(n_tiles * tile, *mask.shape[2:])[:Q]
        return idx, mask

    # ------------------------------------------------- shard-local pieces
    # The sharded simulator (core.sharded) drives these from inside a
    # shard_map body: each device runs the same quantize/search pipeline on
    # its local nv (bank) shard of the grid, and only the vertical merge
    # crosses devices.
    def need_dist(self) -> bool:
        """The AND merge consumes match lines only; the fused kernel then
        skips the (Q, nv, nh, R) distance write-back entirely."""
        cfg = self.config
        return not (cfg.app.match_type in ("exact", "threshold")
                    and cfg.arch.h_merge == "and")

    def match_k(self, padded_K: int) -> int:
        """Result width k of the merge for a padded_K-row store."""
        cfg = self.config
        return merge.match_k(cfg.app.match_type, cfg.app.match_param,
                             padded_K)

    def query_codes(self, state: CAMState, queries: jax.Array) -> jax.Array:
        """Quantize with the store's shared scale: (Q, N) code-domain."""
        cfg = self.config
        with jax.named_scope("cam.quantize"):
            qcodes, _, _ = quantize.quantize_for_cell(
                queries, cfg.circuit.cell_type, cfg.app.data_bits,
                state.lo, state.hi)
        return qcodes

    def segment_queries(self, state: CAMState, queries: jax.Array
                        ) -> jax.Array:
        """Quantize (shared scale) + partition: (Q, N) -> (Q, nh, C)."""
        return mapping.partition_query(self.query_codes(state, queries),
                                       state.spec)

    # --------------------------------------------------- cascade (stage 1)
    def route_banks(self, state: CAMState, qcodes: jax.Array,
                    p: Optional[int] = None,
                    valid: Optional[jax.Array] = None) -> jax.Array:
        """Stage-1 routing: (Q, N) query codes -> (p,) sorted bank ids.
        ``valid`` (Q,) bool excludes pad rows from the shared selection."""
        cfg = self.config
        qsig = prefilter.query_signatures(qcodes, state.sig_thr, state.spec,
                                          cfg.sim.signature_bits)
        scores = prefilter.bank_scores(state.sigs, qsig, state.row_valid,
                                       use_kernel=self.use_kernel)
        if p is None:
            p = min(cfg.sim.top_p_banks, state.spec.nv)
        return prefilter.select_banks(scores, p, valid)

    def _query_cascade(self, state: CAMState, qcodes, qseg, key,
                       valid: Optional[jax.Array] = None):
        """Two-stage search: route to top-p banks, exact-search only the
        gathered (p, nh, R, C) sub-grid, merge against original bank ids.

        With ``top_p_banks >= nv`` the selection is ``arange(nv)``, the
        gather is the identity, and the result is bit-identical to the
        full scan (a parity test asserts this per cell/merge combo)."""
        cfg = self.config
        spec = state.spec
        bank_ids = self.route_banks(state, qcodes, valid=valid)
        sub_grid = jnp.take(state.grid, bank_ids, axis=0)
        sub_rv = jnp.take(state.row_valid, bank_ids, axis=0)
        # C2C noise (if any) folds per ORIGINAL bank id, so the surviving
        # banks see exactly the noise they would in a full scan
        dist, match = self.search_shard(
            sub_grid, qseg, col_valid=state.col_valid, row_valid=sub_rv,
            key=key, bank_ids=bank_ids)
        with jax.named_scope("cam.merge"):
            return merge.merge_selected(
                dist, match, bank_ids, nv_total=spec.nv,
                match_type=cfg.app.match_type,
                h_merge=cfg.arch.h_merge,
                v_merge=cfg.arch.v_merge,
                match_param=self.match_k(spec.padded_K),
                sensing_limit=cfg.circuit.sensing_limit,
                threshold=float(cfg.app.match_param)
                if cfg.app.match_type == "threshold" else 0.0)

    def _to_original(self, state: CAMState, idx, mask):
        """Map placed-order results back to the caller's row order.

        ``placed[i] = orig[perm[i]]``, so a placed index maps through a
        gather and the placed mask scatters onto original positions."""
        if state.perm is None:
            return idx, mask
        with jax.named_scope("cam.backmap"):
            safe = jnp.take(state.perm, jnp.maximum(idx, 0))
            idx = jnp.where(idx >= 0, safe, -1)
            mask = jnp.zeros_like(mask).at[..., state.perm].set(mask)
        return idx, mask

    def search_shard(self, grid: jax.Array, qseg: jax.Array, *,
                     col_valid: jax.Array, row_valid: jax.Array,
                     key: Optional[jax.Array] = None, v_offset=0,
                     cycle_keys: Optional[jax.Array] = None,
                     bank_ids: Optional[jax.Array] = None
                     ) -> Tuple[Optional[jax.Array], jax.Array]:
        """Shard-local search over a pre-split grid.

        ``grid`` may be an nv-shard of the full stored grid whose first
        bank has global index ``v_offset`` (``row_valid`` is the matching
        (nv_local, R) shard; ``col_valid`` is replicated).  C2C noise uses
        the per-bank RNG fold (``variation.apply_c2c_banked``), so any
        split of the nv axis draws bit-identical noise.  ``cycle_keys``
        overrides the per-cycle key derivation for query-sharded batches
        (the caller splits the global key and slices this shard's cycles).
        ``bank_ids`` names the global bank each grid slot holds when the
        shard is a *gathered* subset (the cascade's top-p banks) rather
        than a contiguous slice — C2C noise then folds by those ids.

        Returns ``(dist, match)``, each (Q, nv_local, nh, R); ``dist`` is
        None when the merge consumes match lines only.
        """
        cfg = self.config
        bits = cfg.app.data_bits

        def run(g, q):
            with jax.named_scope("cam.search"):
                return subarray.subarray_query_batched(
                    g, q,
                    distance=cfg.app.distance,
                    sensing=cfg.circuit.sensing,
                    sensing_limit=cfg.circuit.sensing_limit,
                    threshold=float(cfg.app.match_param)
                    if cfg.app.match_type == "threshold" else 0.0,
                    col_valid=col_valid,
                    row_valid=row_valid,
                    use_kernel=self.use_kernel,
                    want_dist=self.need_dist(),
                    q_tile=self.q_tile,
                    pipeline=self.pipeline,
                    int_codes=self.int_codes)

        if cfg.device.variation not in ("c2c", "both"):
            return run(grid, qseg)

        Q = qseg.shape[0]
        tile = min(self.c2c_query_tile, Q)
        pad = (-Q) % tile
        qt = jnp.pad(qseg, ((0, pad), (0, 0), (0, 0)))
        n_tiles = qt.shape[0] // tile
        qt = qt.reshape(n_tiles, tile, *qseg.shape[1:])
        if cycle_keys is None:
            cycle_keys = variation.split_for_queries(key, n_tiles)
        noisy = variation.apply_c2c_banked(grid, cfg.device, bits,
                                           cycle_keys, v_offset,
                                           bank_ids=bank_ids)
        dist, match = jax.vmap(run)(noisy, qt)
        match = match.reshape(n_tiles * tile, *match.shape[2:])[:Q]
        if dist is not None:
            dist = dist.reshape(n_tiles * tile, *dist.shape[2:])[:Q]
        return dist, match

    def merge_rows(self, dist, match, padded_K: int):
        """Single-device merge of (Q, nv, nh, R) subarray outputs."""
        cfg = self.config
        with jax.named_scope("cam.merge"):
            return merge.merge(
                dist, match,
                match_type=cfg.app.match_type,
                h_merge=cfg.arch.h_merge,
                v_merge=cfg.arch.v_merge,
                match_param=self.match_k(padded_K),
                sensing_limit=cfg.circuit.sensing_limit,
                threshold=float(cfg.app.match_param)
                if cfg.app.match_type == "threshold" else 0.0)

    def _search_batch(self, grid, qseg, state: CAMState):
        """One fused batched search + merge over a (Q, nh, C) block."""
        cfg = self.config
        with jax.named_scope("cam.search"):
            dist, match = subarray.subarray_query_batched(
                grid, qseg,
                distance=cfg.app.distance,
                sensing=cfg.circuit.sensing,
                sensing_limit=cfg.circuit.sensing_limit,
                threshold=float(cfg.app.match_param)
                if cfg.app.match_type == "threshold" else 0.0,
                col_valid=state.col_valid,
                row_valid=state.row_valid,
                use_kernel=self.use_kernel,
                want_dist=self.need_dist(),
                q_tile=self.q_tile,
                pipeline=self.pipeline,
                int_codes=self.int_codes)
        return self.merge_rows(dist, match, state.spec.padded_K)
