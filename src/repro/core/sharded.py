"""Sharded CAM search: the bank level of the paper's hierarchy as a
physical device-mesh axis.

``ShardedCAMSimulator`` wraps ``FunctionalSimulator`` with a shard_map over
the stored grid's nv (vertical/bank) axis: each device holds an
``(nv_local, nh, R, C)`` shard of the grid and runs the fused batched
search kernel (one HBM pass per query batch) on its local banks, so
dataset capacity scales with the mesh instead of a single HBM.  Only the
*vertical* merge crosses devices — and it reproduces ``merge.merge``
bit-for-bit:

  * exact/threshold (gather v-merge): each device h-reduces its rows to a
    local 0/1 match-line block; ``all_gather`` along the bank axis
    concatenates the blocks into the global match lines (the lossless
    gather of paper Fig. 3).
  * best (comparator v-merge): each device takes a *stable* local top-k of
    its row scores (``merge.local_topk_candidates``), the (n_banks × k)
    candidate scores+global indices are gathered — bytes ~ n_banks·k, not
    the row count — and a stable re-rank picks the global winners
    (``merge.rerank_candidates``).  Stability makes the two-level
    comparator tree exact, ties included.  The voting tie-break normalizer
    is globalized with one ``lax.pmax`` of the per-device max distance.

  Horizontal (nh) reduction and the sense amplifier never cross devices:
  every device holds complete (R, C) subarrays, so ``sensing='best'``'s
  intra-subarray winner-take-all stays inside the local kernel.

C2C variation uses the per-bank RNG fold (``variation.apply_c2c_banked``):
bank v draws its cycle noise from ``fold_in(cycle_key, v)``, which is
invariant to how the nv axis is split — the single-device reference is
``FunctionalSimulator(..., c2c_fold='bank')``.

Grids whose nv does not divide the bank-axis size are padded with
all-invalid banks (row_valid 0): padded rows carry +inf distance / zero
match lines so they can never win, and the returned mask is sliced back to
the true padded_K.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_cam_mesh
from . import merge, prefilter, variation
from .config import CAMConfig
from .functional import (CAMState, FunctionalSimulator,
                         resolve_sim_overrides)
from .reliability import ReliabilityState
from .perf import ArchSpecifics, MeshLink, MeshSpec, perf_report
from .results import SearchResult


class ShardedCAMSimulator:
    """Multi-device store-once / search-many CAM simulation.

    Drop-in for ``FunctionalSimulator``: ``write`` places the grid across
    the mesh, ``query`` runs the shard_map search + cross-device merge.

    ``mesh``: a mesh with a ``bank_axis`` axis (see
    ``launch.mesh.make_cam_mesh``); when omitted it is derived from
    ``config.sim`` (``devices`` banks x ``query_shards``; 0 devices = all
    local).  ``query_axis``: optional mesh axis that additionally splits
    the query batch (Q must be a multiple of its size; with C2C noise, a
    multiple of ``query_shards * c2c_query_tile`` so cycle tiles align
    with shard boundaries); defaults to 'query' when
    ``config.sim.query_shards > 1``.  The ``use_kernel`` /
    ``c2c_query_tile`` kwargs are deprecated overrides for the
    ``config.sim`` fields of the same names.
    """

    def __init__(self, config: CAMConfig, mesh: Optional[Mesh] = None, *,
                 bank_axis: str = "bank", query_axis: Optional[str] = None,
                 use_kernel: Optional[bool] = None,
                 c2c_query_tile: Optional[int] = None):
        config = resolve_sim_overrides(config, use_kernel=use_kernel,
                                       c2c_query_tile=c2c_query_tile)
        # the inner reference simulator always draws C2C noise per bank
        # (the shard-invariant fold), whatever the config says
        self.sim = FunctionalSimulator(
            config.replace(sim=dict(c2c_fold="bank")))
        self.config = config
        if mesh is None:
            scfg = config.sim
            mesh = make_cam_mesh(scfg.devices or None, scfg.query_shards)
            if query_axis is None and scfg.query_shards > 1:
                query_axis = "query"
        self.mesh = mesh
        sizes = dict(zip(self.mesh.axis_names, self.mesh.axis_sizes))
        if bank_axis not in sizes:
            raise ValueError(f"mesh has no {bank_axis!r} axis: "
                             f"{self.mesh.axis_names}")
        self.bank_axis = bank_axis
        self.n_banks = sizes[bank_axis]
        if query_axis and query_axis not in sizes:
            raise ValueError(f"mesh has no {query_axis!r} axis: "
                             f"{self.mesh.axis_names}")
        self.query_axis = query_axis
        self.n_query = sizes[query_axis] if query_axis else 1

    # ------------------------------------------------------------- write
    def write(self, stored: jax.Array, key: Optional[jax.Array] = None
              ) -> CAMState:
        """Write simulation + mesh placement of the resulting state."""
        return self.shard_state(self.sim.write(stored, key))

    def shard_state(self, state: CAMState) -> CAMState:
        """Pad nv to a bank-axis multiple and place the state's pytree.

        The padding banks are all-invalid (row_valid 0), so searches treat
        them exactly like the in-bank padding rows the mapping submodule
        already produces for K % R != 0.
        """
        from repro.runtime.sharding import cam_state_shardings
        nv = state.grid.shape[0]
        pad = (-nv) % self.n_banks
        grid, row_valid, sigs = state.grid, state.row_valid, state.sigs
        codes, rel = state.codes, state.rel
        if pad:
            grid = jnp.pad(grid,
                           ((0, pad),) + ((0, 0),) * (grid.ndim - 1))
            row_valid = jnp.pad(row_valid, ((0, pad), (0, 0)))
            if sigs is not None:
                sigs = jnp.pad(sigs, ((0, pad), (0, 0), (0, 0)))
            if codes is not None:
                codes = jnp.pad(codes,
                                ((0, pad),) + ((0, 0),) * (codes.ndim - 1))
            if rel is not None:
                # padding banks: never programmed (age 0, no wear) and
                # row-invalid, like the in-bank padding rows
                rel = ReliabilityState(
                    age=rel.age,
                    prog_age=jnp.pad(rel.prog_age, ((0, pad), (0, 0))),
                    writes=jnp.pad(rel.writes, ((0, pad), (0, 0))),
                    retired=jnp.pad(rel.retired, ((0, pad), (0, 0))),
                    failed=jnp.pad(rel.failed, ((0, pad), (0, 0))))
        sh = cam_state_shardings(self.mesh, grid.ndim)
        if rel is not None:
            rel = ReliabilityState(
                age=jax.device_put(rel.age, sh["rel_age"]),
                prog_age=jax.device_put(rel.prog_age, sh["rel_rows"]),
                writes=jax.device_put(rel.writes, sh["rel_rows"]),
                retired=jax.device_put(rel.retired, sh["rel_rows"]),
                failed=jax.device_put(rel.failed, sh["rel_rows"]))
        return CAMState(
            grid=jax.device_put(grid, sh["grid"]),
            lo=jax.device_put(state.lo, sh["lo"]),
            hi=jax.device_put(state.hi, sh["hi"]),
            spec=state.spec,
            col_valid=jax.device_put(state.col_valid, sh["col_valid"]),
            row_valid=jax.device_put(row_valid, sh["row_valid"]),
            sigs=(jax.device_put(sigs, sh["sigs"])
                  if sigs is not None else None),
            sig_thr=(jax.device_put(state.sig_thr, sh["sig_thr"])
                     if state.sig_thr is not None else None),
            perm=(jax.device_put(state.perm, sh["perm"])
                  if state.perm is not None else None),
            codes=(jax.device_put(codes, sh["codes"])
                   if codes is not None else None),
            rel=rel)

    # --------------------------------------------------------- mutations
    # The mutation logic is shape-preserving and bank-local (scatter into
    # the touched rows' slots), so it is delegated to the inner reference
    # simulator on the placed arrays and the result is re-placed without a
    # re-shard (nv is already a bank multiple, so ``shard_state`` only
    # refreshes device placement).  Free slots never include the all-invalid
    # padding banks (``free_slots`` stops at ``spec.padded_K``).
    def insert(self, state: CAMState, rows: jax.Array,
               key: Optional[jax.Array] = None):
        new_state, ids = self.sim.insert(state, rows, key)
        return self.shard_state(new_state), ids

    def delete(self, state: CAMState, ids) -> CAMState:
        return self.shard_state(self.sim.delete(state, ids))

    def update(self, state: CAMState, ids, rows: jax.Array,
               key: Optional[jax.Array] = None) -> CAMState:
        return self.shard_state(self.sim.update(state, ids, rows, key))

    def compact(self, state: CAMState,
                key: Optional[jax.Array] = None) -> CAMState:
        return self.shard_state(self.sim.compact(state, key))

    # ------------------------------------------------------- reliability
    def free_slots(self, state: CAMState):
        return self.sim.free_slots(state)

    def age_tick(self, state: CAMState, steps: int = 1) -> CAMState:
        # only the replicated age scalar changes; the sharded row arrays
        # keep their placement, so no re-shard is needed
        return self.sim.age_tick(state, steps)

    def scrub(self, state: CAMState,
              key: Optional[jax.Array] = None) -> CAMState:
        return self.shard_state(self.sim.scrub(state, key))

    # ------------------------------------------------------------- perf
    def plan(self, entries: int, dims: int) -> ArchSpecifics:
        """Estimator-only planning: derive ``ArchSpecifics`` from shapes
        alone so ``eval_perf`` works before (or without) ``write``."""
        return self.sim.plan(entries, dims)

    def arch_specifics(self) -> ArchSpecifics:
        return self.sim.arch_specifics()

    def eval_perf(self, n_queries: int = 1, include_write: bool = False,
                  ops_per_query: int = 1,
                  clock_hz: Optional[float] = None,
                  link: Union[str, MeshLink] = "on_package",
                  queries_per_batch: int = 1,
                  mesh: Optional[Union[int, MeshSpec]] = None,
                  searched_fraction: Optional[float] = None,
                  prefilter_bits: Optional[int] = None):
        """Mesh-level hardware performance prediction for the written
        store: per-device hierarchy rollup + cross-device merge over
        chip-to-chip ``link``s, for the topology this simulator executes
        (its bank-axis size; pass ``mesh`` to predict a different one).

        ``queries_per_batch`` amortizes the merge collective over a query
        batch (the serving batch size); defaults to 1.  A 1-bank mesh
        reproduces ``CAMASim.eval_perf`` exactly."""
        if mesh is None:
            mesh = MeshSpec(self.n_banks, link)
        return perf_report(
            self.config, self.arch_specifics(),
            mesh=mesh, n_queries=n_queries,
            include_write=include_write, ops_per_query=ops_per_query,
            clock_hz=clock_hz, queries_per_batch=queries_per_batch,
            searched_fraction=searched_fraction,
            prefilter_bits=prefilter_bits)

    # --------------------------------------------------- shard-local pieces
    # Backend-protocol delegation: the same shard-local entry points the
    # functional simulator exposes, on the shared reference simulator.
    def segment_queries(self, state: CAMState, queries: jax.Array
                        ) -> jax.Array:
        return self.sim.segment_queries(state, queries)

    def search_shard(self, grid, qseg, **kw):
        return self.sim.search_shard(grid, qseg, **kw)

    # ------------------------------------------------------------- query
    def query(self, state: CAMState, queries: jax.Array,
              key: Optional[jax.Array] = None,
              valid_count: Optional[int] = None) -> SearchResult:
        """Query simulation across the mesh.

        queries: (Q, N) application-domain batch (or a single (N,) query).
        Returns a ``SearchResult`` (unpacks as ``(indices, mask)``),
        bit-identical to ``FunctionalSimulator(..., c2c_fold='bank')``.

        ``valid_count`` marks only the first ``valid_count`` rows as real
        queries (the serve loop's pad-exclusion knob — see
        ``FunctionalSimulator.query``); it only affects the cascade's
        shared bank routing.
        """
        if queries.ndim == 1:
            idx, mask = self.query(state, queries[None], key)
            return SearchResult(idx[0], mask[0])
        if self.n_banks == 1 and self.n_query == 1:
            # Degenerate 1-device mesh: the shard_map collectives are
            # identities that only add dispatch overhead (BENCH:
            # kernel_*_sharded_d1 losing at 0.97x/0.85x), and the inner
            # simulator IS the documented bit-identical reference
            # (c2c_fold='bank') — delegate outright.
            return self.sim.query(state, queries, key,
                                  valid_count=valid_count)
        Q = queries.shape[0]
        if self.n_query > 1:
            tile = (min(self.sim.c2c_query_tile, Q)
                    if self.config.device.variation in ("c2c", "both")
                    else 1)
            if Q % (self.n_query * tile):
                raise ValueError(
                    f"Q={Q} must be a multiple of query_shards*c2c_tile="
                    f"{self.n_query}*{tile} for query-axis sharding")
        idx, mask = self._query_jit(state, queries,
                                    key if key is not None
                                    else jax.random.PRNGKey(1),
                                    None if valid_count is None
                                    else jnp.asarray(valid_count, jnp.int32))
        return SearchResult(idx, mask)

    @partial(jax.jit, static_argnums=(0,))
    def _query_jit(self, state: CAMState, queries, key, valid_count=None):
        cfg = self.config
        # reliability read path: drift + fault overlay is elementwise in
        # global coordinates, so it applies to the placed grid before the
        # shard_map and partitions along with it (bit-identical to the
        # functional reference's overlay)
        state = self.sim._effective_state(state)
        qcodes = self.sim.query_codes(state, queries)        # (Q, N)
        qseg = self.sim.segment_queries(state, queries)      # (Q, nh, C)
        qsig = qvalid = None
        if cfg.sim.cascade_enabled() and state.sigs is not None:
            # stage-1 query signatures are cheap and replicated-friendly:
            # computed once outside the shard_map, sharded like the batch
            qsig = prefilter.query_signatures(
                qcodes, state.sig_thr, state.spec, cfg.sim.signature_bits)
            # the routing valid mask is materialized (all-true when no
            # count is given) so the shard_map arity stays fixed
            qvalid = (jnp.ones((queries.shape[0],), bool)
                      if valid_count is None
                      else jnp.arange(queries.shape[0]) < valid_count)
        idx, mask = self._sharded_search(state, qseg, qsig, key, qvalid)
        return self.sim._to_original(state, idx,
                                     mask[..., :state.spec.padded_K])

    # -------------------------------------------------------- shard_map
    def _sharded_search(self, state: CAMState, qseg, qsig, key,
                        qvalid=None):
        cfg = self.config
        ba, qa = self.bank_axis, self.query_axis
        nv_pad, R = state.grid.shape[0], state.grid.shape[2]
        assert nv_pad % self.n_banks == 0, \
            "state not placed with shard_state()"
        nv_loc = nv_pad // self.n_banks
        K_pad = nv_pad * R
        k = self.sim.match_k(state.spec.padded_K)
        Q = qseg.shape[0]
        use_c2c = cfg.device.variation in ("c2c", "both")
        tile = min(self.sim.c2c_query_tile, Q) if use_c2c else 1
        n_tiles = -(-Q // tile) if use_c2c else 0

        def cycle_keys_for(key):
            if not use_c2c:
                return None
            # the cycle keys are a function of the GLOBAL tile index:
            # split once for all tiles, slice this query shard's range
            gkeys = variation.split_for_queries(key, n_tiles)
            if self.n_query > 1:
                tiles_loc = n_tiles // self.n_query
                q_idx = jax.lax.axis_index(qa)
                return jax.lax.dynamic_slice_in_dim(
                    gkeys, q_idx * tiles_loc, tiles_loc)
            return gkeys

        q_spec = P(qa) if self.n_query > 1 else P()

        if qsig is not None:
            # per-device routing: each device prunes its OWN nv_loc banks
            # down to p_loc; the global budget splits evenly across the
            # bank axis, so top_p_banks >= nv gives p_loc = nv_loc (full
            # local scan) and the cascade degenerates to the exact path
            p_loc = min(nv_loc,
                        -(-min(cfg.sim.top_p_banks, state.spec.nv)
                          // self.n_banks))

            def body(grid, row_valid, sigs, col_valid, qseg_l, qsig_l,
                     qvalid_l, key):
                b_idx = jax.lax.axis_index(ba)
                scores = prefilter.bank_scores(
                    sigs, qsig_l, row_valid, use_kernel=self.sim.use_kernel)
                local_ids = prefilter.select_banks(scores, p_loc, qvalid_l)
                sub_grid = jnp.take(grid, local_ids, axis=0)
                sub_rv = jnp.take(row_valid, local_ids, axis=0)
                # C2C noise folds by GLOBAL bank id of each selected bank
                dist, match = self.sim.search_shard(
                    sub_grid, qseg_l, col_valid=col_valid, row_valid=sub_rv,
                    key=key, cycle_keys=cycle_keys_for(key),
                    bank_ids=b_idx * nv_loc + local_ids)
                return self._combine(dist, match, b_idx, nv_loc, R,
                                     K_pad, k, local_ids)

            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(ba), P(ba), P(ba), P(), q_spec, q_spec, q_spec,
                          P()),
                out_specs=(q_spec, q_spec), check_vma=False)(
                state.grid, state.row_valid, state.sigs, state.col_valid,
                qseg, qsig, qvalid, key)

        def body(grid, row_valid, col_valid, qseg_l, key):
            b_idx = jax.lax.axis_index(ba)
            dist, match = self.sim.search_shard(
                grid, qseg_l, col_valid=col_valid, row_valid=row_valid,
                key=key, v_offset=b_idx * nv_loc,
                cycle_keys=cycle_keys_for(key))
            return self._combine(dist, match, b_idx, nv_loc, R, K_pad, k)

        # the merged results are replicated over the bank axis by the
        # all_gather + re-rank; check_vma=False because the varying-axes
        # inference cannot see that through the comparator re-rank
        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(ba), P(ba), P(), q_spec, P()),
            out_specs=(q_spec, q_spec), check_vma=False)(
            state.grid, state.row_valid, state.col_valid, qseg, key)

    def _combine(self, dist, match, b_idx, nv_loc: int, R: int,
                 K_pad: int, k: int, local_ids=None):
        """Cross-device vertical merge of shard-local subarray outputs.

        Mirrors ``merge.merge`` (same h-reduce, same stable comparator
        ordering) with the nv reduction distributed over the bank axis.
        ``local_ids`` names the (p_loc,) local banks a routed search ran
        on (the cascade): results are put back into the device's full
        (nv_loc, R) frame, so the collective payloads keep their shapes
        (``merge.shard_merge_payload`` still models them); with
        ``p_loc = nv_loc`` and sorted ids this is bit-identical to the
        full scan.

        The device-local work carries the scope ``cam.merge`` (as the
        single-device merge does), the collectives and the re-rank
        ``cam.shard.exchange``.
        """
        cfg = self.config
        ba = self.bank_axis
        thr = (float(cfg.app.match_param)
               if cfg.app.match_type == "threshold" else 0.0)

        if cfg.app.match_type in ("exact", "threshold"):
            if cfg.arch.v_merge != "gather":
                raise ValueError(
                    f"{cfg.app.match_type} match uses gather v-merge")
            with jax.named_scope("cam.merge"):
                row = merge.h_reduce_match(
                    dist, match, match_type=cfg.app.match_type,
                    h_merge=cfg.arch.h_merge,
                    sensing_limit=cfg.circuit.sensing_limit, threshold=thr)
                if local_ids is not None:
                    # unselected local banks read as unmatched
                    row = merge.scatter_match_rows(row, local_ids, nv_loc)
                    row = row.reshape(*row.shape[:-1], nv_loc, R)
            with jax.named_scope("cam.shard.exchange"):
                # lossless gather: concatenate the per-bank match lines
                rows = jax.lax.all_gather(row, ba, axis=1, tiled=True)
            with jax.named_scope("cam.merge"):
                mask = merge.v_merge_gather(rows)           # (Q, K_pad)
                return merge.first_k_indices(mask, k), mask

        if cfg.app.match_type != "best":
            raise ValueError(f"unknown match_type {cfg.app.match_type!r}")
        if cfg.arch.v_merge != "comparator":
            raise ValueError("best match requires comparator v-merge")
        dmax = None
        if cfg.arch.h_merge == "voting":
            # tie-break normalizer over ALL banks: one scalar-ish pmax
            with jax.named_scope("cam.merge"):
                dmax = merge.voting_dmax(dist)
            with jax.named_scope("cam.shard.exchange"):
                dmax = jax.lax.pmax(dmax, ba)
        with jax.named_scope("cam.merge"):
            values, largest = merge.h_reduce_best(
                dist, match, h_merge=cfg.arch.h_merge, dmax=dmax)
            if local_ids is None:
                vals, gidx = merge.local_topk_candidates(
                    values, k, largest=largest, row_offset=b_idx * nv_loc * R)
            else:
                vals, gidx = merge.selected_topk(
                    values, k, largest=largest, bank_ids=local_ids,
                    bank_offset=b_idx * nv_loc)
        with jax.named_scope("cam.shard.exchange"):
            # comparator tree: gather only the candidate scores + global
            # indices, then a stable re-rank (replicated on every device)
            av = jax.lax.all_gather(vals, ba)        # (n_banks, Q, k_l)
            ai = jax.lax.all_gather(gidx, ba)
            av = jnp.moveaxis(av, 0, -2).reshape(*vals.shape[:-1], -1)
            ai = jnp.moveaxis(ai, 0, -2).reshape(*gidx.shape[:-1], -1)
            best_v, best_i = merge.rerank_candidates(av, ai, k,
                                                     largest=largest)
        with jax.named_scope("cam.merge"):
            return merge.finalize_topk(best_v, best_i, largest=largest,
                                       K=K_pad)
