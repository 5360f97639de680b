"""Distributed MapReduce-on-graph engine (paper §II-B execution model).

Simulates K servers bit-faithfully: each server Maps its subgraph M_k, the
Shuffle phase moves exactly the bits the chosen scheme prescribes, and each
server Reduces R_k using *only* locally-Mapped plus delivered values. Any
divergence from the single-machine oracle is therefore a real bug in the
allocation or coding logic, not a modeling artifact.

The multicast schedule depends only on (graph, allocation), so `run` compiles
a `ShufflePlan` once and replays it every iteration (compile-once /
execute-many); the schedule-completeness check that used to run per iteration
now runs once at compile time inside `compile_plan`.

Execution paths (`path=` argument):
  sparse (default when the program has an edge-value form) - one iteration is
      O(edges + plan) in time and memory: Map emits a [nnz] edge-value vector
      in CSR order, the plan's sparse executors move exactly the scheduled
      entries, and the Reduce is one gather (local CSR slice + delivery
      arrays, via the plan's precompiled edge-order gather table) followed by
      a segment reduction. Because the gather lands every row's values in
      canonical CSR entry order, the distributed result is bitwise equal to
      the sparse single-machine oracle (`reference_run(path="sparse")`).
  dense - the paper-literal [n, n] form, kept as the validation oracle (and
      the only path for programs without an edge-value form). Bitwise equal
      to `reference_run(path="dense")`. Cross-path, sum-programs (pagerank)
      may differ by float reduction order within a few ulp; min/integer
      programs are bitwise identical (see algorithms.py).

Backends (sparse path): `backend="numpy"` segment-reduces with reduceat;
`backend="spmv"` routes the row reduction of linear programs (pagerank,
degree) through the kernels/spmv Pallas kernel in [bm, n] blocked strips, so
the TPU path exercises real MXU tiles at O(bm*n) memory; `backend="fused"`
(mode="coded" only) executes each iteration's Shuffle on a multi-device
('servers',) mesh under shard_map - per-shard XOR encode, one packed
all_gather of uint32 coded words, per-shard strip - via
`fused_shuffle.FusedSparseShuffle`, jitted once and replayed, with delivered
words bitwise equal to the NumPy plan executor (the Reduce then rides the
same gather + segment reduction as backend="numpy").

Modes:
  single      - oracle, no distribution.
  uncoded     - baseline unicast shuffle   (load ~ p(1 - r/K)).
  coded       - paper's XOR multicast      (load ~ p(1 - r/K)/r), bit-exact.
  coded-fast  - same schedule/loads via the compiled plan, values moved
                directly (skips the XOR simulation; used for large sweeps).
  coded-ref   - the literal per-group reference (`coded_shuffle.run_coded`),
                dict delivery and dense reduce; kept for A/B validation.

Sessions: `compile(program, g, alloc, mode, path=, backend=, **opts)` returns
a `CompiledEngine` holding every reusable artifact (plan, edge tables, fused
exchange) so repeated `.run(iters)` / `.run_batch(states, iters)` calls never
recompile; `run(...)` remains as a thin one-shot wrapper over it. Batched
states [n, B] (multi-source SSSP, personalized PageRank) ride ONE Shuffle
exchange per iteration on the sparse path - the schedule is value-agnostic,
so `bits_sent` scales as B x the single-query schedule bits.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ..launch.mesh import Topology
from ..obs import get_tracer
from .algorithms import VertexProgram
from .allocation import Allocation
from .bitcodec import T_BITS
from .coded_shuffle import run_coded
from .graph_models import Graph
from .shuffle_plan import (HierarchicalPlan, PlanShuffleResult, ShufflePlan,
                           compile_hierarchical, compile_plan_csr,
                           resolve_topology)
from .uncoded_shuffle import missing_pairs

PLAN_MODES = ("uncoded", "coded", "coded-fast")

# Per-backend accepted `backend_opts` keys. Validated up front so a typo'd
# option raises instead of being silently dropped (numpy takes none).
_BACKEND_OPTS: dict[str, frozenset] = {
    "numpy": frozenset(),
    "spmv": frozenset({"bm", "interpret"}),
    "fused": frozenset({"mesh", "encode", "interpret"}),
}


def _validate_backend_opts(backend: str, opts: dict) -> None:
    if backend not in _BACKEND_OPTS:
        raise ValueError(f"unknown backend {backend!r}")
    unknown = sorted(set(opts) - _BACKEND_OPTS[backend])
    if unknown:
        accepted = sorted(_BACKEND_OPTS[backend])
        raise ValueError(
            f"backend {backend!r} got unknown option(s) {unknown}; "
            f"accepted: {accepted if accepted else '(none)'}")


@dataclasses.dataclass
class EngineResult:
    state: np.ndarray            # [n] (or [n, B] from a batched run)
    iters: int
    shuffle_bits: int            # total over all iterations
    mode: str
    faults: "object | None" = None   # faults.FaultLog when a schedule ran

    @property
    def batch(self) -> int:
        """Number of query columns carried (1 for unbatched runs)."""
        return 1 if self.state.ndim == 1 else int(self.state.shape[1])

    @property
    def normalized_load(self) -> float:
        """Average per-iteration, per-query Definition-2 load."""
        n = self.state.shape[0]
        return (self.shuffle_bits / max(self.iters, 1)
                / (self.batch * n * n * T_BITS))


def _reduce_distributed(program: VertexProgram, g: Graph, alloc: Allocation,
                        values: np.ndarray,
                        delivered: dict[int, dict[tuple[int, int], float]],
                        state: np.ndarray) -> np.ndarray:
    """Dict-delivery Reduce (reference path; `faults.py` and coded-ref)."""
    new_state = np.empty_like(state)
    for k in range(alloc.K):
        vk = np.full((g.n, g.n), program.identity, dtype=np.float32)
        cols = alloc.map_sets[k]
        vk[:, cols] = values[:, cols]                  # locally Mapped
        for (i, j), v in delivered[k].items():
            vk[i, j] = v
        rk = alloc.reduce_owner == k
        # Verify the server really has everything it needs (catches schedule bugs).
        need = g.adj & rk[:, None]
        have = cols[None, :] | np.zeros((g.n, g.n), dtype=bool)
        for (i, j) in delivered[k]:
            have[i, j] = True
        if (need & ~have).any():
            miss = np.argwhere(need & ~have)[:5]
            raise RuntimeError(f"server {k} missing values, e.g. {miss.tolist()}")
        reduced = program.reduce(vk, g.adj, state, g)
        new_state[rk] = reduced[rk]
    return new_state


def _reduce_plan(program: VertexProgram, g: Graph, alloc: Allocation,
                 values: np.ndarray, res: PlanShuffleResult,
                 state: np.ndarray) -> np.ndarray:
    """Array-delivery dense Reduce: scatter each server's CSR slice.

    O(K n^2) per iteration - the reference the sparse path is validated and
    benchmarked against (`path="dense"`). Schedule completeness was verified
    once at plan-compile time, so the per-iteration missing-value scan of the
    dict path is not repeated here.
    """
    new_state = np.empty_like(state)
    for k in range(alloc.K):
        vk = np.full((g.n, g.n), program.identity, dtype=np.float32)
        cols = alloc.map_sets[k]
        vk[:, cols] = values[:, cols]                  # locally Mapped
        a, b = int(res.ptr[k]), int(res.ptr[k + 1])
        vk[res.i[a:b], res.j[a:b]] = res.values[a:b]   # delivered
        rk = alloc.reduce_owner == k
        reduced = program.reduce(vk, g.adj, state, g)
        new_state[rk] = reduced[rk]
    return new_state


def _reduce_sparse(program: VertexProgram, g: Graph, edge_vals: np.ndarray,
                   res: PlanShuffleResult, gather: np.ndarray,
                   state: np.ndarray) -> np.ndarray:
    """Gather-then-segment-reduce over all servers at once, O(edges).

    Each CSR entry's value comes from its owner's locally-Mapped slice or
    its delivery slot (the precompiled `gather` table encodes which); the
    gathered vector is in canonical CSR entry order, so the segment
    reduction is bitwise identical to the sparse single-machine oracle.
    """
    buf = np.concatenate([edge_vals, res.values])
    return program.reduce_edges(buf[gather], g.csr.indptr, state, g)


def _reduce_spmv(program: VertexProgram, g: Graph, state: np.ndarray, *,
                 bm: int = 128,
                 interpret: bool | None = None) -> np.ndarray:
    """Blocked row reduction through the kernels/spmv Pallas kernel.

    Valid for linear programs (v_{i,j} = map_source(g, state)[j], Reduce =
    sum + elementwise finalize): acc = adj @ c computed strip-by-strip from
    the CSR view at O(bm * n) memory. Kernel float accumulation order
    differs from reduceat, so this backend is tolerance- (not bit-) exact.
    Batched [n, B] states run the kernel once per query column and finalize
    on the stacked [n, B] accumulator (finalize may close over per-query
    data, e.g. personalized-PageRank preference columns).
    """
    from ..kernels.spmv import ops as spmv_ops

    c = program.map_source(g, state)

    def one(col):
        return spmv_ops.spmv_csr_rows(g.csr.indptr, g.csr.indices, col, g.n,
                                      rows=g.csr.rows, bm=bm,
                                      interpret=interpret)

    acc = (np.stack([one(c[:, b]) for b in range(c.shape[1])], axis=1)
           if c.ndim == 2 else one(c))
    return program.finalize(acc, state, g)


def _use_sparse(program: VertexProgram, mode: str, path: str) -> bool:
    if path not in ("auto", "sparse", "dense"):
        raise ValueError(f"unknown path {path!r}")
    if mode not in PLAN_MODES + ("single", "coded-ref"):
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "coded-ref":
        if path == "sparse":
            raise ValueError("coded-ref is the dense dict-delivery reference")
        return False
    if path == "sparse" and not program.supports_sparse:
        raise ValueError(f"{program.name} has no edge-value (sparse) form")
    return path != "dense" and program.supports_sparse


def _plan_bits(plan: ShufflePlan, mode: str) -> int:
    """Bits-on-the-wire of one Shuffle: schedule-only, data-independent."""
    if mode == "coded":
        return plan.coded_bits + plan.leftover_bits
    if mode == "coded-fast":
        return plan.coded_bits
    return plan.uncoded_bits


class CompiledEngine:
    """Compile-once session bound to (graph, allocation, mode, path, backend).

    Holds every reusable artifact - the `ShufflePlan`, its CSR edge tables,
    and (for backend="fused") the jitted multi-device exchange - so repeated
    `.run` / `.run_batch` calls replay iterations with zero recompilation.
    All of those artifacts are *program-independent* (the schedule is a
    function of (graph, allocation) only), which is why `with_program`
    rebinds the vertex program for free: the serving queue swaps in a fresh
    `multi_sssp` / `personalized_pagerank` per admitted batch on one session
    without ever touching the plan.
    """

    def __init__(self, program: VertexProgram, g: Graph,
                 alloc: Allocation | None, mode: str = "coded", *,
                 path: str = "auto", backend: str = "numpy",
                 plan: ShufflePlan | HierarchicalPlan | None = None,
                 backend_opts: dict | None = None,
                 topology: Topology | None = None):
        backend_opts = dict(backend_opts or {})
        sparse = _use_sparse(program, mode, path)
        _validate_backend_opts(backend, backend_opts)
        self.hplan = None
        plan, topology = resolve_topology(plan, topology)
        if isinstance(plan, HierarchicalPlan):
            self.hplan = plan
            plan = plan.flat
        hier = topology is not None and not topology.is_flat
        if hier:
            # The hierarchical executor implements the coded sparse Shuffle
            # only; spmv never executes a Shuffle at all.
            if mode != "coded" or not sparse:
                raise ValueError(
                    "a non-flat topology runs the two-level coded Shuffle: "
                    f"mode='coded' on the sparse path required (got "
                    f"mode={mode!r}, path={path!r})")
            if backend == "spmv":
                raise ValueError(
                    "backend='spmv' skips the Shuffle; a non-flat topology "
                    "needs backend 'numpy' or 'fused'")
            if alloc is None:
                raise ValueError("a non-flat topology needs an allocation")
            topology.check_K(alloc.K)
        if backend == "spmv":
            if not sparse:
                raise ValueError("backend='spmv' requires the sparse path")
            if program.map_source is None or program.finalize is None:
                raise ValueError(
                    f"{program.name} is not linear (no map_source/finalize); "
                    "backend='spmv' needs a per-source Map and a sum Reduce")
        if backend == "fused":
            if not sparse:
                raise ValueError("backend='fused' requires the sparse path")
            if mode != "coded":
                raise ValueError(
                    "backend='fused' executes the coded multicast schedule; "
                    f"use mode='coded' (got {mode!r})")
            if alloc is None:
                raise ValueError("backend='fused' needs an allocation")
        self.program = program
        self.g = g
        self.alloc = alloc
        self.mode = mode
        self.path = path                      # as requested ("auto" kept)
        self.backend = backend
        self.backend_opts = backend_opts
        self.topology = topology
        self.sparse = sparse
        self.distributed = mode != "single" and alloc is not None
        if self.distributed and mode in PLAN_MODES and plan is None:
            # Uncoded only consumes the missing set; skip the column tables.
            # CSR entry point: adjacency-free and schedule-identical to the
            # dense compile, so CSR-native graphs never materialize [n, n].
            with get_tracer().span(
                    "engine.compile", mode=mode, backend=backend, n=g.n,
                    K=alloc.K,
                    **({"racks": topology.racks,
                        "servers_per_rack": topology.servers_per_rack}
                       if hier else {})):
                if hier:
                    self.hplan = compile_hierarchical(g.csr, alloc, topology)
                    plan = self.hplan.flat
                else:
                    plan = compile_plan_csr(g.csr, alloc,
                                            schedule=mode != "uncoded")
        self.plan = plan
        self.tables = (plan.edge_tables(g.csr, alloc)
                       if sparse and self.distributed and mode in PLAN_MODES
                       else None)
        self.htables = (self.hplan.edge_tables(g.csr, alloc)
                        if self.hplan is not None and sparse
                        and self.distributed else None)
        self._fused = None
        self.recovery = None                  # faults.RepairStats after fail()
        self.delta_stats = None               # shuffle_plan.DeltaStats after update()

    @property
    def fused(self):
        """The jitted shard_map exchange, built on first use and replayed
        (compile-once / execute-many); value- and program-agnostic."""
        if self.backend == "fused" and self._fused is None:
            from .fused_shuffle import FusedSparseShuffle
            self._fused = FusedSparseShuffle(
                self.hplan if self.hplan is not None else self.plan,
                self.g.csr, self.alloc, **self.backend_opts)
        return self._fused

    def with_program(self, program: VertexProgram) -> "CompiledEngine":
        """Rebind the vertex program on the same compiled artifacts.

        No recompilation: the plan, edge tables, and fused exchange carry
        over verbatim (they never saw the program). This is the serving
        queue's per-batch hook.
        """
        eng = CompiledEngine(
            program, self.g, self.alloc, self.mode, path=self.path,
            backend=self.backend,
            plan=self.hplan if self.hplan is not None else self.plan,
            backend_opts=self.backend_opts)
        eng._fused = self._fused
        return eng

    def fail(self, servers) -> "CompiledEngine":
        """Degrade this session after `servers` crash; returns the survivors'.

        Coded modes repair the compiled schedule in place of recompiling
        (`ShufflePlan.repair`: dead senders' columns handed to healthy group
        members, bitwise-equal delivered words), so post-failure iterations
        keep the coded gain; uncoded recompiles the missing set on the
        degraded allocation. Always call on the *original* session with the
        cumulative failed set - `fail((0,)).fail((0, 1))` is not supported,
        `fail((0, 1))` is. The returned session's `.recovery` holds the
        `RepairStats` (hand-over bits, demotions, re-Mapped vertices); its
        per-iteration `run` bits include the hand-over overhead.
        """
        from .faults import RepairStats, degrade_allocation

        if not self.distributed or self.mode not in PLAN_MODES:
            raise ValueError(
                "fail() needs a distributed plan-mode session "
                f"(uncoded/coded/coded-fast; got mode={self.mode!r})")
        failed = tuple(sorted({int(s) for s in np.atleast_1d(servers)}))
        bad = [s for s in failed if not 0 <= s < self.alloc.K]
        if bad:
            raise ValueError(f"failed servers {bad} out of range 0..{self.alloc.K - 1}")
        if self.mode == "uncoded":
            degraded, dstats = degrade_allocation(self.alloc, failed)
            plan = compile_plan_csr(self.g.csr, degraded, schedule=False)
            rstats = RepairStats(failed, dstats.remapped_vertices, 0, 0)
        else:
            plan, degraded, rstats = self.plan.repair(self.g.csr, self.alloc,
                                                      failed)
            if self.hplan is not None:
                # Repair keeps the rack structure: the survivors stay in
                # their racks, so the two-level session recompiles the
                # hierarchical plan on the degraded allocation (O(edges))
                # while `rstats` keeps the flat repair's hand-over pricing.
                plan = compile_hierarchical(self.g.csr, degraded,
                                            self.topology)
        eng = CompiledEngine(self.program, self.g, degraded, self.mode,
                             path=self.path, backend=self.backend, plan=plan,
                             backend_opts=self.backend_opts)
        eng.recovery = rstats
        return eng

    def update(self, delta) -> "CompiledEngine":
        """Rebind this session to the mutated graph in O(plan + delta).

        `delta` is a `graphs.EdgeDelta`. The returned session is
        array-identical to compiling fresh on the mutated graph - the plan
        is patched by `ShufflePlan.apply_delta` (bitwise-equal schedule),
        the CSR edge tables are carried forward incrementally (no
        re-locate), and for backend="fused" the partition tables are
        rebuilt on the *same* jitted exchange, which re-lowers only if the
        padded partition shapes actually changed. The new session's
        `.delta_stats` holds the `DeltaStats`.

        Composes with `fail` both ways: `update` on a degraded session
        re-patches hand-over senders for the new schedule (its
        `.recovery.handover_bits` is refreshed), and `fail` on an updated
        session repairs the updated plan.
        """
        if not self.distributed or self.mode not in PLAN_MODES:
            raise ValueError(
                "update() needs a distributed plan-mode session "
                f"(uncoded/coded/coded-fast; got mode={self.mode!r})")
        with get_tracer().span("engine.update", mode=self.mode,
                               inserts=delta.num_insert,
                               deletes=delta.num_delete) as sp:
            csr2 = self.g.csr.apply_delta(delta)
            g2 = Graph(model=self.g.model, params=dict(self.g.params),
                       csr=csr2, dense_limit=self.g.dense_limit)
            plan2, dstats = self.plan.apply_delta(
                self.g.csr, self.alloc, delta, csr_new=csr2)
            if self.hplan is not None:
                # The flat patch prices the delta (`dstats`); the rack-level
                # stream can shift arbitrarily under it, so the two-level
                # session recompiles the hierarchy on the new CSR.
                plan2 = compile_hierarchical(csr2, self.alloc, self.topology)
            eng = CompiledEngine(self.program, g2, self.alloc, self.mode,
                                 path=self.path, backend=self.backend,
                                 plan=plan2, backend_opts=self.backend_opts)
            eng.delta_stats = dstats
            if self.recovery is not None:
                eng.recovery = (
                    dataclasses.replace(self.recovery,
                                        handover_bits=dstats.handover_bits)
                    if dstats.schedule_changed else self.recovery)
            if self._fused is not None:
                eng._fused = (self._fused if len(delta) == 0
                              else self._fused.rebind(plan2, csr2,
                                                      self.alloc))
            sp.set(schedule_changed=dstats.schedule_changed,
                   handover_bits=dstats.handover_bits)
        return eng

    def _apply_events(self, cur: "CompiledEngine", events,
                      failed: set, straggling: set, log) -> tuple["CompiledEngine", bool]:
        """Fold one boundary's fault events into the (failed, straggling)
        sets; returns (current session, whether a new crash landed)."""
        crashed = changed = False
        tr = get_tracer()
        for ev in events:
            tr.event(f"fault.{ev.kind}", at=ev.at,
                     servers=",".join(str(s) for s in ev.servers))
            if ev.kind == "crash":
                new = set(ev.servers) - failed
                if new:
                    failed |= new
                    straggling -= new
                    changed = crashed = True
                    log.crashes += 1
            elif ev.kind == "recover":
                if set(ev.servers) & failed:
                    failed.difference_update(ev.servers)
                    changed = True
                    log.recoveries += 1
                straggling.difference_update(ev.servers)
            else:                                       # "straggle"
                straggling |= set(ev.servers) - failed
            log.applied += (ev,)
        if changed:
            cur = self if not failed else self.fail(tuple(sorted(failed)))
            if cur.recovery is not None:
                log.demoted_pairs = cur.recovery.demoted_pairs
                log.remapped_vertices = cur.recovery.remapped_vertices
        return cur, crashed

    def _step(self, state: np.ndarray) -> tuple[np.ndarray, int]:
        """One Map -> Shuffle -> Reduce round; returns (state', bits_sent)."""
        program, g, alloc = self.program, self.g, self.alloc
        tr = get_tracer()
        if self.sparse:
            if self.backend == "spmv":
                # Coverage was verified when `tables` was built, so the
                # blocked kernel reads each owner's full CSR row slice; the
                # shuffled values would be recomputed per-source anyway, so
                # only the (schedule-only) bit accounting is added. Batched
                # states run the kernel per query column.
                B = 1 if state.ndim == 1 else state.shape[1]
                bits = _plan_bits(self.plan, self.mode) * B \
                    if self.distributed else 0
                return _reduce_spmv(program, g, state,
                                    **self.backend_opts), bits
            with tr.span("phase.map", nnz=g.csr.nnz):
                edge_vals = program.map_edge_values(g, state) \
                    .astype(np.float32)
            if not self.distributed:
                with tr.span("phase.reduce"):
                    return program.reduce_edges(edge_vals, g.csr.indptr,
                                                state, g), 0
            # The executor emits phase.encode / phase.exchange /
            # phase.decode spans itself (it knows words and bits).
            if self.backend == "fused":
                res = self.fused.execute(edge_vals)
            elif self.hplan is not None:
                res = self.hplan.execute_coded_sparse(edge_vals, self.htables)
            else:
                res = self.plan.execute_sparse(edge_vals, self.mode,
                                               self.tables)
            with tr.span("phase.reduce", nnz=g.csr.nnz):
                state = _reduce_sparse(program, g, edge_vals, res,
                                       self.tables.gather, state)
            return state, res.bits_sent
        with tr.span("phase.map"):
            values = program.map_values(g, state).astype(np.float32)
        if not self.distributed:
            with tr.span("phase.reduce"):
                return program.reduce(values, g.adj, state, g), 0
        if self.mode in PLAN_MODES:
            res = self.plan.execute(values, self.mode)
            with tr.span("phase.reduce"):
                return _reduce_plan(program, g, alloc, values, res,
                                    state), res.bits_sent
        if self.mode == "coded-ref":
            with tr.span("phase.exchange", mode=self.mode) as sp:
                ref = run_coded(g.adj, values, alloc)
                delivered, bits = ref.delivered, ref.bits_sent
                bits += _unicast_leftovers(g, alloc, values, delivered)
                sp.set(bits=bits)
            with tr.span("phase.reduce"):
                return _reduce_distributed(program, g, alloc, values,
                                           delivered, state), bits
        raise ValueError(f"unknown mode {self.mode!r}")

    def run(self, iters: int, state: np.ndarray | None = None, *,
            start_iter: int = 0, start_bits: int = 0,
            checkpoint=None, checkpoint_every: int = 1,
            fault_schedule=None) -> EngineResult:
        """Execute `iters` rounds from `program.init` (or a given state).

        `start_iter`/`start_bits` resume a checkpointed run: iteration
        indices continue from `start_iter` (fault-schedule boundaries and
        checkpoint epochs line up with the uninterrupted run) and the
        returned `shuffle_bits` is cumulative from `start_bits`.

        `checkpoint` (a `core.checkpoint.SessionCheckpointer`) persists
        (iteration, state, cumulative bits, current allocation) every
        `checkpoint_every` iterations and always after the final one;
        saves are atomic and run on a background thread, so a crash
        mid-save never corrupts the newest complete epoch.

        `fault_schedule` (a `faults.FaultSchedule`) applies crash /
        straggle / recover events at iteration boundaries: crashes swap in
        the repaired coded session (`fail`), recovers swap the original
        back, stragglers re-price the Shuffle per the hand-over rule
        (values are unaffected). The result's `.faults` is the `FaultLog`.
        """
        state = (self.program.init(self.g) if state is None
                 else np.asarray(state, dtype=np.float32))
        total_bits = start_bits
        cur, log = self, None
        failed: set[int] = set()
        straggling: set[int] = set()
        crash_pending = False
        if fault_schedule is not None:
            from .faults import FaultLog
            log = FaultLog()
        tr = get_tracer()
        B0 = 1 if state.ndim == 1 else state.shape[1]
        with tr.span("engine.run", mode=self.mode, backend=self.backend,
                     iters=iters, B=B0) as run_sp:
            for it in range(start_iter, start_iter + iters):
                with tr.span("engine.iteration", iteration=it) as it_sp:
                    if fault_schedule is not None:
                        cur, crashed = self._apply_events(
                            cur, fault_schedule.at(it), failed, straggling,
                            log)
                        crash_pending |= crashed
                    state, bits = cur._step(state)
                    B = 1 if state.ndim == 1 else state.shape[1]
                    if straggling and cur.mode in ("coded", "coded-fast"):
                        from .faults import _straggler_bits_plan
                        bits = _straggler_bits_plan(
                            cur.plan, tuple(sorted(straggling))) * B
                        if cur.mode == "coded":
                            bits += cur.plan.leftover_bits * B
                    if log is not None and straggling:
                        log.straggled_iters += 1
                    if cur.recovery is not None:
                        bits += cur.recovery.handover_bits * B
                        if log is not None:
                            log.handover_bits += \
                                cur.recovery.handover_bits * B
                    if crash_pending:
                        log.recovery_bits += bits
                        crash_pending = False
                    total_bits += bits
                    it_sp.set(bits=bits)
                    if checkpoint is not None and (
                            (it + 1 - start_iter) % max(checkpoint_every, 1)
                            == 0 or it == start_iter + iters - 1):
                        checkpoint.save(it + 1, state, total_bits, cur.alloc)
            run_sp.set(shuffle_bits=total_bits - start_bits)
        return EngineResult(state, start_iter + iters, total_bits, self.mode,
                            faults=log)

    def run_batch(self, states, iters: int) -> EngineResult:
        """Run B queries on ONE Shuffle exchange per iteration.

        `states` is [n, B] (or a sequence of B [n] columns, stacked here).
        The program must be batch-polymorphic (`multi_sssp`,
        `personalized_pagerank`, or any program whose map/reduce broadcast
        over a trailing query axis). Result state is [n, B]; `shuffle_bits`
        is exactly B x the single-query schedule bits.
        """
        if not self.sparse:
            raise ValueError(
                "run_batch needs the sparse path (dense [n, n] value "
                "matrices have no query axis)")
        if isinstance(states, (list, tuple)):
            st = np.stack([np.asarray(s, dtype=np.float32) for s in states],
                          axis=1)
        else:
            st = np.asarray(states, dtype=np.float32)
        if st.ndim != 2 or st.shape[0] != self.g.n:
            raise ValueError(
                f"states must be [n={self.g.n}, B]; got shape {st.shape}")
        return self.run(iters, state=st)

    def loads(self) -> dict[str, float]:
        """Exact Definition-2 loads of this session's schedule (no data
        moves; see `loads.empirical_loads`)."""
        if self.plan is None:
            raise ValueError(
                "loads() needs a compiled plan (a distributed plan mode)")
        from .loads import empirical_loads
        return empirical_loads(
            self.hplan if self.hplan is not None else self.plan, self.alloc,
            topology=self.topology)


def compile(program: VertexProgram, g: Graph, alloc: Allocation | None,
            mode: str = "coded", *, path: str = "auto",
            backend: str = "numpy",
            plan: ShufflePlan | HierarchicalPlan | None = None,
            backend_opts: dict | None = None,
            topology: Topology | None = None, **opts) -> CompiledEngine:
    """Compile a reusable execution session (see `CompiledEngine`).

    Backend options may be passed inline (``compile(..., backend="spmv",
    bm=256)``) or via `backend_opts=`; both are validated against the
    backend's accepted set. Pass a pre-compiled `plan` to share a schedule
    across sessions. A non-flat `topology` compiles the two-level
    hierarchical Shuffle (`shuffle_plan.compile_hierarchical`): coded across
    racks, plain within them, delivered words bitwise equal to the flat
    plan's.
    """
    merged = dict(backend_opts or {})
    merged.update(opts)
    return CompiledEngine(program, g, alloc, mode, path=path,
                          backend=backend, plan=plan, backend_opts=merged,
                          topology=topology)


def run(program: VertexProgram, g: Graph, alloc: Allocation | None,
        iters: int, mode: str = "coded",
        plan: ShufflePlan | HierarchicalPlan | None = None, *,
        path: str = "auto", backend: str = "numpy",
        backend_opts: dict | None = None,
        topology: Topology | None = None) -> EngineResult:
    """One-shot wrapper: `compile(...)` + `.run(iters)` (back-compat form).

    `path` picks the execution form (see module docstring); "auto" resolves
    to sparse whenever the program supplies the edge-value form. `backend`
    ("numpy" | "spmv" | "fused") selects the sparse implementation;
    `backend_opts` is forwarded to it (spmv: `bm`, `interpret`; fused:
    `mesh`, `encode`, `interpret` - see `fused_shuffle.FusedSparseShuffle`).
    Pallas kernels run compiled on a TPU and interpreted on the CPU unless
    `interpret` says otherwise (`kernels.platform`). Unknown option
    keys raise `ValueError` naming the accepted set. Prefer `compile` when
    running the same (graph, allocation) more than once.
    """
    return compile(program, g, alloc, mode, path=path, backend=backend,
                   plan=plan, backend_opts=backend_opts,
                   topology=topology).run(iters)


def restore(directory, program: VertexProgram, g: Graph, *,
            K: int | None = None, mode: str = "coded", path: str = "auto",
            backend: str = "numpy", backend_opts: dict | None = None,
            topology: Topology | None = None, epoch: int | None = None):
    """Rebuild a session from the newest complete checkpoint under
    `directory`; returns `(CompiledEngine, SessionCheckpoint)`.

    The checkpoint carries the exact allocation (fingerprint-verified), so
    the default restore recompiles the *same* schedule and
    ``eng.run(remaining, state=ckpt.state, start_iter=ckpt.iteration,
    start_bits=ckpt.shuffle_bits)`` resumes bitwise-identically to the
    uninterrupted run (the sparse Reduce gathers in canonical CSR entry
    order, so even float-sum programs are insensitive to the allocation).
    Pass `K` != the checkpointed K for an *elastic* restore: the allocation
    is re-derived via `faults.rebalance` and a fresh plan compiled - state
    still resumes bitwise-identically, only the schedule (bits) changes.
    `epoch` pins a specific checkpoint instead of the newest.
    """
    from .checkpoint import load_checkpoint

    ckpt = load_checkpoint(directory, epoch=epoch)
    if ckpt.state.shape[0] != g.n:
        raise ValueError(
            f"checkpoint state has n={ckpt.state.shape[0]} but graph has "
            f"n={g.n}")
    alloc = ckpt.alloc
    if K is not None and alloc is not None and K != alloc.K:
        from .faults import rebalance
        alloc = rebalance(alloc, K)
    eng = compile(program, g, alloc, mode, path=path, backend=backend,
                  backend_opts=backend_opts, topology=topology)
    return eng, ckpt


def _unicast_leftovers(g: Graph, alloc: Allocation, values: np.ndarray,
                       delivered: dict[int, dict[tuple[int, int], float]]) -> int:
    """Unicast whatever the coded groups did not cover (e.g. the phase-III
    spill Reducers of the bi-partite allocation, Appendix A)."""
    bits = 0
    for k in range(alloc.K):
        for i, j in missing_pairs(g.adj, alloc, k):
            if (int(i), int(j)) not in delivered[k]:
                delivered[k][(int(i), int(j))] = float(values[i, j])
                bits += T_BITS
    return bits
