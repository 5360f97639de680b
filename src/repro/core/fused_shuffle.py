"""Multi-device coded Shuffle under shard_map (devices = servers).

The literal scheme multicasts per (r+1)-group columns one at a time - fine on
an Ethernet bus, wrong on an ICI torus. Here every server packs ALL its coded
columns (across all groups it serves) into one dense uint32 buffer and a
single jax.lax.all_gather moves every buffer to every server in one fused
collective; receivers slice their groups and XOR-strip locally (kernels/
xor_code). Bit volume on the wire equals the literal schedule's (padding
aside); latency collapses from O(#groups * #columns) transmissions to one
collective phase - this is the hardware adaptation of the paper's shared-bus
assumption.

Two executors share that design:

  * **Sparse (production path)** - `partition_plan` splits a compiled CSR
    `ShufflePlan` per server: each device holds only its own slice of the
    Map output (`loc_e`, the [nnz]-indexed values it Mapped, O(r nnz / K))
    plus its encode/decode/strip tables (O(plan / K)). The host gathers
    each server's words straight into the slot order of its encode and
    strip tables. One iteration under `shard_map` on a ('servers',) mesh is
    then (a) per-shard shift-mask + XOR encode through the batched
    `kernels/xor_code` route, (b) one packed dense all_gather of uint32
    coded words, (c) per-shard delivery gather, strip + shift-back into
    each receiver's delivery slice. No [n, n] or O(n^2)-shaped array
    exists anywhere on this path; `FusedSparseShuffle` jits the exchange
    once and replays it every iteration, bit-exact against
    `ShufflePlan.execute_coded_sparse` (unicast leftovers ride the same
    all_gather as single-slot full-width columns).

  * **Dense (small-n validation reference)** - `fused_exchange` consumes a
    replicated [n, n] value matrix through [n, n]-indexed schedule tensors;
    kept only to cross-check the collective layout at validation scale.

The column/slot structure comes straight off the compiled `ShufflePlan`
(compile-once) via `compile_plan_csr` - `build_schedule` accepts a `Graph`
and never touches `.adj`, so schedule construction works on CSR-native
graphs beyond `dense_limit`.

Word format: one uint32 per coded column and slot, in *codec bit order*
(`bitcodec.floats_to_words`), so segment s of a value travels left-aligned
as ``(word << shift_s) & mask_s`` - identical bit semantics to the NumPy
plan executor, which is what makes the device path bitwise comparable.

**Topology-aware two-level path.** Given a non-flat `Topology` (racks x
servers) and a `HierarchicalPlan`, the exchange runs on a
('racks', 'servers') mesh in two collectives: a *plain* all_gather of the
local Map words on the cheap 'servers' (intra-rack) axis, then the coded XOR
all_gather of rack-level packed buffers on the expensive 'racks' axis -
every rack encodes from its phase-A union buffer (replicated within the
rack, so recompute beats a leader branch), and each server decodes its own
delivery slice from the rack buffers plus direct intra-rack gathers.
Delivered words stay bitwise equal to the flat path (`partition_plan` /
`FusedSparseShuffle` accept a `Topology` and degenerate to the single-level
exchange on `Topology.flat(K)`).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.platform import interpret_mode
from ..kernels.xor_code import ops as xor_ops
from ..launch.mesh import Topology, make_racks_mesh, make_servers_mesh
from ..obs import get_tracer
from ..obs.metrics import get_registry
from .allocation import Allocation
from .bitcodec import floats_to_words, words_to_floats
from .graph_models import CSR, Graph
from .shuffle_plan import (HierarchicalPlan, PlanShuffleResult, ShufflePlan,
                           _rack_first_mapper, _run_ranks, compile_plan_csr)

FULL_MASK = np.uint32(0xFFFFFFFF)


def _sender_layout(plan: ShufflePlan) -> tuple[np.ndarray, np.ndarray]:
    """Per-sender packing of the plan's coded columns.

    Deterministic order within each sender: (group, in-group column rank).
    Returns (colpos [C] - position of column c in its sender's buffer,
    ncols [K] - coded-column count per sender).
    """
    order = np.lexsort((plan.col_rank, plan.col_gm, plan.col_sender))
    _, rank = _run_ranks(plan.col_sender[order])
    colpos = np.empty(plan.col_sender.size, dtype=np.int64)
    colpos[order] = rank
    ncols = np.bincount(plan.col_sender, minlength=plan.K)
    return colpos, ncols


# ---------------------------------------------------------------------------
# Sparse multi-device path (production)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedSparseSchedule:
    """Per-server partition of a compiled CSR plan (all arrays plan-sized).

    Device k's shard (row k of every array) is everything it needs for one
    coded Shuffle: `loc_e` selects the [nnz] edge values it Mapped (column
    vertex in M_k - O(r nnz / K) entries), the `enc_*` tables lay its coded
    columns (+ its unicast leftovers, as single-slot full-width columns)
    into a [W]-word buffer, and the `dec_*`/`strip_*` tables recover its
    delivery slice from the all_gathered [K, W] buffer matrix.

    `enc_e` and `strip_e` are `enc_l` and `strip_l` composed with `loc_e`:
    the CSR entry of every encode and strip slot. The flat exchange's host
    side gathers the edge words through them straight into slot order, so
    its device program reads every encode and strip word in place; the
    local-index tables stay for the two-level partition and the locality
    checks.

    Sentinels: local index `Lmax` and CSR entry `nnz` are guaranteed-zero
    words; buffer column `W` is a guaranteed-zero column (padded after the
    all_gather); masks of sentinel slots are 0, so they OR/XOR away -
    encode and decode are plain shift-mask pipelines with no control flow.
    """

    K: int
    r: int
    W: int                        # per-sender buffer width (words)
    Lmax: int                     # max local-value count over servers
    Dmax: int                     # max delivery count over receivers
    loc_e: np.ndarray             # [K, Lmax] int64 CSR entry (nnz = zero pad)
    enc_l: np.ndarray             # [K, W, r] int32 local index (Lmax = zero)
    enc_shift: np.ndarray         # [K, W, r] uint32 segment left-shift
    enc_mask: np.ndarray          # [K, W, r] uint32 segment keep-mask
    dec_s: np.ndarray             # [K, Dmax, r] int32 sender of segment t
    dec_w: np.ndarray             # [K, Dmax, r] int32 buffer column (W = zero)
    dec_mask: np.ndarray          # [K, Dmax, r] uint32 own-slot keep-mask
    dec_shift: np.ndarray         # [K, Dmax, r] uint32 shift back into place
    strip_l: np.ndarray           # [K, Dmax, r, r-1] int32 local index
    strip_shift: np.ndarray       # [K, Dmax, r, r-1] uint32
    strip_mask: np.ndarray        # [K, Dmax, r, r-1] uint32
    enc_e: np.ndarray             # [K, W, r] int32 CSR entry (nnz = zero)
    strip_e: np.ndarray           # [K, Dmax, r, r-1] int32 CSR entry (nnz = zero)


def partition_plan(plan: ShufflePlan | HierarchicalPlan, csr: CSR,
                   alloc: Allocation, topology: Topology | None = None):
    """Partition a compiled plan per server for the fused sparse path.

    Pure compile-time layout (no data): every output array is [nnz]- or
    [plan]-sized. Unicast leftovers are assigned to the smallest server
    that Mapped their column vertex and appended to that sender's buffer as
    single-slot full-width columns, so they ride the same all_gather.

    Topology-aware form: a `HierarchicalPlan` (its `Topology` non-flat)
    routes to `partition_hierarchical`; `Topology.flat(K)` degenerates to
    the single-level partition of the plan's flat schedule.
    """
    if isinstance(plan, HierarchicalPlan):
        if topology is not None and topology != plan.topology:
            raise ValueError(
                f"topology {topology} disagrees with the plan's "
                f"{plan.topology}")
        if not plan.topology.is_flat:
            return partition_hierarchical(plan, csr, alloc)
        plan = plan.flat
    elif topology is not None and not topology.is_flat:
        raise ValueError(
            "a non-flat Topology needs a HierarchicalPlan "
            "(core.shuffle_plan.compile_hierarchical), got a flat "
            "ShufflePlan")
    plan._require_schedule()
    tables = plan.edge_tables(csr, alloc)     # locates edges + validates
    K, r = plan.K, plan.r
    C = plan.col_sender.size
    Pn = plan.pair_k.size
    L = plan.left_k.size
    nstrip = max(r - 1, 0)

    colpos, ncols = _sender_layout(plan)

    # Leftover layout: sender = smallest mapper of the column vertex,
    # appended after that sender's coded columns (stable (k, i, j) order).
    if L:
        lsender = np.argmax(alloc.map_sets[:, plan.left_j], axis=0)
        if not alloc.map_sets[lsender, plan.left_j].all():
            raise RuntimeError("leftover value has no Mapping server")
        lorder = np.argsort(lsender, kind="stable")
        _, lrank = _run_ranks(lsender[lorder])
        leftw = np.empty(L, dtype=np.int64)
        leftw[lorder] = ncols[lsender[lorder]] + lrank
        nleft = np.bincount(lsender, minlength=K)
    else:
        lsender = np.zeros(0, dtype=np.int64)
        leftw = np.zeros(0, dtype=np.int64)
        nleft = np.zeros(K, dtype=np.int64)
    W = max(int((ncols + nleft).max()), 1)

    # Per-server local Map slices: CSR entries whose column vertex the
    # server Mapped (it can recompute exactly these values locally).
    member = alloc.map_sets[:, csr.indices]             # [K, nnz] bool
    counts = member.sum(axis=1)
    Lmax = max(int(counts.max()), 1)
    loc_e = np.full((K, Lmax), csr.nnz, dtype=np.int64)  # nnz = zero pad

    # --- encode tables: valid plan slots + leftover slots, per sender ---
    enc_l = np.full((K, W, r), Lmax, dtype=np.int32)     # Lmax = zero word
    enc_shift = np.zeros((K, W, r), dtype=np.uint32)
    enc_mask = np.zeros((K, W, r), dtype=np.uint32)
    cs, sl = np.nonzero(plan.slot_pair < Pn) if C else (
        np.zeros(0, np.int64), np.zeros(0, np.int64))
    e_of_slot = tables.pair_e[plan.slot_pair[cs, sl]] if cs.size else cs
    s_of_slot = plan.col_sender[cs] if cs.size else cs

    # --- decode tables, first in flat (k, i, j) delivery order ---
    M = plan.all_k.size
    f_s = np.zeros((M, r), dtype=np.int32)
    f_w = np.full((M, r), W, dtype=np.int32)             # W = zero column
    f_mask = np.zeros((M, r), dtype=np.uint32)
    f_shift = np.zeros((M, r), dtype=np.uint32)
    f_sl = np.full((M, r, nstrip), Lmax, dtype=np.int32)
    f_ssh = np.zeros((M, r, nstrip), dtype=np.uint32)
    f_smk = np.zeros((M, r, nstrip), dtype=np.uint32)
    if Pn:
        mpos = plan.pos_covered
        c, slot = plan.pair_col, plan.pair_slot          # [P, r]
        f_s[mpos] = plan.col_sender[c]
        f_w[mpos] = colpos[c]
        f_mask[mpos] = plan.slot_mask[c, slot]
        f_shift[mpos] = np.broadcast_to(plan.seg_shift[None, :], (Pn, r))
        if nstrip:
            ar = np.broadcast_to(np.arange(r)[None, None, :], (Pn, r, r))
            others = ar[~(ar == slot[..., None])].reshape(Pn, r, nstrip)
            c3 = np.broadcast_to(c[:, :, None], (Pn, r, nstrip))
            sp = plan.slot_pair[c3, others]              # [P, r, r-1]
            svalid = sp < Pn
            f_ssh[mpos] = plan.slot_shift[c3, others]
            f_smk[mpos] = plan.slot_mask[c3, others]
            e_strip = tables.pair_e[np.minimum(sp, max(Pn - 1, 0))]
    if L:
        f_s[plan.pos_left, 0] = lsender
        f_w[plan.pos_left, 0] = leftw
        f_mask[plan.pos_left, 0] = FULL_MASK             # full word, shift 0

    # --- per-server local index conversions (one vectorized pass each) ---
    for k in range(K):
        lset = np.flatnonzero(member[k])
        loc_e[k, :lset.size] = lset
        lpos = np.cumsum(member[k]) - 1                  # entry -> local idx
        if cs.size:
            m = s_of_slot == k                           # encode slots k sends
            if not member[k][e_of_slot[m]].all():
                raise RuntimeError(f"sender {k} schedules a value it "
                                   "did not Map")
            enc_l[k, colpos[cs[m]], sl[m]] = lpos[e_of_slot[m]]
            enc_shift[k, colpos[cs[m]], sl[m]] = plan.slot_shift[cs[m], sl[m]]
            enc_mask[k, colpos[cs[m]], sl[m]] = plan.slot_mask[cs[m], sl[m]]
        if L:
            m = lsender == k                             # leftovers k unicasts
            if not member[k][tables.left_e[m]].all():
                raise RuntimeError(f"sender {k} unicasts a value it "
                                   "did not Map")
            enc_l[k, leftw[m], 0] = lpos[tables.left_e[m]]
            enc_mask[k, leftw[m], 0] = FULL_MASK         # full word, shift 0
        if Pn and nstrip:
            m = plan.pair_k == k                         # strips k recomputes
            li = np.where(svalid[m], lpos[e_strip[m]], Lmax)
            if not (member[k][e_strip[m]] | ~svalid[m]).all():
                raise RuntimeError(f"receiver {k} must strip a value it "
                                   "did not Map")
            f_sl[plan.pos_covered[m]] = li.astype(np.int32)

    # --- scatter the flat decode tables into per-receiver padded rows ---
    dcount = np.diff(plan.ptr)
    Dmax = max(int(dcount.max()) if K else 0, 1)
    kk = plan.all_k
    dd = np.arange(M, dtype=np.int64) - plan.ptr[kk]
    dec_s = np.zeros((K, Dmax, r), dtype=np.int32)
    dec_w = np.full((K, Dmax, r), W, dtype=np.int32)
    dec_mask = np.zeros((K, Dmax, r), dtype=np.uint32)
    dec_shift = np.zeros((K, Dmax, r), dtype=np.uint32)
    strip_l = np.full((K, Dmax, r, nstrip), Lmax, dtype=np.int32)
    strip_shift = np.zeros((K, Dmax, r, nstrip), dtype=np.uint32)
    strip_mask = np.zeros((K, Dmax, r, nstrip), dtype=np.uint32)
    dec_s[kk, dd] = f_s
    dec_w[kk, dd] = f_w
    dec_mask[kk, dd] = f_mask
    dec_shift[kk, dd] = f_shift
    strip_l[kk, dd] = f_sl
    strip_shift[kk, dd] = f_ssh
    strip_mask[kk, dd] = f_smk

    # --- slot-order CSR entries: local indices composed with loc_e ---
    if csr.nnz >= np.iinfo(np.int32).max:
        raise ValueError(f"nnz={csr.nnz} does not fit int32 CSR entries")
    loc_z = np.concatenate([loc_e, np.full((K, 1), csr.nnz, np.int64)],
                           axis=1).astype(np.int32)      # [Lmax] -> nnz

    def entries(local):
        flat = np.take_along_axis(loc_z, local.reshape(K, -1), axis=1)
        return flat.reshape(local.shape)

    return FusedSparseSchedule(
        K=K, r=r, W=W, Lmax=Lmax, Dmax=Dmax, loc_e=loc_e,
        enc_l=enc_l, enc_shift=enc_shift, enc_mask=enc_mask,
        dec_s=dec_s, dec_w=dec_w, dec_mask=dec_mask, dec_shift=dec_shift,
        strip_l=strip_l, strip_shift=strip_shift, strip_mask=strip_mask,
        enc_e=entries(enc_l), strip_e=entries(strip_l))


@dataclasses.dataclass(frozen=True)
class FusedHierarchicalSchedule:
    """Per-device partition of a `HierarchicalPlan` for the two-level path.

    Phase A all_gathers each server's `loc` words on the 'servers' axis, so
    every server holds its rack's union buffer ``rflat`` of
    ``S * (Lmax + 1)`` words (block s = server s of the rack, word `Lmax`
    of block 0 a guaranteed zero - the sentinel `ZERO = Lmax`). The rack
    encode tables (`enc_*`, one row per *rack*, replicated over its
    servers) index `rflat`; phase B all_gathers the [Wx]-word rack buffers
    on the 'racks' axis. Per-server decode reads coded segments from
    ``allbufs[dec_rk, dec_w]`` (rack column `Wx` = zero pad), strips the
    other slots from `rflat`, and ORs in `direct_l`/`direct_mask` gathers
    for the intra-only deliveries that never crossed a rack.
    """

    K: int
    R: int
    S: int
    rr: int                       # rack-level redundancy (inter.r)
    Wx: int                       # per-rack buffer width (words)
    Lmax: int                     # max local-value count over servers
    Dmax: int                     # max delivery count over receivers
    loc_e: np.ndarray             # [K, Lmax] int64 CSR entry (nnz = zero pad)
    enc_l: np.ndarray             # [R, Wx, rr] int32 into rflat (ZERO = pad)
    enc_shift: np.ndarray         # [R, Wx, rr] uint32
    enc_mask: np.ndarray          # [R, Wx, rr] uint32
    dec_rk: np.ndarray            # [K, Dmax, rr] int32 sending rack
    dec_w: np.ndarray             # [K, Dmax, rr] int32 rack column (Wx = zero)
    dec_mask: np.ndarray          # [K, Dmax, rr] uint32
    dec_shift: np.ndarray         # [K, Dmax, rr] uint32
    strip_f: np.ndarray           # [K, Dmax, rr, rr-1] int32 into rflat
    strip_shift: np.ndarray       # [K, Dmax, rr, rr-1] uint32
    strip_mask: np.ndarray        # [K, Dmax, rr, rr-1] uint32
    direct_l: np.ndarray          # [K, Dmax] int32 into rflat (ZERO = pad)
    direct_mask: np.ndarray       # [K, Dmax] uint32 (FULL for intra-only)


def partition_hierarchical(hplan: HierarchicalPlan, csr: CSR,
                           alloc: Allocation) -> FusedHierarchicalSchedule:
    """Partition a `HierarchicalPlan` per device for the two-level exchange.

    Same compile-time/no-data discipline as `partition_plan`; every value
    read from a rack's phase-A buffer comes from the rack's *designated
    source* (its lowest Mapping server - the same rule the plan's
    `intra_rack_bits` accounting charges), and every coded segment decodes
    bitwise like the NumPy hierarchical executor because identical floats
    produce identical codec words on every holder.
    """
    flat, inter, topo = hplan.flat, hplan.inter, hplan.topology
    R, S = topo.racks, topo.servers_per_rack
    K, rr = flat.K, inter.r
    nstrip = max(rr - 1, 0)
    flat._require_schedule()
    inter._require_schedule()
    ft = flat.edge_tables(csr, alloc)           # locates + validates
    xt = inter.edge_tables(csr, hplan.rack_alloc)
    has = hplan.rack_alloc.map_sets             # [R, n] rack Mapped vertex
    first, _ = _rack_first_mapper(alloc, R, S)

    member = alloc.map_sets[:, csr.indices]     # [K, nnz]
    Lmax = max(int(member.sum(axis=1).max()), 1)
    loc_e = np.full((K, Lmax), csr.nnz, dtype=np.int64)
    for k in range(K):
        lset = np.flatnonzero(member[k])
        loc_e[k, :lset.size] = lset
    lpos_all = np.where(member, np.cumsum(member, axis=1) - 1, 0)
    blk = Lmax + 1
    ZERO = Lmax                                 # rflat[Lmax] == 0 pad word

    def rfidx(rack, j, e):
        """Phase-A buffer position of vertex j's value (CSR entry e) as
        held by `rack`'s designated source server."""
        if not has[rack, j].all():
            raise RuntimeError("hierarchical schedule references a vertex "
                               "its consuming rack never Mapped")
        off = first[rack, j].astype(np.int64)
        src = rack.astype(np.int64) * S + off
        if not member[src, e].all():
            raise RuntimeError("designated in-rack source did not Map its "
                               "assigned value")
        return (off * blk + lpos_all[src, e]).astype(np.int32)

    # --- rack-level sender layout + encode tables (one row per rack) ---
    colpos, ncols = _sender_layout(inter)
    Px = inter.pair_k.size
    Lx = inter.left_k.size
    if Lx:
        lsender = np.argmax(has[:, inter.left_j], axis=0)
        if not has[lsender, inter.left_j].all():
            raise RuntimeError("rack-level leftover has no Mapping rack")
        lorder = np.argsort(lsender, kind="stable")
        _, lrank = _run_ranks(lsender[lorder])
        leftw = np.empty(Lx, dtype=np.int64)
        leftw[lorder] = ncols[lsender[lorder]] + lrank
        nleft = np.bincount(lsender, minlength=R)
    else:
        lsender = np.zeros(0, dtype=np.int64)
        leftw = np.zeros(0, dtype=np.int64)
        nleft = np.zeros(R, dtype=np.int64)
    Wx = max(int((ncols + nleft).max()), 1)

    enc_l = np.full((R, Wx, rr), ZERO, dtype=np.int32)
    enc_shift = np.zeros((R, Wx, rr), dtype=np.uint32)
    enc_mask = np.zeros((R, Wx, rr), dtype=np.uint32)
    if inter.col_sender.size:
        cs, sl = np.nonzero(inter.slot_pair < Px)
        p = inter.slot_pair[cs, sl]
        sr = inter.col_sender[cs]               # sending rack per slot
        enc_l[sr, colpos[cs], sl] = rfidx(sr, inter.pair_j[p], xt.pair_e[p])
        enc_shift[sr, colpos[cs], sl] = inter.slot_shift[cs, sl]
        enc_mask[sr, colpos[cs], sl] = inter.slot_mask[cs, sl]
    if Lx:
        enc_l[lsender, leftw, 0] = rfidx(lsender, inter.left_j, xt.left_e)
        enc_mask[lsender, leftw, 0] = FULL_MASK

    # --- decode tables, first in flat (k, i, j) delivery order ---
    M = flat.all_k.size
    f_rk = np.zeros((M, rr), dtype=np.int32)
    f_w = np.full((M, rr), Wx, dtype=np.int32)
    f_mask = np.zeros((M, rr), dtype=np.uint32)
    f_shift = np.zeros((M, rr), dtype=np.uint32)
    f_sf = np.full((M, rr, nstrip), ZERO, dtype=np.int32)
    f_ssh = np.zeros((M, rr, nstrip), dtype=np.uint32)
    f_smk = np.zeros((M, rr, nstrip), dtype=np.uint32)
    f_dl = np.full(M, ZERO, dtype=np.int32)
    f_dm = np.zeros(M, dtype=np.uint32)

    d_rho = hplan.rack_of[flat.all_k]
    intra = hplan.inter_pos < 0
    if intra.any():
        f_dl[intra] = rfidx(d_rho[intra], flat.all_j[intra], ft.all_e[intra])
        f_dm[intra] = FULL_MASK

    # Inter deliveries: invert the inter plan's pos_covered/pos_left to
    # find which covered pair / leftover each flat delivery resolves to.
    Mx = inter.all_k.size
    kind_left = np.zeros(Mx, dtype=bool)
    kind_left[inter.pos_left] = True
    idx_in = np.empty(Mx, dtype=np.int64)
    idx_in[inter.pos_covered] = np.arange(Px, dtype=np.int64)
    idx_in[inter.pos_left] = np.arange(Lx, dtype=np.int64)
    ms = np.flatnonzero(~intra)
    q = hplan.inter_pos[ms]
    is_l = kind_left[q]
    mc, pc = ms[~is_l], idx_in[q[~is_l]]
    if mc.size:
        c, slot = inter.pair_col[pc], inter.pair_slot[pc]   # [Pc, rr]
        f_rk[mc] = inter.col_sender[c]
        f_w[mc] = colpos[c]
        f_mask[mc] = inter.slot_mask[c, slot]
        f_shift[mc] = np.broadcast_to(inter.seg_shift[None, :],
                                      (mc.size, rr))
        if nstrip:
            ar = np.broadcast_to(np.arange(rr)[None, None, :],
                                 (mc.size, rr, rr))
            others = ar[~(ar == slot[..., None])].reshape(mc.size, rr,
                                                          nstrip)
            c3 = np.broadcast_to(c[:, :, None], (mc.size, rr, nstrip))
            sp = inter.slot_pair[c3, others]
            svalid = sp < Px
            if svalid.any():
                spv = sp[svalid]
                rho3 = np.broadcast_to(d_rho[mc][:, None, None],
                                       sp.shape)[svalid]
                fill = np.full(sp.shape, ZERO, dtype=np.int32)
                fill[svalid] = rfidx(rho3, inter.pair_j[spv],
                                     xt.pair_e[spv])
                f_sf[mc] = fill
            f_ssh[mc] = inter.slot_shift[c3, others]
            f_smk[mc] = inter.slot_mask[c3, others]
    ml, pl = ms[is_l], idx_in[q[is_l]]
    if ml.size:
        f_rk[ml, 0] = lsender[pl]
        f_w[ml, 0] = leftw[pl]
        f_mask[ml, 0] = FULL_MASK               # full word, shift 0

    # --- scatter into per-receiver padded rows (flat per-server CSR) ---
    Dmax = max(int(np.diff(flat.ptr).max()) if K else 0, 1)
    kk = flat.all_k
    dd = np.arange(M, dtype=np.int64) - flat.ptr[kk]
    dec_rk = np.zeros((K, Dmax, rr), dtype=np.int32)
    dec_w = np.full((K, Dmax, rr), Wx, dtype=np.int32)
    dec_mask = np.zeros((K, Dmax, rr), dtype=np.uint32)
    dec_shift = np.zeros((K, Dmax, rr), dtype=np.uint32)
    strip_f = np.full((K, Dmax, rr, nstrip), ZERO, dtype=np.int32)
    strip_shift = np.zeros((K, Dmax, rr, nstrip), dtype=np.uint32)
    strip_mask = np.zeros((K, Dmax, rr, nstrip), dtype=np.uint32)
    direct_l = np.full((K, Dmax), ZERO, dtype=np.int32)
    direct_mask = np.zeros((K, Dmax), dtype=np.uint32)
    dec_rk[kk, dd] = f_rk
    dec_w[kk, dd] = f_w
    dec_mask[kk, dd] = f_mask
    dec_shift[kk, dd] = f_shift
    strip_f[kk, dd] = f_sf
    strip_shift[kk, dd] = f_ssh
    strip_mask[kk, dd] = f_smk
    direct_l[kk, dd] = f_dl
    direct_mask[kk, dd] = f_dm

    return FusedHierarchicalSchedule(
        K=K, R=R, S=S, rr=rr, Wx=Wx, Lmax=Lmax, Dmax=Dmax, loc_e=loc_e,
        enc_l=enc_l, enc_shift=enc_shift, enc_mask=enc_mask,
        dec_rk=dec_rk, dec_w=dec_w, dec_mask=dec_mask, dec_shift=dec_shift,
        strip_f=strip_f, strip_shift=strip_shift, strip_mask=strip_mask,
        direct_l=direct_l, direct_mask=direct_mask)


ENCODE_BACKENDS = ("xor-ref", "xor-kernel", "jnp")


def _fold(a, axis: int, op):
    """uint32 XOR/OR reduction of one (small, static) axis."""
    return jax.lax.reduce(a, jnp.uint32(0), op, (axis % a.ndim,))


def _gather_servers(words, axis_name: str):
    """all_gather of per-shard [(B,) N] words -> [(B,) servers, N].

    The gather runs on a new leading axis: gathering along a minor axis
    makes XLA move the payload axis B onto the TPU's lanes, where it is
    padded to 128 (16x at B=8).
    """
    return jnp.moveaxis(jax.lax.all_gather(words, axis_name), 0, -2)


def _pad_last(a):
    """Append one zero column on the last axis (the sentinel column)."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, 1),))


def take(words, *idx):
    """``words[..., *idx]`` with at most one leading payload axis, one
    payload row at a time.

    A single gather over [B, N] words lets XLA lay the operand out with the
    B axis minor (each gathered slice a contiguous B-vector), which a TPU
    pads to 128 lanes; a loop of 1-D gathers keeps N on the lanes.
    """
    if words.ndim == len(idx):
        return words[idx]
    return jax.lax.map(lambda row: row[idx], words)


def lanes_last(a: np.ndarray) -> np.ndarray:
    """Device layout of a partition table: [rows, W or Dmax, slots...] ->
    [rows, slots..., W or Dmax].

    On a TPU the last axis is the 128-lane axis, and a trailing slot axis
    of r (or r-1) words would be padded to 128 lanes - a 64x blow-up that
    does not fit the chip at deployment size.
    """
    return np.ascontiguousarray(np.moveaxis(a, 1, -1))


def receiver_spans(plan: ShufflePlan) -> tuple[np.ndarray, np.ndarray]:
    """(start [K], count [K]) of each receiver's deliveries in [M] order.

    Every plan constructor groups the deliveries by receiver: receiver k's
    are `all_k[ptr[k]:ptr[k+1]]`, so delivery m sits at column m - ptr[k]
    of k's padded row (as `partition_plan` lays the decode tables). Raises
    ValueError on a plan that is not grouped as its `ptr` says.
    """
    K, M = plan.K, plan.all_k.size
    ptr = np.asarray(plan.ptr, np.int64)
    counts = np.diff(ptr)
    grouped = (ptr.shape == (K + 1,) and ptr[0] == 0 and ptr[-1] == M
               and (counts >= 0).all()
               and all((plan.all_k[a:b] == k).all()
                       for k, (a, b) in enumerate(zip(ptr[:-1], ptr[1:]))))
    if not grouped:
        raise ValueError("the plan's deliveries are not grouped by receiver "
                         "as its ptr says (all_k[ptr[k]:ptr[k+1]] == k)")
    return ptr[:-1], counts


def gather_deliveries(out: np.ndarray, starts: np.ndarray,
                      counts: np.ndarray) -> np.ndarray:
    """Padded per-receiver deliveries [K, (B,) Dmax] -> [M] or [M, B] in the
    plan's delivery order (`receiver_spans`): K contiguous copies into a
    fresh C-contiguous array."""
    res = np.empty((int(counts.sum()),) + out.shape[1:-1], out.dtype)
    for k, (a, c) in enumerate(zip(starts.tolist(), counts.tolist())):
        res[a:a + c] = out[k, ..., :c].T
    return res


def shard_encoder(encode: str, interpret: bool):
    """The exchange's per-shard encode for one `encode` backend.

    words [(B,) r, W] uint32, the server's value word of every encode slot
    in slot order (0 in sentinel slots); shift, mask [r, W], one server's
    `lanes_last` encode tables -> [(B,) W] coded columns. The flat exchange
    passes its operand's slot words as they arrive; the two-level exchange
    gathers them from its phase-A buffer first. "xor-ref" and "xor-kernel"
    fold the slots through `xor_ops.xor_encode_rows` (jnp oracle / Pallas
    kernel), "jnp" through a plain XOR reduce; all three give the same
    words.
    """
    if encode not in ENCODE_BACKENDS:
        raise ValueError(f"unknown encode backend {encode!r}")

    def enc(words, shift, mask):
        slotw = (words << shift) & mask                     # [(B,) r, W]
        if encode == "jnp":
            return _fold(slotw, -2, jax.lax.bitwise_xor)
        rows = jnp.moveaxis(slotw, -2, 0)                   # [r, (B,) W]
        out = xor_ops.xor_encode_rows(
            rows.reshape(rows.shape[0], -1),
            use_kernel=encode == "xor-kernel", interpret=interpret)
        return out.reshape(rows.shape[1:])
    return enc


class FusedSparseShuffle:
    """Jit-once / replay-every-iteration multi-device coded Shuffle.

    Wraps a compiled plan's per-server partition and the jitted shard_map
    exchange. `execute` is a drop-in peer of
    `ShufflePlan.execute_coded_sparse`: same [nnz] edge-value input, same
    `PlanShuffleResult` (bitwise-equal uint32 words, same bit accounting).

    Given a `HierarchicalPlan` (or a non-flat `topology=` plus one), the
    exchange runs the two-level ('racks' x 'servers') pipeline instead -
    see the module docstring - with `bits_sent` split into
    inter-rack/intra-rack on the exchange span.
    `Topology.flat(K)` degenerates to the single-level exchange.

    Instrumentation (all of it off the device program's data path): host
    spans `exchange.partition`, `exchange.place_tables`, `exchange.place`,
    `exchange.first_call` / `exchange.run`, `decode.pull`, `decode.reindex`
    and `phase.codec`; device scopes `exchange.encode`, `exchange.gather`
    and `exchange.decode` (`jax.named_scope`, HLO metadata only); and the
    registry counters `exchange_calls_total`,
    `exchange_host_copy_bytes_total`, `exchange_bits_useful_total` and
    `exchange_bits_sent_total`.

    encode:
      "xor-ref"    - batched kernels/xor_code route, jnp oracle (default).
      "xor-kernel" - same route through the Pallas kernel: compiled when
                     the mesh's devices are TPUs, interpreted on CPU devices
                     (`kernels.platform.interpret_mode`; `interpret=`
                     overrides).
      "jnp"        - plain jnp XOR reduce (no kernel route).
    """

    def __init__(self, plan: ShufflePlan | HierarchicalPlan, csr: CSR,
                 alloc: Allocation, mesh: Mesh | None = None, *,
                 topology: Topology | None = None, encode: str = "xor-ref",
                 interpret: bool | None = None):
        if encode not in ENCODE_BACKENDS:
            raise ValueError(f"unknown encode backend {encode!r}")
        self._bind(plan, csr, alloc, topology)
        if mesh is None:
            mesh = (make_racks_mesh(self.topology) if self._hier
                    else make_servers_mesh(self.plan.K))
        self.mesh = mesh
        if self.mesh.devices.size != self.plan.K:
            raise ValueError(
                f"mesh has {self.mesh.devices.size} devices but the plan "
                f"has K={self.plan.K} servers (one device per server)")
        self._encode = encode
        self._interpret = interpret_mode(
            interpret, self.mesh.devices.flat[0].platform)
        build = self._build_hier if self._hier else self._build
        # One jitted exchange serves [nnz] and [nnz, B] payloads alike: jit
        # traces each payload shape once and replays it.
        self._fn = build(encode, self._interpret)
        self._seen = set()                    # call shapes `_fn` has compiled
        self._dev_tables = None               # placed on the first exchange

    def _bind(self, plan, csr, alloc, topology) -> None:
        """Resolve (plan, topology) into the flat or two-level partition.

        A `HierarchicalPlan` carries its own Topology; `Topology.flat(K)`
        (or no topology) degenerates to the single-level exchange on the
        plan's flat schedule.
        """
        if isinstance(plan, HierarchicalPlan):
            if topology is not None and topology != plan.topology:
                raise ValueError(
                    f"topology {topology} disagrees with the plan's "
                    f"{plan.topology}")
            topology = plan.topology
            if topology.is_flat:
                plan = plan.flat
        elif topology is not None and not topology.is_flat:
            raise ValueError(
                "a non-flat Topology needs a HierarchicalPlan "
                "(core.shuffle_plan.compile_hierarchical), got a flat "
                "ShufflePlan")
        self.topology = topology
        self._hier = isinstance(plan, HierarchicalPlan)
        # The pull-back's re-index: K contiguous copies (`gather_deliveries`)
        self._starts, self._counts = receiver_spans(
            plan.flat if self._hier else plan)
        with get_tracer().span("exchange.partition", hier=self._hier):
            if self._hier:
                self.hplan = plan
                self.plan = plan.flat
                self.sched = partition_hierarchical(plan, csr, alloc)
                self._schedule_bits = (plan.inter_rack_bits
                                       + plan.intra_rack_bits)
            else:
                self.hplan = None
                self.plan = plan
                self.sched = partition_plan(plan, csr, alloc)
                self._schedule_bits = plan.coded_bits + plan.leftover_bits
        s = self.sched
        if self._counts.max(initial=0) > s.Dmax:
            raise ValueError(f"a receiver has {self._counts.max()} deliveries "
                             f"but its padded row holds Dmax={s.Dmax}")
        # Words the chips put into the collectives per payload column, each
        # buffer counted once (a multicast): the K coded [W] buffers, or on
        # two levels the R rack buffers plus every server's phase-A words.
        self._sent_words = (s.R * s.Wx + s.K * (s.Lmax + 1) if self._hier
                            else s.K * s.W)
        self._nnz = csr.nnz
        if not self._hier:
            # The flat operand's gather index, [K, r W + r (r-1) Dmax] CSR
            # entries laid as the device tables are (`lanes_last`); intp,
            # so `np.take` converts nothing per call.
            self._slot_e = np.concatenate(
                [lanes_last(s.enc_e).reshape(s.K, -1),
                 lanes_last(s.strip_e).reshape(s.K, -1)],
                axis=1).astype(np.intp)
        self._bufs = {}                       # host buffers per payload shape

    def _in_specs(self) -> tuple:
        """The exchange's shard_map in_specs: the word operand, then the
        device tables.

        Flat: every array is split one server per device. Two levels: loc
        and the per-server tables split over (racks, servers); the rack
        encode tables split over racks, replicated within a rack.
        """
        if self._hier:
            return ((P("racks", "servers"),) + (P("racks"),) * 3
                    + (P("racks", "servers"),) * 9)
        return (P("servers"),) * 9

    def operand_shape(self, B: int | None = None) -> tuple:
        """Shape of the word operand `exchange_device` places per call, for
        an [nnz] payload (B None) or an [nnz, B] one.

        Flat: [K, (B,) r W + r (r-1) Dmax], every server's encode words
        then its strip words, in the device tables' slot order. Two levels:
        [R, S, (B,) Lmax+1], every server's local Map words and the zero
        sentinel, which phase A all_gathers within the rack.
        """
        s, lead = self.sched, (() if B is None else (B,))
        if self._hier:
            return ((self.topology.racks, self.topology.servers_per_rack)
                    + lead + (s.Lmax + 1,))
        return (s.K,) + lead + (self._slot_e.shape[1],)

    def _place(self, arrays, specs) -> tuple:
        """Host arrays -> device arrays laid out as the exchange reads them:
        each device receives only its own block (nothing is staged whole
        on one device and resharded per call)."""
        return tuple(jax.device_put(a, NamedSharding(self.mesh, p))
                     for a, p in zip(arrays, specs))

    def _host_tables(self) -> tuple:
        """The partition tables as the exchange takes them (`lanes_last`,
        mesh-shaped), still on the host."""
        s = self.sched
        if self._hier:
            R, S = self.topology.racks, self.topology.servers_per_rack

            def rs(a):
                # per-server rows -> mesh-shaped (racks, servers) blocks
                return a.reshape((R, S) + a.shape[1:])

            return (
                lanes_last(s.enc_l), lanes_last(s.enc_shift),
                lanes_last(s.enc_mask),
                *(rs(lanes_last(a)) for a in (
                    s.dec_rk, s.dec_w, s.dec_mask, s.dec_shift, s.strip_f,
                    s.strip_shift, s.strip_mask)),
                rs(s.direct_l), rs(s.direct_mask))
        return tuple(lanes_last(a) for a in (
            s.enc_shift, s.enc_mask, s.dec_s, s.dec_w, s.dec_mask,
            s.dec_shift, s.strip_shift, s.strip_mask))

    def rebind(self, plan: ShufflePlan | HierarchicalPlan, csr: CSR,
               alloc: Allocation) -> "FusedSparseShuffle":
        """New exchange bound to a mutated (plan, csr) on this instance's
        jitted callables.

        `CompiledEngine.update`'s hook: the per-server partition and device
        tables are rebuilt for the new plan (they index CSR entries, so any
        real delta moves them), but the traced shard_map exchange, mesh,
        and backend flags carry over - the tables are jit *arguments*, so
        XLA re-lowers only if the partition's padded shapes (W, Lmax, Dmax)
        actually changed, and replays the cached executable otherwise.
        A two-level instance expects a fresh `HierarchicalPlan` on the same
        Topology (repair keeps the rack structure).
        """
        ex = object.__new__(FusedSparseShuffle)
        ex._bind(plan, csr, alloc, self.topology)
        if ex._hier != self._hier:
            raise ValueError("rebind cannot switch between the flat and "
                             "two-level exchange; build a new instance")
        ex.mesh = self.mesh
        ex._encode = self._encode
        ex._interpret = self._interpret
        ex._fn = self._fn
        ex._seen = self._seen
        ex._dev_tables = None
        return ex

    def _build(self, encode: str, interpret: bool):
        # Batched payloads put one B axis *before* every word array's long
        # axis (words [B, N], buffers [B, K, W], deliveries [B, Dmax]);
        # the schedule tables are value-agnostic and broadcast against it.
        # All device ops stay uint32 shift/mask/XOR, so payload column b is
        # bitwise the unbatched exchange of column b.
        encode_shard = shard_encoder(encode, interpret)

        def per_server(words, enc_shift, enc_mask, dec_s, dec_w, dec_mask,
                       dec_shift, strip_shift, strip_mask):
            # The operand holds the encode then the strip slot words in the
            # tables' own order (`operand_shape`): no local-word gather. The
            # split follows the tables' shapes, which a `rebind` may change.
            words = words[0]                      # [(B,) r W + r(r-1) Dmax]
            lead, nenc = words.shape[:-1], enc_shift[0].size
            with jax.named_scope("exchange.encode"):
                enc_w = words[..., :nenc].reshape(lead + enc_shift[0].shape)
                coded = encode_shard(enc_w, enc_shift[0], enc_mask[0])
            with jax.named_scope("exchange.gather"):
                allbufs = _gather_servers(coded, "servers")  # [(B,) K, W]
                allbufs = _pad_last(allbufs)                 # zero col W
            with jax.named_scope("exchange.decode"):
                got = take(allbufs, dec_s[0], dec_w[0])      # [(B,) r, Dmax]
                strip_w = words[..., nenc:].reshape(
                    lead + strip_shift[0].shape)
                sw = (strip_w << strip_shift[0]) & strip_mask[0]
                strip = _fold(sw, -2, jax.lax.bitwise_xor)   # [(B,) r, Dmax]
                rec = ((got ^ strip) & dec_mask[0]) >> dec_shift[0]
                return _fold(rec, -2, jax.lax.bitwise_or)[None]  # [1, (B,) Dmax]

        # pallas_call has no replication rule, so the kernel route must
        # disable the output-replication checker (outputs are per-shard
        # anyway - nothing is claimed replicated).
        f = jax.shard_map(per_server, mesh=self.mesh,
                          in_specs=self._in_specs(),
                          out_specs=P("servers"),
                          check_vma=encode != "xor-kernel")
        return jax.jit(f)

    def _build_hier(self, encode: str, interpret: bool):
        encode_shard = shard_encoder(encode, interpret)

        def per_server(loc, enc_l, enc_shift, enc_mask, dec_rk, dec_w,
                       dec_mask, dec_shift, strip_f, strip_shift,
                       strip_mask, direct_l, direct_mask):
            loc = loc[0, 0]                       # [(B,) Lmax+1]
            # Phase A: plain all_gather of local Map words on the cheap
            # intra-rack axis -> the rack's union buffer, on every member
            # (block s of rflat = server s of the rack).
            with jax.named_scope("exchange.gather"):
                rloc = _gather_servers(loc, "servers")  # [(B,) S, Lmax+1]
                rflat = rloc.reshape(loc.shape[:-1] + (-1,))  # [(B,) S(Lmax+1)]
            # Phase B: rack-level coded encode (replicated within the rack:
            # every member computes the same buffer from rflat - recompute
            # beats a leader branch) + one coded XOR all_gather on the
            # expensive inter-rack axis.
            with jax.named_scope("exchange.encode"):
                coded = encode_shard(take(rflat, enc_l[0]), enc_shift[0],
                                     enc_mask[0])
            with jax.named_scope("exchange.gather"):
                allbufs = _gather_servers(coded, "racks")    # [(B,) R, Wx]
                allbufs = _pad_last(allbufs)                 # zero col Wx
            with jax.named_scope("exchange.decode"):
                got = take(allbufs, dec_rk[0, 0], dec_w[0, 0])  # [(B,) rr, Dmax]
                sw = ((take(rflat, strip_f[0, 0]) << strip_shift[0, 0])
                      & strip_mask[0, 0])
                strip = _fold(sw, -2, jax.lax.bitwise_xor)
                rec = ((got ^ strip) & dec_mask[0, 0]) >> dec_shift[0, 0]
                words = _fold(rec, -2, jax.lax.bitwise_or)   # [(B,) Dmax]
                # Intra-only deliveries never crossed a rack: direct gather
                # from the phase-A buffer (mask 0 on inter deliveries).
                words = words | (take(rflat, direct_l[0, 0])
                                 & direct_mask[0, 0])
                return words[None, None]          # [1, 1, (B,) Dmax]

        f = jax.shard_map(
            per_server, mesh=self.mesh,
            in_specs=self._in_specs(),
            out_specs=P("racks", "servers"),
            check_vma=encode != "xor-kernel")
        return jax.jit(f)

    def exchange_words(self, edge_words: np.ndarray) -> np.ndarray:
        """One coded Shuffle on codec-order uint32 words.

        edge_words [nnz] -> recovered delivery words [M] in the plan's
        (k, i, j) order, bitwise equal to what `execute_coded_sparse`
        would deliver. The whole device computation is uint32 shift/mask/
        XOR - no float ops - which is what makes equality exact.

        Batched edge_words [nnz, B] -> [M, B]: one exchange moves all B
        payload columns (word arrays gain a trailing B axis; the jitted
        schedule tables are shared), column-b bitwise equal to the
        unbatched exchange of that column.
        """
        dev = self.exchange_device(edge_words)
        plan = self.plan
        B = int(edge_words.shape[1]) if np.ndim(edge_words) == 2 else 1
        tr = get_tracer()
        with tr.span("phase.decode", backend="fused", B=B,
                     deliveries=int(plan.all_k.size)):
            with tr.span("decode.pull"):
                out = np.asarray(dev)             # [K, (B,) Dmax]
            get_registry().counter("exchange_host_copy_bytes_total") \
                .inc(out.nbytes)
            with tr.span("decode.reindex"):
                if self._hier:
                    out = out.reshape((plan.K,) + out.shape[2:])
                return gather_deliveries(out, self._starts, self._counts)

    def exchange_device(self, edge_words: np.ndarray) -> jax.Array:
        """Encode + exchange of `exchange_words`, left on the mesh.

        The host gathers each server's words into the operand
        (`operand_shape`) and places it one server per device. Flat: one
        `np.take` of the edge words through the slot-order CSR entries
        (`FusedSparseSchedule.enc_e`, `strip_e`) into a buffer reused per
        payload shape, so the device program gathers only its deliveries
        from the all_gathered buffers. Two levels: each server's local Map
        words (`loc_e`), which phase A all_gathers within the rack.

        Returns the per-server padded delivery words, sharded one server
        per device: [K, (B,) Dmax] flat, [R, S, (B,) Dmax] on two levels.
        """
        s = self.sched
        tr = get_tracer()
        ew = np.ascontiguousarray(edge_words, np.uint32)
        if ew.shape[0] != self._nnz:
            raise ValueError(f"{ew.shape[0]} edge words for a plan over "
                             f"{self._nnz} CSR entries")
        batched = ew.ndim == 2
        B = int(ew.shape[1]) if batched else 1
        words = s.K * B * (s.Lmax if self._hier else self._slot_e.shape[1])
        with tr.span("phase.encode", backend="fused", B=B,
                     nnz=int(edge_words.shape[0]), words=words):
            if self._hier:
                # payload axis first, the sentinel zero word at index nnz
                ew = np.concatenate([np.atleast_2d(ew.T),
                                     np.zeros((B, 1), np.uint32)], axis=1)
                loc = np.zeros((s.K, B, s.Lmax + 1), dtype=np.uint32)
                loc[:, :, :s.Lmax] = np.moveaxis(ew[:, s.loc_e], 0, 1)
                # device (rho, s) of the (racks, servers) mesh is server
                # rho * S + s, so the reshape is the identity placement
                operand = loc.reshape(
                    self.operand_shape(B if batched else None))
            else:
                operand = self._slot_words(ew)
        bits = self._schedule_bits * B
        attrs = dict(backend="fused", bits=bits, B=B, K=s.K)
        if self._hier:
            attrs.update(inter_rack_bits=self.hplan.inter_rack_bits * B,
                         intra_rack_bits=self.hplan.intra_rack_bits * B)
        # Host-side timing around the jitted multi-device exchange: block
        # on the device buffers before stamping so the span covers the
        # collective's execution, not just its dispatch.
        with tr.span("phase.exchange", **attrs):
            if self._dev_tables is None:
                with tr.span("exchange.place_tables"):
                    self._dev_tables = self._place(self._host_tables(),
                                                   self._in_specs()[1:])
            with tr.span("exchange.place"):
                (dop,) = self._place((operand,), self._in_specs()[:1])
            # A call shape new to the jitted exchange traces and compiles
            # (or loads from the compile cache) inside the call.
            key = (operand.shape,) + tuple(t.shape for t in self._dev_tables)
            first = key not in self._seen
            with tr.span("exchange.first_call" if first else "exchange.run"):
                dev = self._fn(dop, *self._dev_tables)
                jax.block_until_ready(dev)
            self._seen.add(key)
        reg = get_registry()
        reg.counter("exchange_calls_total",
                    "fused coded-Shuffle exchanges run").inc()
        reg.counter("exchange_host_copy_bytes_total",
                    "bytes copied between host and chips per exchange: word "
                    "operand in, padded deliveries out").inc(operand.nbytes)
        reg.counter("exchange_bits_useful_total",
                    "Definition 2 load bits the schedule needs").inc(bits)
        reg.counter("exchange_bits_sent_total",
                    "bits the chips put into the exchange's collectives, "
                    "each buffer once").inc(32 * self._sent_words * B)
        return dev

    def _slot_words(self, ew: np.ndarray) -> np.ndarray:
        """The flat operand: edge words [nnz] or [nnz, B] -> [K, (B,) N]
        slot words, CSR entry `nnz` reading the zero sentinel. Both buffers
        are allocated on a payload shape's first call and reused, so calls
        on one exchange must not overlap (sessions and the serving worker
        call it from one thread)."""
        lead = ew.shape[1:]
        if lead not in self._bufs:
            src = np.zeros(lead + (self._nnz + 1,), np.uint32)  # [(B,) nnz+1]
            out = np.empty(self.operand_shape(*lead), np.uint32)
            self._bufs[lead] = (src, out)
        src, out = self._bufs[lead]
        src[..., :-1] = ew.T                  # payload axis first
        for k, idx in enumerate(self._slot_e):
            # mode="clip" keeps `out=` unbuffered; every index is in range
            np.take(src, idx, axis=-1, out=out[k], mode="clip")
        return out

    def execute(self, edge_vals: np.ndarray) -> PlanShuffleResult:
        """Drop-in peer of `ShufflePlan.execute_coded_sparse` (batched
        [nnz, B] edge values supported the same way)."""
        plan = self.plan
        tr = get_tracer()
        with tr.span("phase.codec"):
            edge_vals = np.asarray(edge_vals, np.float32)
            edge_words = floats_to_words(edge_vals)
        words = self.exchange_words(edge_words)
        with tr.span("phase.codec"):
            values = words_to_floats(words)
        bits = (self._schedule_bits
                * (edge_vals.shape[1] if edge_vals.ndim == 2 else 1))
        return PlanShuffleResult(plan.all_k, plan.all_i, plan.all_j,
                                 values, plan.ptr, bits, plan.n)


def run_fused_sparse(g: Graph, edge_vals: np.ndarray, alloc: Allocation,
                     mesh: Mesh | None = None, *, encode: str = "xor-ref",
                     interpret: bool | None = None) -> PlanShuffleResult:
    """Convenience one-shot: compile + partition + one sparse exchange."""
    plan = compile_plan_csr(g.csr, alloc, validate=False)
    ex = FusedSparseShuffle(plan, g.csr, alloc, mesh, encode=encode,
                            interpret=interpret)
    return ex.execute(edge_vals)


# ---------------------------------------------------------------------------
# Dense small-n validation reference
# ---------------------------------------------------------------------------


def build_schedule(g: Graph, alloc: Allocation,
                   plan: ShufflePlan | None = None):
    """Static (graph-dependent, data-independent) dense-reference schedule.

    Compiles the ShufflePlan once - adjacency-free via `compile_plan_csr`,
    so a CSR-native graph beyond `dense_limit` never materializes [n, n] -
    and lays its columns out per sender, padded to a common buffer length
    so the all_gather is dense. Returns numpy index tensors consumed by the
    jitted dense exchange (covered pairs only; leftovers are a sparse-path
    concern - see `partition_plan`).
    """
    K, r = alloc.K, alloc.r
    if plan is None:
        plan = compile_plan_csr(g.csr, alloc, validate=False)
    # Per-sender column order comes from the one shared layout rule
    # (`_sender_layout`), so the dense reference and the sparse partition
    # can never disagree on buffer positions.
    colpos, ncols = _sender_layout(plan)
    per_s: list[list[int]] = [[0] * int(ncols[s]) for s in range(K)]
    for c in range(plan.col_sender.size):
        per_s[int(plan.col_sender[c])][int(colpos[c])] = c
    width = int(ncols.max()) if ncols.size else 0

    P_pairs = plan.pair_k.size
    # Encode tensors: for slot t of server s, the XOR of values v[i,j] over
    # receivers. We express it as up-to-r (i, j) index pairs (-1 padded).
    enc_idx = np.full((K, width, r, 2), -1, dtype=np.int32)
    for s in range(K):
        for t, c in enumerate(per_s[s]):
            for sl in range(r):
                p = int(plan.slot_pair[c, sl])
                if p == P_pairs:          # sentinel: empty slot
                    continue
                enc_idx[s, t, sl] = (plan.pair_i[p], plan.pair_j[p])
    # Decode map: receiver k strips every other member's value from the slot.
    # For each (sender s, slot t) useful to k: target (i, j) plus the strip
    # list; represent as target idx and r-1 strip idx pairs.
    dec: dict[int, list] = {k: [] for k in range(K)}
    for s in range(K):
        for t, c in enumerate(per_s[s]):
            occupied = [sl for sl in range(r)
                        if int(plan.slot_pair[c, sl]) != P_pairs]
            for sl in occupied:
                p = int(plan.slot_pair[c, sl])
                k = int(plan.pair_k[p])
                strips = [(int(plan.pair_i[int(plan.slot_pair[c, sl2])]),
                           int(plan.pair_j[int(plan.slot_pair[c, sl2])]))
                          for sl2 in occupied if sl2 != sl]
                tgt = (int(plan.pair_i[p]), int(plan.pair_j[p]))
                dec[k].append((s, t, tgt, strips))
    dwidth = max((len(d) for d in dec.values()), default=0)
    dec_src = np.zeros((K, dwidth, 2), dtype=np.int32)       # (sender, slot)
    dec_tgt = np.full((K, dwidth, 2), -1, dtype=np.int32)    # (i, j)
    dec_strip = np.full((K, dwidth, r - 1, 2), -1, dtype=np.int32) \
        if r > 1 else np.zeros((K, dwidth, 0, 2), np.int32)
    for k, items in dec.items():
        for t, (s, slot_t, (i, j), strips) in enumerate(items):
            dec_src[k, t] = (s, slot_t)
            dec_tgt[k, t] = (i, j)
            for ri, (i2, j2) in enumerate(strips):
                dec_strip[k, t, ri] = (i2, j2)
    return enc_idx, dec_src, dec_tgt, dec_strip


def _as_words(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)


def _as_floats(w):
    return jax.lax.bitcast_convert_type(w, jnp.float32)


def fused_exchange(values: jnp.ndarray, enc_idx, dec_src, dec_tgt, dec_strip,
                   mesh: Mesh):
    """One coded Shuffle as a single all_gather of packed XOR buffers.

    values [n, n] float32 (replicated Map output; each server only reads its
    own columns through the schedule indices). Returns [n, n] recovered
    missing values (0 where not delivered) - identical on every server.
    Validation reference only: the production path is `FusedSparseShuffle`.
    """
    def per_server(words, enc_s, dec_src_s, dec_tgt_s, dec_strip_s):
        # enc_s [1, W, r, 2] on this shard.
        enc_s = enc_s[0]
        valid = enc_s[:, :, 0] >= 0
        vals = words[jnp.clip(enc_s[:, :, 0], 0), jnp.clip(enc_s[:, :, 1], 0)]
        buf = jnp.where(valid, vals, jnp.uint32(0))
        coded = jax.lax.reduce(buf, jnp.uint32(0), jax.lax.bitwise_xor, (1,))
        allbufs = jax.lax.all_gather(coded, "servers")       # [K, W]
        # Decode this server's targets.
        d_src, d_tgt, d_strip = dec_src_s[0], dec_tgt_s[0], dec_strip_s[0]
        got = allbufs[d_src[:, 0], d_src[:, 1]]
        sv = d_strip[:, :, 0] >= 0
        strip_vals = words[jnp.clip(d_strip[:, :, 0], 0),
                           jnp.clip(d_strip[:, :, 1], 0)]
        strip = jax.lax.reduce(jnp.where(sv, strip_vals, jnp.uint32(0)),
                               jnp.uint32(0), jax.lax.bitwise_xor, (1,))
        rec = got ^ strip
        out = jnp.zeros(words.shape, jnp.uint32)
        tgt_ok = d_tgt[:, 0] >= 0
        out = out.at[jnp.clip(d_tgt[:, 0], 0),
                     jnp.clip(d_tgt[:, 1], 0)].set(
            jnp.where(tgt_ok, rec, jnp.uint32(0)))
        return jax.lax.psum(out, "servers")   # union of per-server recoveries

    # The value words enter as a replicated argument, not a closure, and
    # the call runs under the mesh: a mesh with explicit axes (the
    # `jax.make_mesh` default) refuses arrays committed to one device.
    f = jax.shard_map(per_server, mesh=mesh,
                      in_specs=(P(),) + (P("servers"),) * 4, out_specs=P())
    with jax.set_mesh(mesh):
        out_words = jax.jit(f)(_as_words(values), jnp.asarray(enc_idx),
                               jnp.asarray(dec_src), jnp.asarray(dec_tgt),
                               jnp.asarray(dec_strip))
    return _as_floats(out_words)


def run_fused(g: Graph, values: np.ndarray, alloc: Allocation, mesh: Mesh):
    """Convenience wrapper: schedule + dense exchange; returns [n, n]."""
    sched = build_schedule(g, alloc)
    return fused_exchange(jnp.asarray(values, jnp.float32), *sched, mesh=mesh)
