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

`partition_plan` splits a compiled CSR `ShufflePlan` per server into
encode/decode/strip tables (O(plan / K) each, padded to the largest
server's). Every encode and strip slot names its word by CSR entry, so the
host gathers each server's edge words straight into the slot order of its
tables. One iteration under `shard_map` on a ('servers',) mesh is then (a)
per-shard shift-mask + XOR encode through the batched `kernels/xor_code`
route, (b) one packed dense all_gather of uint32 coded words, (c) per-shard
delivery gather, strip + shift-back into each receiver's delivery slice. No
[n, n] or O(n^2)-shaped array exists anywhere on this path;
`FusedSparseShuffle` jits the exchange once and replays it every iteration,
bit-exact against `ShufflePlan.execute_coded_sparse` (unicast leftovers ride
the same all_gather as single-slot full-width columns).

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
`partition_hierarchical` lays out the rack-level plan with the same code as
the flat partition (`_coded_level`); its slots name words by phase-A buffer
position instead of CSR entry. Delivered words stay bitwise equal to the
flat path (`partition_plan` / `FusedSparseShuffle` accept a `Topology` and
degenerate to the single-level exchange on `Topology.flat(K)`).
"""
from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from functools import partial

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
from .graph_models import CSR
from .shuffle_plan import (HierarchicalPlan, PlanShuffleResult, ShufflePlan,
                           _rack_first_mapper, _run_ranks, resolve_topology)

FULL_MASK = np.uint32(0xFFFFFFFF)

# The flat operand's host gather (`FusedSparseShuffle._slot_words`) cuts each
# server's row into slices of at least `_MIN_CHUNK` words and runs them on one
# pool of threads shared by the process; `np.take` releases the GIL. Rows
# shorter than a slice take one serial `np.take` per server.
_MIN_CHUNK = 1 << 18
_MAX_THREADS = 16
_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def _gather_threads() -> int:
    """Threads the slot-order gather may use: the cores this process may
    run on, at most `_MAX_THREADS`."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:                # no affinity call on this OS
        cores = os.cpu_count() or 1
    return max(1, min(cores, _MAX_THREADS))


def _run_jobs(jobs: list, workers: int) -> None:
    """Run every job: in this thread if `workers` is 1, else on the shared
    gather pool (made on first use), waiting for all before raising the
    first error, so no job still writes when the call returns."""
    global _pool
    if workers == 1:
        for job in jobs:
            job()
        return
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(_gather_threads(),
                                       thread_name_prefix="slot-gather")
    futures = [_pool.submit(job) for job in jobs]
    wait(futures)
    for f in futures:
        f.result()


def _cuts(n: int, parts: int) -> np.ndarray:
    """[parts + 1] bounds that cut range(n) into `parts` near-equal
    contiguous slices."""
    return np.arange(parts + 1) * n // parts


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


def _per_receiver(stream: ShufflePlan, rows: np.ndarray, fill) -> np.ndarray:
    """[M, ...] rows in the delivery order of `stream` -> [K, Dmax, ...]
    padded per-receiver rows (delivery m at column m - ptr[k] of its
    receiver k's row, `fill` in the padding; Dmax the largest receiver's
    delivery count)."""
    kk = stream.all_k
    Dmax = max(int(np.diff(stream.ptr).max()), 1)
    out = np.full((stream.K, Dmax) + rows.shape[1:], fill, rows.dtype)
    out[kk, np.arange(kk.size, dtype=np.int64) - stream.ptr[kk]] = rows
    return out


def _coded_level(plan: ShufflePlan, stream: ShufflePlan, holders: np.ndarray,
                 tables, name, zero: int, cov, left):
    """Lay out one coded level of a plan for the fused exchange.

    `plan` is the level's schedule: the flat plan, or a `HierarchicalPlan`'s
    rack-level `inter` (racks as senders). Sender s holds the values of the
    vertices in `holders[s]` (the level's `map_sets`); `tables` are the
    level's CSR edge tables. A slot names its word through
    ``name(who, j, e, role)``: the word of vertex j's value, CSR entry e, as
    `who` holds it; `role` ("sender {} schedules", ...) words the error when
    `who` does not hold it. `zero` names the guaranteed-zero word.

    Unicast leftovers go to the smallest sender holding their column vertex,
    appended after its coded columns as single-slot full-width columns.
    `cov` = (rows, pairs) and `left` = (rows, leftovers) place the covered
    pairs and leftovers among the deliveries of `stream` (the flat plan),
    whose per-receiver rows (`_per_receiver`) the decode tables are.

    Returns (W, encode, decode): the per-sender buffer width; the
    [senders, W, r] word, shift and mask tables; and the [K, Dmax, r]
    sender, buffer column (W = zero column), mask and shift tables followed
    by the [K, Dmax, r, r-1] strip word, shift and mask tables.
    """
    r, Pn = plan.r, plan.pair_k.size
    nstrip = max(r - 1, 0)
    colpos, ncols = _sender_layout(plan)

    lsender = np.argmax(holders[:, plan.left_j], axis=0)
    if not holders[lsender, plan.left_j].all():
        raise RuntimeError("leftover value has no Mapping server")
    lorder = np.argsort(lsender, kind="stable")
    _, lrank = _run_ranks(lsender[lorder])
    leftw = np.empty(lsender.size, dtype=np.int64)
    leftw[lorder] = ncols[lsender[lorder]] + lrank
    nleft = np.bincount(lsender, minlength=ncols.size)
    W = max(int((ncols + nleft).max()), 1)

    # --- encode tables: valid plan slots + leftover slots, per sender ---
    enc = np.full((ncols.size, W, r), zero, dtype=np.int32)
    enc_shift = np.zeros((ncols.size, W, r), dtype=np.uint32)
    enc_mask = np.zeros((ncols.size, W, r), dtype=np.uint32)
    cs, sl = np.nonzero(plan.slot_pair < Pn)
    p = plan.slot_pair[cs, sl]
    snd = plan.col_sender[cs]
    enc[snd, colpos[cs], sl] = name(snd, plan.pair_j[p], tables.pair_e[p],
                                    "sender {} schedules")
    enc_shift[snd, colpos[cs], sl] = plan.slot_shift[cs, sl]
    enc_mask[snd, colpos[cs], sl] = plan.slot_mask[cs, sl]
    enc[lsender, leftw, 0] = name(lsender, plan.left_j, tables.left_e,
                                  "sender {} unicasts")
    enc_mask[lsender, leftw, 0] = FULL_MASK          # full word, shift 0

    # --- decode rows, first in the stream's flat delivery order ---
    M = stream.all_k.size
    f_s = np.zeros((M, r), dtype=np.int32)
    f_w = np.full((M, r), W, dtype=np.int32)         # W = zero column
    f_mask = np.zeros((M, r), dtype=np.uint32)
    f_shift = np.zeros((M, r), dtype=np.uint32)
    f_x = np.full((M, r, nstrip), zero, dtype=np.int32)
    f_ssh = np.zeros((M, r, nstrip), dtype=np.uint32)
    f_smk = np.zeros((M, r, nstrip), dtype=np.uint32)
    rows, pc = cov
    c, slot = plan.pair_col[pc], plan.pair_slot[pc]  # [Pc, r]
    f_s[rows] = plan.col_sender[c]
    f_w[rows] = colpos[c]
    f_mask[rows] = plan.slot_mask[c, slot]
    f_shift[rows] = plan.seg_shift
    if nstrip:
        # the other slots of each segment's column, which its receiver strips
        ar = np.broadcast_to(np.arange(r), (rows.size, r, r))
        others = ar[ar != slot[..., None]].reshape(rows.size, r, nstrip)
        c3 = np.broadcast_to(c[:, :, None], others.shape)
        sp = plan.slot_pair[c3, others]              # [Pc, r, r-1]
        valid = sp < Pn
        who = np.broadcast_to(plan.pair_k[pc][:, None, None], sp.shape)
        sx = np.full(sp.shape, zero, dtype=np.int32)
        sx[valid] = name(who[valid], plan.pair_j[sp[valid]],
                         tables.pair_e[sp[valid]], "receiver {} must strip")
        f_x[rows] = sx
        f_ssh[rows] = plan.slot_shift[c3, others]
        f_smk[rows] = plan.slot_mask[c3, others]
    rows, pl = left
    f_s[rows, 0] = lsender[pl]
    f_w[rows, 0] = leftw[pl]
    f_mask[rows, 0] = FULL_MASK                      # full word, shift 0

    decode = tuple(_per_receiver(stream, a, fill) for a, fill in (
        (f_s, 0), (f_w, W), (f_mask, 0), (f_shift, 0), (f_x, zero),
        (f_ssh, 0), (f_smk, 0)))
    return W, (enc, enc_shift, enc_mask), decode


@dataclasses.dataclass(frozen=True)
class FusedSparseSchedule:
    """Per-server partition of a compiled CSR plan (all arrays plan-sized).

    Device k's shard (row k of every array) is everything it needs for one
    coded Shuffle: the `enc_*` tables lay its coded columns (+ its unicast
    leftovers, as single-slot full-width columns) into a [W]-word buffer,
    and the `dec_*`/`strip_*` tables recover its delivery slice from the
    all_gathered [K, W] buffer matrix.

    `enc_e` and `strip_e` name the word of every encode and strip slot by
    its CSR entry, an entry whose column vertex server k Mapped. The
    exchange's host side gathers the edge words through them straight into
    slot order, so its device program reads every encode and strip word in
    place. `Lmax`, the most CSR entries whose column vertex one server
    Mapped, sizes a server's share of the Map output.

    Sentinels: CSR entry `nnz` is a guaranteed-zero word; buffer column `W`
    is a guaranteed-zero column (padded after the all_gather); masks of
    sentinel slots are 0, so they OR/XOR away - encode and decode are plain
    shift-mask pipelines with no control flow.
    """

    K: int
    r: int
    W: int                        # per-sender buffer width (words)
    Lmax: int                     # max local-value count over servers
    Dmax: int                     # max delivery count over receivers
    enc_e: np.ndarray             # [K, W, r] int32 CSR entry (nnz = zero)
    enc_shift: np.ndarray         # [K, W, r] uint32 segment left-shift
    enc_mask: np.ndarray          # [K, W, r] uint32 segment keep-mask
    dec_s: np.ndarray             # [K, Dmax, r] int32 sender of segment t
    dec_w: np.ndarray             # [K, Dmax, r] int32 buffer column (W = zero)
    dec_mask: np.ndarray          # [K, Dmax, r] uint32 own-slot keep-mask
    dec_shift: np.ndarray         # [K, Dmax, r] uint32 shift back into place
    strip_e: np.ndarray           # [K, Dmax, r, r-1] int32 CSR entry (nnz = zero)
    strip_shift: np.ndarray       # [K, Dmax, r, r-1] uint32
    strip_mask: np.ndarray        # [K, Dmax, r, r-1] uint32


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
    plan, _ = resolve_topology(plan, topology)
    if isinstance(plan, HierarchicalPlan):
        return partition_hierarchical(plan, csr, alloc)
    plan._require_schedule()
    if csr.nnz >= np.iinfo(np.int32).max:
        raise ValueError(f"nnz={csr.nnz} does not fit int32 CSR entries")
    tables = plan.edge_tables(csr, alloc)     # locates edges + validates

    def entry(who, j, e, role):
        # A slot's word is its CSR entry, which `who` must have Mapped.
        bad = ~alloc.map_sets[who, csr.indices[e]]
        if bad.any():
            raise RuntimeError(f"{role.format(int(who[bad].min()))} a value "
                               "it did not Map")
        return e

    W, enc, dec = _coded_level(
        plan, plan, alloc.map_sets, tables, entry, csr.nnz,
        (plan.pos_covered, np.arange(plan.pair_k.size)),
        (plan.pos_left, np.arange(plan.left_k.size)))
    in_deg = np.bincount(csr.indices, minlength=alloc.n)
    Lmax = max(int((alloc.map_sets @ in_deg).max()), 1)
    return FusedSparseSchedule(plan.K, plan.r, W, Lmax, dec[0].shape[1],
                               *enc, *dec)


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
    K = flat.K
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

    def rfidx(rack, j, e, role=None):
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

    # Inter deliveries: invert the inter plan's pos_covered/pos_left to
    # find which covered pair / leftover each flat delivery resolves to.
    Mx = inter.all_k.size
    kind_left = np.zeros(Mx, dtype=bool)
    kind_left[inter.pos_left] = True
    idx_in = np.empty(Mx, dtype=np.int64)
    idx_in[inter.pos_covered] = np.arange(inter.pair_k.size, dtype=np.int64)
    idx_in[inter.pos_left] = np.arange(inter.left_k.size, dtype=np.int64)
    intra = hplan.inter_pos < 0
    ms = np.flatnonzero(~intra)
    q = hplan.inter_pos[ms]
    is_l = kind_left[q]
    Wx, enc, dec = _coded_level(
        inter, flat, has, xt, rfidx, ZERO,
        (ms[~is_l], idx_in[q[~is_l]]), (ms[is_l], idx_in[q[is_l]]))

    # Intra-only deliveries: a direct word from the receiving rack's
    # phase-A buffer.
    f_dl = np.full(flat.all_k.size, ZERO, dtype=np.int32)
    f_dm = np.zeros(flat.all_k.size, dtype=np.uint32)
    f_dl[intra] = rfidx(hplan.rack_of[flat.all_k[intra]], flat.all_j[intra],
                        ft.all_e[intra])
    f_dm[intra] = FULL_MASK
    return FusedHierarchicalSchedule(
        K, R, S, inter.r, Wx, Lmax, dec[0].shape[1], loc_e, *enc, *dec,
        _per_receiver(flat, f_dl, ZERO), _per_receiver(flat, f_dm, 0))


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
    `exchange_host_copy_bytes_total`, `exchange_bits_useful_total`,
    `exchange_bits_sent_total` and `exchange_encode_parallel_total`.

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
        plan, self.topology = resolve_topology(plan, topology)
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
            # The gather's chunk plan: each row in `parts` slices of at
            # least _MIN_CHUNK words, about one per thread at B=1, and the
            # staging copy of the [nnz] words in as many slices as B=1 uses
            # threads. A row shorter than one slice runs serially.
            N, threads = self._slot_e.shape[1], _gather_threads()
            parts = max(1, min(-(-threads // s.K), N // _MIN_CHUNK))
            self._row_cuts = _cuts(N, parts)
            self._threads = threads if N >= _MIN_CHUNK else 1
            self._nnz_cuts = _cuts(self._nnz, self._workers(1))
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
        (`operand_shape`) and places it one server per device. Flat: a
        `np.take` of the edge words through the slot-order CSR entries
        (`FusedSparseSchedule.enc_e`, `strip_e`) into a buffer reused per
        payload shape, in row slices on a pool of threads once a row is
        long enough (`_slot_words`), so the device program gathers only
        its deliveries from the all_gathered buffers. Two levels: each
        server's local Map words (`loc_e`), which phase A all_gathers
        within the rack.

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
        workers = 1 if self._hier else self._workers(B)
        with tr.span("phase.encode", backend="fused", B=B,
                     nnz=int(edge_words.shape[0]), words=words,
                     workers=workers):
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
                operand = self._slot_words(ew, workers)
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
        reg.counter("exchange_encode_parallel_total",
                    "flat exchanges whose slot-order gather ran on more "
                    "than one thread").inc(int(workers > 1))
        return dev

    def _workers(self, B: int) -> int:
        """Threads a flat gather of a B-column payload runs on: one job per
        (server, column, row slice), at most `_threads`."""
        return min(self._slot_e.shape[0] * B * (self._row_cuts.size - 1),
                   self._threads)

    def _slot_words(self, ew: np.ndarray, workers: int) -> np.ndarray:
        """The flat operand: edge words [nnz] or [nnz, B] -> [K, (B,) N]
        slot words, CSR entry `nnz` reading the zero sentinel.

        The staging copy into a [(B,) nnz+1] source, then one `np.take` per
        (server, payload column, row slice of `_row_cuts`), each into a
        C-contiguous slice of the operand, on `workers` threads. Every job
        writes its own slice, so the words are those of one serial
        `np.take` per server. Both buffers are allocated on a payload
        shape's first call and reused, so calls on one exchange must not
        overlap (sessions and the serving worker call it from one thread);
        two exchanges write to their own buffers. The two-level operand's
        gather (`exchange_device`) stays serial: no benchmark cell runs it.
        """
        lead = ew.shape[1:]
        if lead not in self._bufs:
            src = np.zeros(lead + (self._nnz + 1,), np.uint32)  # [(B,) nnz+1]
            out = np.empty(self.operand_shape(*lead), np.uint32)
            self._bufs[lead] = (src, out)
        src, out = self._bufs[lead]
        c = self._nnz_cuts                    # payload axis first
        _run_jobs([partial(np.copyto, src[..., a:b], ew[a:b].T)
                   for a, b in zip(c[:-1], c[1:])], workers)
        rows = src.reshape(-1, self._nnz + 1)          # [B or 1, nnz+1]
        dst = out.reshape(out.shape[0], rows.shape[0], -1)  # [K, B or 1, N]
        c = self._row_cuts
        # mode="clip" keeps `out=` unbuffered; every index is in range
        _run_jobs([partial(np.take, rows[j], idx[a:b], -1, dst[k, j, a:b],
                           "clip")
                   for k, idx in enumerate(self._slot_e)
                   for j in range(rows.shape[0])
                   for a, b in zip(c[:-1], c[1:])], workers)
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
