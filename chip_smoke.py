"""Bring-up check: the coded-Shuffle engine's main path on a TPU.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the fused exchange across four chips

Data: an Erdos-Renyi graph drawn from `--seed` at n = 2**21 (rounded up to
divisibility by K and C(K, r)) with average degree 32 - about 33.5M
undirected edges, 67M CSR entries - on K=4 servers at redundancy r=2.

One chip: the per-server device programs of the main path at real width.
For the PageRank edge values of the first two iterations of the host
session, (a) the plan executor's XOR-kernel route delivers words bitwise
equal to its NumPy route, and (b) every server's per-shard encode of the
fused exchange (`fused_shuffle.shard_encoder` over its `partition_plan`
tables, laid out as the exchange stores them) through the compiled kernel
is bitwise equal to the kernel-free route.

Four chips (`--chips 4`, that phase alone): `engine.compile(...,
backend="fused", encode="xor-kernel")`, flat and on `Topology(2, 2)`, runs
3 PageRank iterations and one multi_sssp batch of B=8 (`--batch-iters`
iterations), each bitwise equal in state and equal in Shuffle bits to the
NumPy session on the same plan, with every server's shard of the exchange
on its own chip.

Exits non-zero, printing no result, where JAX finds no TPU or any check
fails. The last stdout line is the JSON result. Times printed on earlier
lines are single samples of one run, not metrics.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

K, R = 4, 2                      # servers, Map redundancy
DEGREE = 32                      # average degree of the ER graph
LOG2N = 21                       # n = 2**21: half of graph500-22's edges


def log(msg: str) -> None:
    print(msg, flush=True)


def words_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and np.array_equal(
        a.view(np.uint32), b.view(np.uint32))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")
    log(f"  ok: {what}")


class Clock:
    """Host wall time of one block, printed as a one-run sample."""

    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"  {self.label}: {self.s:.3f} s (one chip-run sample)")


def build(log2n: int, seed: int) -> dict:
    """The graph, allocation and compiled plan; host set-up, timed."""
    from repro import graphs
    from repro.core.allocation import divisible_n, er_allocation
    from repro.core.shuffle_plan import compile_plan_csr

    n = divisible_n(2 ** log2n, K, R)
    log(f"graph: ER n={n} average degree {DEGREE}, K={K} r={R}, "
        f"seed={seed}")
    if log2n < 22:
        log(f"  size cut: n=2**{log2n} holds about "
            f"{100 * 2 ** (log2n - 22):g}% of the edges of LDBC "
            "Graphalytics graph500-22 (about 64M edges)")
    with Clock("set-up graphs.erdos_renyi"):
        g = graphs.erdos_renyi(n, DEGREE / (n - 1), seed=seed)
    log(f"  {g.num_edges} undirected edges, {g.csr.nnz} CSR entries")
    with Clock("set-up er_allocation"):
        alloc = er_allocation(n, K, R)
    with Clock("set-up compile_plan_csr"):
        plan = compile_plan_csr(g.csr, alloc)
    with Clock("set-up edge_tables"):
        tables = plan.edge_tables(g.csr, alloc)
    log(f"  plan: {plan.col_sender.size} coded columns, "
        f"{plan.all_k.size} deliveries")
    return {"g": g, "alloc": alloc, "plan": plan, "tables": tables}


def one_chip(s: dict) -> None:
    """(a) plan executor, kernel vs NumPy; (b) per-server fused encode,
    compiled kernel vs kernel-free route - for the first two iterations'
    edge values."""
    import functools

    import jax

    from repro.core import algorithms as algo
    from repro.core import engine
    from repro.core.bitcodec import floats_to_words
    from repro.core.fused_shuffle import (lanes_last, partition_plan,
                                          shard_encoder)
    from repro.kernels.platform import interpret_mode
    from repro.kernels.xor_code import ops as xor_ops

    g, alloc, plan, tables = s["g"], s["alloc"], s["plan"], s["tables"]
    with Clock("set-up partition_plan"):
        sched = partition_plan(plan, g.csr, alloc)
    log(f"  per-server encode width W={sched.W}, local values "
        f"Lmax={sched.Lmax}")

    cols = jax.ShapeDtypeStruct((plan.col_sender.size, R), np.uint32)
    text = jax.jit(xor_ops.xor_encode_columns).lower(cols).compile().as_text()
    check("tpu_custom_call" in text,
          "(a) compiled column route holds the kernel (tpu_custom_call)")

    dev = jax.devices()[0]
    put = functools.partial(jax.device_put, device=dev)
    # [K, r, W]: the encode tables as the fused exchange stores them, and
    # the CSR entry of every slot, which its host side gathers through
    enc_e, *enc = [lanes_last(a) for a in (sched.enc_e, sched.enc_shift,
                                           sched.enc_mask)]
    shapes = [jax.ShapeDtypeStruct(a.shape[1:], np.uint32)
              for a in (enc_e, *enc)]
    interpret = interpret_mode(platform=dev.platform)
    with Clock("compile the per-shard encode, kernel and xor-ref routes"):
        kernel = jax.jit(shard_encoder("xor-kernel", interpret)) \
            .lower(*shapes).compile()
        ref_route = jax.jit(shard_encoder("xor-ref", interpret)) \
            .lower(*shapes).compile()
    check("tpu_custom_call" in kernel.as_text(),
          "(b) compiled per-shard encode holds the kernel (tpu_custom_call)")

    prog = algo.pagerank()
    sess = engine.compile(prog, g, alloc, "coded", path="sparse", plan=plan)
    state = prog.init(g)
    for it in range(2):
        if it:
            with Clock("host session iteration (numpy)"):
                state = sess.run(1, state=state).state
        log(f"iteration {it}:")
        ev = prog.map_edge_values(g, state).astype(np.float32)
        with Clock("(a) execute_coded_sparse numpy"):
            want = plan.execute_coded_sparse(ev, tables, backend="numpy")
        with Clock("(a) execute_coded_sparse xor-kernel, host + device"):
            got = plan.execute_coded_sparse(ev, tables,
                                            backend="xor-kernel")
        check(words_equal(got.values, want.values)
              and got.bits_sent == want.bits_sent,
              f"(a) {want.values.size} delivered words bitwise equal")

        words = np.append(floats_to_words(ev), np.uint32(0))  # nnz -> 0
        for k in range(K):
            args = [put(a) for a in (words[enc_e[k]], *(t[k] for t in enc))]
            with Clock(f"(b) server {k} kernel encode, device"):
                coded = kernel(*args).block_until_ready()
            ref = ref_route(*args).block_until_ready()
            check(words_equal(coded, ref),
                  f"(b) server {k}: {coded.size} coded words bitwise equal")


def four_chips(s: dict, iters: int = 3, batch: int = 8,
               batch_iters: int = 1, seed: int = 0) -> None:
    """Fused sessions, flat and Topology(2, 2), against NumPy sessions on
    the same plans."""
    import jax

    from repro.core import algorithms as algo
    from repro.core import engine
    from repro.core.bitcodec import floats_to_words
    from repro.core.shuffle_plan import compile_hierarchical
    from repro.launch.mesh import Topology

    g, alloc, plan = s["g"], s["alloc"], s["plan"]
    topo = Topology(2, 2)
    with Clock("set-up compile_hierarchical Topology(2, 2)"):
        hplan = compile_hierarchical(g.csr, alloc, topo)
    roots = np.random.default_rng(seed).choice(g.n, batch, replace=False)
    pagerank, sssp = algo.pagerank(), algo.multi_sssp(roots)

    for name, p in (("flat", plan), ("Topology(2, 2)", hplan)):
        log(f"{name}:")
        ref = engine.compile(pagerank, g, alloc, "coded", path="sparse",
                             plan=p)
        fused = engine.compile(pagerank, g, alloc, "coded", path="sparse",
                               plan=p, backend="fused", encode="xor-kernel")
        with Clock("set-up fused partition + jit"):
            ex = fused.fused
        with Clock(f"numpy pagerank x{iters}"):
            want = ref.run(iters)
        with Clock(f"fused pagerank x{iters} (first call compiles)"):
            got = fused.run(iters)
        check(words_equal(got.state, want.state)
              and got.shuffle_bits == want.shuffle_bits,
              f"{name} pagerank: state bitwise equal, "
              f"{got.shuffle_bits} Shuffle bits equal")

        init = sssp.init(g)
        with Clock(f"numpy multi_sssp B={batch} x{batch_iters}"):
            want = ref.with_program(sssp).run_batch(init, batch_iters)
        with Clock(f"fused multi_sssp B={batch} x{batch_iters} (first call "
                   "compiles)"):
            got = fused.with_program(sssp).run_batch(init, batch_iters)
        check(words_equal(got.state, want.state)
              and got.shuffle_bits == want.shuffle_bits,
              f"{name} multi_sssp B={batch}: state bitwise equal, "
              f"{got.shuffle_bits} Shuffle bits equal")

        ev = pagerank.map_edge_values(g, pagerank.init(g)).astype(np.float32)
        out = ex.exchange_device(floats_to_words(ev))
        where = {}
        for shard in out.addressable_shards:
            # flat [K, Dmax]: server k; two levels [R, S, Dmax]: rho * S + s
            idx = [sl.start or 0 for sl in shard.index[:out.ndim - 1]]
            server = (idx[0] if len(idx) == 1
                      else idx[0] * topo.servers_per_rack + idx[1])
            where[server] = shard.device
            log(f"  server {server}: exchange output on device "
                f"{shard.device.id} {shard.device}")
        check(sorted(where) == list(range(K))
              and len({d.id for d in where.values()}) == K,
              f"{name}: each of the {K} servers' shards on its own device")

    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"device {d.id}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: per-server device programs on one chip; "
                         "4: the fused exchange across four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log2n", type=int, default=LOG2N,
                    help="graph size n = 2**LOG2N (default 21)")
    ap.add_argument("--batch-iters", type=int, default=1,
                    help="--chips 4: iterations of the multi_sssp batch")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU found: JAX's devices are {devices}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} TPU "
                         f"chips; found {len(devices)}")
    log(f"device: {dev.device_kind} x{len(devices)} "
        f"(platform {dev.platform}), jax {jax.__version__}")

    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    s = build(args.log2n, args.seed)
    if args.chips == 4:
        four_chips(s, batch_iters=args.batch_iters, seed=args.seed)
    else:
        one_chip(s)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
