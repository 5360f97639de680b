"""Phase-level profiler: span-timed Map/encode/exchange/decode/Reduce vs roofline.

The ROADMAP's kernel-campaign item asks for a profiler-instrumented
phase-timing microbenchmark connected to `launch/roofline.py`. This module
is it: it replays a coded PageRank session under an enabled `obs.Tracer`,
aggregates the per-phase spans the engine emits (`phase.map` /
`phase.encode` / `phase.exchange` / `phase.decode` / `phase.reduce`),
cross-checks that the summed exchange-span bits equal the run's
`shuffle_bits`, and judges each phase's measured seconds + payload bytes
against its bandwidth roof (`launch.roofline.phase_roofline`: HBM for the
streaming phases, ICI for the exchange) - printing a %-of-roofline figure
per phase. The roofs are the published peaks of the device the run found
(`launch.roofline.PEAKS`, keyed by `device_kind`); off a TPU there is no
device figure, and every share prints as "not measured". The phases are
host spans: even on a TPU they time host NumPy around the device work.

Outputs: per-phase report rows, the CI-gated ``scale_phase_profile_*``
record (untraced replay wall-clock, so the gate measures the engine, not
the tracer), and - via ``--trace PATH`` - a Chrome-trace JSON artifact
loadable in chrome://tracing or ui.perfetto.dev.
"""
import argparse
import pathlib
import sys

try:
    import repro  # noqa: F401  (run.py already put src/ on the path)
except ImportError:
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro import graphs, obs
from repro.core import algorithms as algo
from repro.core import engine
from repro.core.allocation import divisible_n, er_allocation
from repro.launch.compile_cache import use_compile_cache
from repro.launch.roofline import PHASE_ROOFS, phase_roofline

SMOKE = {"n": 360, "K": 4, "r": 2, "p": 0.05, "iters": 3}
FULL = {"n": 2048, "K": 10, "r": 3, "p": 0.01, "iters": 10}

PHASES = ("map", "encode", "exchange", "decode", "reduce")


def _phase_bytes_per_iter(plan, g) -> dict:
    """Payload-byte estimates of one iteration's phases (float32/uint32).

    Deliberately simple traffic models - each counts the arrays a phase
    streams, not cache behavior: Map reads the state and writes the [nnz]
    edge values; encode gathers the covered-pair words and builds + XORs
    the [C, r] slot words; the exchange moves the schedule's
    bits-on-the-wire (counted from the spans' exact `bits` attrs, so it is
    NOT estimated here); decode re-masks the slot words, shifts the pair
    segments back, and writes the delivery vector; Reduce gathers every
    CSR entry and writes the new state.
    """
    n, nnz = g.n, g.csr.nnz
    P = int(plan.pair_k.size)        # covered pairs
    C = int(plan.col_width.size)     # coded columns
    M = int(plan.all_k.size)         # delivered values
    r = plan.r
    return {
        "map": 4 * (n + nnz),
        "encode": 4 * (P + 2 * C * r + C),
        "exchange": None,            # exact, from the span bits
        "decode": 4 * (C * r + P * r + M),
        "reduce": 4 * (2 * nnz + n),
    }


def profile(smoke: bool = False, trace_path: str | None = None) -> dict:
    """Trace one coded PageRank session; return the per-phase profile."""
    cfg = SMOKE if smoke else FULL
    n = divisible_n(cfg["n"], cfg["K"], cfg["r"])
    iters = cfg["iters"]
    g = graphs.erdos_renyi(n, cfg["p"], seed=7)
    alloc = er_allocation(n, cfg["K"], cfg["r"])

    tracer = obs.Tracer(enabled=True)
    prev = obs.set_tracer(tracer)
    try:
        sess = engine.compile(algo.pagerank(), g, alloc, "coded",
                              path="sparse")
        res = sess.run(iters)
    finally:
        obs.set_tracer(prev)

    span_bits = sum(s.attrs["bits"] for s in tracer.find("phase.exchange"))
    if span_bits != res.shuffle_bits:
        raise AssertionError(
            f"span bits {span_bits} != run shuffle_bits {res.shuffle_bits}")

    import jax

    dev = jax.devices()[0]
    est = _phase_bytes_per_iter(sess.plan, g)
    phases = {}
    for ph in PHASES:
        spans = tracer.find(f"phase.{ph}")
        secs = sum(s.duration_s for s in spans)
        byts = (span_bits / 8 if ph == "exchange"
                else est[ph] * len(spans))
        frac = (phase_roofline(ph, secs, byts, device_kind=dev.device_kind,
                               chips=cfg["K"]).fraction
                if dev.platform == "tpu" else None)
        phases[ph] = {"count": len(spans), "seconds": secs,
                      "bytes": byts, "roof": PHASE_ROOFS[ph],
                      "roofline_fraction": frac}

    if trace_path:
        tracer.dump_chrome_trace(trace_path)

    # The CI-gated wall-clock replays the session *untraced* so the
    # regression gate watches the engine, not the tracer.
    m = obs.measure(lambda: sess.run(iters), reps=3, warmup=0)
    return {"n": n, "K": cfg["K"], "r": cfg["r"], "iters": iters,
            "edges": g.num_edges, "shuffle_bits": res.shuffle_bits,
            "phases": phases, "untraced_s_per_iter": m.best_s / iters,
            "trace_path": trace_path}


def _share(p: dict) -> str:
    f = p["roofline_fraction"]
    return "not measured" if f is None else f"{100 * f:.4f}%"


def _fractions_str(phases: dict) -> str:
    return " ".join(f"{ph}:{_share(p)}({p['roof']})"
                    for ph, p in phases.items())


def run(report, smoke: bool = False, trace_path: str | None = None) -> dict:
    prof = profile(smoke=smoke, trace_path=trace_path)
    phases = prof["phases"]
    for ph, p in phases.items():
        report(f"phase_{ph}_n{prof['n']}",
               p["seconds"] / max(p["count"], 1) * 1e6,
               f"bytes_per_iter={p['bytes'] / max(p['count'], 1):.0f} "
               f"roof={p['roof']} "
               f"roofline={_share(p)}")
    total = sum(p["seconds"] for p in phases.values())
    report(f"scale_phase_profile_n{prof['n']}",
           prof["untraced_s_per_iter"] * 1e6,
           f"iters={prof['iters']} edges={prof['edges']} "
           f"bits={prof['shuffle_bits']} phase_s={total:.4f} "
           f"roofline%=[{_fractions_str(phases)}] "
           "(span-attributed phase profile, PR 8)")
    return prof


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized problem (n~360, 3 iterations)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/perfetto JSON artifact")
    args = ap.parse_args(argv)
    use_compile_cache()
    print("name,us_per_call,derived")
    prof = run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}"),
               smoke=args.smoke, trace_path=args.trace)
    ws = max(len(p) for p in PHASES)
    print(f"\nper-phase roofline ({prof['iters']} iterations, "
          f"n={prof['n']}, K={prof['K']}, r={prof['r']}):")
    for ph, p in prof["phases"].items():
        print(f"  {ph:<{ws}}  {p['seconds'] * 1e3:8.2f} ms  "
              f"{p['bytes'] / 1e6:9.3f} MB  vs {p['roof'].upper()} roof: "
              f"{_share(p)} of roofline")
    if args.trace:
        print(f"\ntrace written to {args.trace} "
              "(load in chrome://tracing or ui.perfetto.dev)")


if __name__ == "__main__":
    main()
