"""Paper bridge (DESIGN.md §4): when does RB-coded token dispatch beat plain
all-to-all for MoE expert parallelism?

Token->expert dispatch is a bipartite shuffle: tokens on one side, experts on
the other, an edge where the router sends a token. Replicating token shards
r x across EP groups (Theorem-2 allocation) enables coded multicast of the
dispatched activations, cutting dispatch bytes ~1/r at the price of r x Map
(= router + pre-dispatch) compute. Model on v5e numbers:

  t_dispatch(r) = (T * topk * d * 2 bytes) / r / (chips * ici_bw)
  t_expert      = (3 * 2 * T * topk * d * d_ff) / (chips * peak)
  t_router(r)   = r * (2 * T * d * E) / (chips * peak)

Coding wins iff the saved dispatch time exceeds the added router/Map time -
i.e. only in the dispatch-bound regime (small d_ff_expert / high top-k).
"""
from repro.launch.roofline import peaks

V5E = peaks("TPU v5 lite")


def analyze(T, d, d_ff, E, topk, chips, r_values=(1, 2, 4)):
    rows = []
    for r in r_values:
        t_disp = T * topk * d * 2 / r / (chips * V5E.ici_bw)
        t_expert = 3 * 2 * T * topk * d * d_ff / (chips * V5E.flops_bf16)
        t_router = r * 2 * T * d * E / (chips * V5E.flops_bf16)
        rows.append((r, t_disp, t_expert, t_router,
                     t_disp + t_expert + t_router))
    return rows


def run(report):
    cases = {
        # (tokens/step, d_model, d_ff_expert, E, topk)
        "llama4_moe": (1_048_576, 5120, 8192, 128, 1),
        "deepseek_moe": (1_048_576, 5120, 1536, 160, 6),
        "dispatch_bound_hypo": (1_048_576, 5120, 256, 256, 8),
    }
    for name, (T, d, dff, E, k) in cases.items():
        rows = analyze(T, d, dff, E, k, chips=256)
        base = rows[0][-1]
        best = min(rows, key=lambda x: x[-1])
        report(f"coded_dispatch_{name}", base * 1e6,
               f"best_r={best[0]} speedup={base / best[-1]:.3f} "
               f"t_disp_r1={rows[0][1] * 1e3:.2f}ms t_expert={rows[0][2] * 1e3:.2f}ms")
    # Conclusion mirrors DESIGN.md §4: for the two assigned MoE archs the
    # expert FLOPs dominate dispatch, so r=1 is optimal; coding only pays in
    # contrived dispatch-bound settings.
