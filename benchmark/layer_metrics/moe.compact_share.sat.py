"""experts: of the layer runs of the window's prefills, the share that took the compact branch (a layer that holds a share of its experts running over the capacity's rows and not over every pair), from the deltas of stats()["moe_prefill"]: compact / layer_runs; 0 where every layer holds all its experts."""
from benchmark.harness import phases


@phases.quiet
def read(ctx):
    serve = ctx.get("serve") or {}
    a = (serve.get("before") or {}).get("moe_prefill")
    b = (serve.get("at_end") or {}).get("moe_prefill")
    if not a or not b or b["layer_runs"] <= a["layer_runs"]:
        return None
    runs = b["layer_runs"] - a["layer_runs"]
    phases.note(ctx, "moe_prefill_routing", {
        "layer_runs": runs, "compact": b["compact"] - a["compact"],
        "pairs_per_layer_run": (b["pairs"] - a["pairs"]) / runs})
    return 100.0 * (b["compact"] - a["compact"]) / runs
