"""experts: device time per prefill run (a jit_fwd run that starts inside an llm.prefill annotation and in no llm.decode one: phases.serve_capture's rule) of the operations under the scopes moe.route, moe.dispatch, moe.experts and moe.combine and of the compiler's ragged-dot-* kernels (filed as moe_phases files a decode run's), all layers; by scope and by bucket in the info line."""
from collections import defaultdict

from benchmark.harness import moe_phases, phases, ssm_phases, trace as T


@phases.quiet
def read(ctx):
    tr = phases.again(ctx)
    if not tr or not tr.devices:
        return None
    lo, hi = T.window_of(tr)
    dev = tr.devices[0]
    decodes = phases._spans(tr, "llm.decode")
    tagged = phases.annotation_tags(ctx["trace_path"], "llm.prefill")
    prefills = [(s, e) for s, e, _ in tagged]
    runs = [run for run in ssm_phases._fwd_runs(dev, lo, hi, prefills)
            if phases._covering(decodes, run[0]) is None]
    buckets = [str(tagged[phases._covering(prefills, s)][2].get("bucket", "?"))
               for s, _ in runs]
    scopes = phases.op_scopes(ctx["trace_path"])
    if not runs or not scopes:
        return None
    by_scope = defaultdict(float)
    by_bucket = defaultdict(float)
    for name, s, e in T._leaves(dev, lo, hi):
        i = phases._covering(runs, s)
        scope = None if i is None \
            else moe_phases._filed_under(name, scopes.get(name))
        if scope:
            by_scope[scope] += (e - s) / 1e6
            by_bucket[buckets[i]] += (e - s) / 1e6
    if not by_scope:
        return None
    phases.note(ctx, "moe_prefill_capture", {
        "prefill_runs": len(runs),
        "ms_by_scope": {k: v / len(runs) for k, v in by_scope.items()},
        "ms_by_bucket": {b: v / buckets.count(b)
                         for b, v in sorted(by_bucket.items())}})
    return sum(by_scope.values()) / len(runs)
