"""What the engine's ledger of runs says of a serving window (PR 51; beside
phases.py and trace.py, which are used as they are).

``stats()["runs"]`` is ``{program: {runs, rows, tokens, paced_s, by_ms,
s_by_ms}}`` by the names ``llm_decode`` / ``llm_prefill[<bucket>]``,
cumulative; ``paced_s`` is what a program cost the engine's loop, from
the ids of the program before it on the host to its own (llm/engine.py).
The runner hands every reader the replica's ``stats()`` at both edges of
the window, so the four counter readers take the WHOLE window's delta:
every prefill of the window by its bucket, where a capture holds a tenth
of them.  Between the two readings, the programs' ``paced_s`` and
``runs_unpaced_s`` (+ ``runs_voided_s``: a compile, none in a window) add
up to the time from the last delivery before the first reading to the
last one before the second: ``counted_s`` to within one program.

The capture's reader FILES device runs by fingerprint (``jit_fwd(<n>)``
is one compiled program, so one name); the ORDER only votes on which
name a fingerprint has, and gives the pairs the clock offset is read
from.  The ``.fetch`` leaves carry the tags ``program`` and ``run`` of
the flight they deliver, and the k-th ``jit_fwd`` run to end on the
device is the k-th ``.fetch`` to end on the host, as long as the capture
lost no annotation between them: ``run``, the flight's ordinal in
``stats()["runs"][program]["runs"]``, says whether it did (a program's
ordinals count up by one; only the pairs before the first gap vote), and
where in the window's ledger the capture lies.  Which run a capture's
first annotation belongs to is not known beforehand (an annotation open
when the capture starts leaves no event, a device run may), so the
pairing takes the shift under which every fingerprint goes with ONE name.

The four counter metrics are printed by a ``--trace 1`` run only
(benchmark/run.py), so the ledger's value is the TRACED window's: the
same as an untraced window's to 0.2 ms and 0.3 points where the decode
program leaves the host slack, and NOT where the host's step is about as
long as the program (PERF.md section 5, my chip runs, PR 51:
``decode.paced_ms.sat`` +1.25 | +1.25 | +1.1 ms, ``prefill.paced_share.sat``
-2.0 | -4.1 | -2.7 points and tokens/s -14 | -5 | -4% in
``serve-gpt2-large-sat`` | ``serve-kimi-k2.5-4k`` |
``serve-xing4.0-29b-a4b-4k``; ``engine.stall_share.sat`` 0.9-1.1% traced
for 0.0 untraced in OLMoE, LFM2 and Olmo-Hybrid).  Until a ``benchmark``
PR prints ``window`` in the untraced run (ROADMAP Design 5), hold a
traced reading against a traced one.

A program without the ledger or the tags (the parent of PR 51) gives
every reader nothing to read: each returns None.
"""

from __future__ import annotations

import bisect
import re
import statistics
from collections import Counter, defaultdict
from typing import Any, Dict, List, Optional, Tuple

from . import phases, trace as T

DECODE = "llm_decode"
PREFILL = re.compile(r"^llm_prefill\[(\d+)\]$")
FETCHES = ("llm.decode.fetch", "llm.prefill.fetch")
SAMPLER = "jit_sample_tokens"
SHIFTS = 4      # how far apart the two sequences' first events may lie


def bucket_of(program: str) -> Optional[int]:
    m = PREFILL.match(program)
    return int(m.group(1)) if m else None


def bin_label(i: int, bins: int = 16) -> str:
    """The milliseconds bin ``i`` of ``by_ms`` holds."""
    if i == 0:
        return "<1"
    return f">={2 ** (i - 1)}" if i == bins - 1 \
        else f"{2 ** (i - 1)}-{2 ** i}"


# ------------------------------------------------------- the counters

def window(ctx) -> Optional[Dict[str, Any]]:
    """The window's delta of the ledger: ``programs`` by name (a program
    that did not run in the window left out; ``timed`` = the runs whose
    interval is in the sums), ``unpaced_s``, ``voided_s``, ``counted_s``
    and the identity's residue."""
    if "_run_ledger" not in ctx:
        ctx["_run_ledger"] = out = _window(ctx)
        if out:
            phases.note(ctx, "run_ledger", _noted(ctx, out))
    return ctx["_run_ledger"]


def _window(ctx) -> Optional[Dict[str, Any]]:
    serve = ctx.get("serve") or {}
    a, b = serve.get("before") or {}, serve.get("at_end") or {}
    if "runs" not in a or "runs" not in b or not serve.get("counted_s"):
        return None
    programs = {}
    for name, now in b["runs"].items():
        was = a["runs"].get(name) or {}
        d = {k: now[k] - was.get(k, 0)
             for k in ("runs", "rows", "tokens", "paced_s")}
        for k in ("by_ms", "s_by_ms"):
            d[k] = [x - y for x, y in zip(
                now[k], was.get(k) or [0] * len(now[k]))]
        d["timed"] = sum(d["by_ms"])
        if d["runs"] > 0:
            programs[name] = d
    if not programs:
        return None
    out = {"programs": programs, "counted_s": float(serve["counted_s"]),
           "unpaced_s": b["runs_unpaced_s"] - a["runs_unpaced_s"],
           "voided_s": b["runs_voided_s"] - a["runs_voided_s"]}
    out["paced_s"] = sum(p["paced_s"] for p in programs.values())
    out["residue"] = (out["paced_s"] + out["unpaced_s"] + out["voided_s"]
                      ) / out["counted_s"] - 1.0
    return out


def _noted(ctx, w: Dict[str, Any]) -> Dict[str, Any]:
    """For the `info` line: the ledger by program, the identities held
    against the engine's other counters, and the residue."""
    serve = ctx["serve"]

    def gained(*path):
        x, y = serve["at_end"], serve["before"]
        try:
            for key in path:
                x, y = x[key], y[key]
            return x - y
        except (KeyError, TypeError):
            return None

    discarded = gained("pipeline", "rows_discarded")
    pre = {n: p for n, p in w["programs"].items() if bucket_of(n)}
    return {
        "counted_s": w["counted_s"], "paced_s": w["paced_s"],
        "unpaced_s": w["unpaced_s"], "voided_s": w["voided_s"],
        "paced_plus_unpaced_over_counted_less_one": w["residue"],
        "by_program": {
            n: {"runs": p["runs"], "rows": p["rows"],
                "tokens": p["tokens"], "paced_s": p["paced_s"],
                "paced_ms_a_run": 1e3 * p["paced_s"] / p["timed"]
                if p["timed"] else None,
                "share_pct": 100.0 * p["paced_s"] / w["counted_s"],
                "by_ms": {bin_label(i, len(p["by_ms"])): n_runs
                          for i, n_runs in enumerate(p["by_ms"]) if n_runs}}
            for n, p in sorted(w["programs"].items(),
                               key=lambda kv: bucket_of(kv[0]) or 0)},
        # the engine's other counters over the same window: a program in
        # the air at either edge is launched and not yet delivered, so
        # each pair may differ by one
        "decode_runs": [w["programs"].get(DECODE, {}).get("runs", 0),
                        gained("attention", "decode_runs")],
        "prefills": [sum(p["runs"] for p in pre.values()),
                     gained("prefills")],
        "rows": [sum(p["rows"] for p in w["programs"].values()),
                 None if discarded is None
                 else gained("tokens_generated") + discarded],
        "prefill_tokens": [sum(p["tokens"] for p in pre.values()),
                           gained("prefill_bucket_tokens")]}


def decode_paced_ms(ctx) -> Optional[float]:
    w = window(ctx)
    d = w and w["programs"].get(DECODE)
    return 1e3 * d["paced_s"] / d["timed"] if d and d["timed"] else None


def _prefills(ctx) -> Optional[Dict[int, Dict[str, Any]]]:
    """The window's timed prefills by bucket."""
    w = window(ctx)
    if not w:
        return None
    return {bucket_of(n): p for n, p in w["programs"].items()
            if bucket_of(n) and p["timed"]} or None


def prefill_paced_share_pct(ctx) -> Optional[float]:
    pre = _prefills(ctx)
    if not pre:
        return None
    return 100.0 * sum(p["paced_s"] for p in pre.values()) \
        / window(ctx)["counted_s"]


def prefill_paced_ms_per_ktok(ctx) -> Optional[float]:
    pre = _prefills(ctx)
    if not pre:
        return None
    phases.note(ctx, "prefill_paced_ms_per_ktok_by_bucket", {
        str(b): 1e6 * p["paced_s"] / (p["timed"] * b)
        for b, p in sorted(pre.items())})
    return 1e6 * sum(p["paced_s"] for p in pre.values()) \
        / sum(p["timed"] * b for b, p in pre.items())


def stall_share_pct(ctx) -> Optional[float]:
    """Per program: the seconds in bins at least FOUR times the bin that
    holds its median run, less what that many runs of the median bin's
    own mean take; summed, over ``counted_s``."""
    w = window(ctx)
    if not w:
        return None
    stalled, slowest = 0.0, None
    by_program = {}
    for name, p in w["programs"].items():
        if not p["timed"]:
            continue
        seen, mid = 0, 0
        for mid, n in enumerate(p["by_ms"]):
            seen += n
            if 2 * seen >= p["timed"]:
                break
        usual = p["s_by_ms"][mid] / p["by_ms"][mid]
        extra = sum(p["s_by_ms"][i] - p["by_ms"][i] * usual
                    for i in range(mid + 2, len(p["by_ms"])))
        last = max(i for i, n in enumerate(p["by_ms"]) if n)
        if slowest is None or last > slowest[1]:
            slowest = (name, last, len(p["by_ms"]))
        if extra > 0:
            by_program[name] = extra
            stalled += extra
    if slowest is None:
        return None
    phases.note(ctx, "stall", {
        "stalled_s": stalled, "stalled_s_by_program": by_program,
        "slowest_run": {"program": slowest[0],
                        "ms": bin_label(slowest[1], slowest[2])}})
    return 100.0 * stalled / w["counted_s"]


# -------------------------------------------------------- the capture

def _runs_of(dev, fn: str) -> List[Tuple[float, float, str]]:
    """(end, start, program) of the runs of ``jit_<fn>``, by their end."""
    return sorted((s + d, s, name) for name, s, d in dev.modules
                  if name.split("(", 1)[0] == fn)


def _paired(runs, fetches, shift: int):
    """(run, fetch) where fetch ``k`` goes with run ``k + shift``."""
    return [(runs[k + shift], fetch) for k, fetch in enumerate(fetches)
            if 0 <= k + shift < len(runs)]


def _pair(runs: List[tuple], fetches: List[tuple]) -> Tuple[int, int]:
    """(shift, conflicts): the shift that files each fingerprint under
    the fewest names and each name under the fewest fingerprints; among
    equals (a capture of one program) the one that puts a fetch's end
    nearest its run's.  The runs are then filed by fingerprint: all the
    order decides is each fingerprint's name, by the pairs' votes."""
    best = None
    for shift in range(-SHIFTS, SHIFTS + 1):
        names, prints, waits = defaultdict(set), defaultdict(set), []
        for (r_end, _, fp), (f_end, program, _) in _paired(runs, fetches,
                                                            shift):
            names[fp].add(program)
            prints[program].add(fp)
            waits.append(f_end - r_end)
        if len(waits) < max(min(len(runs), len(fetches)) - SHIFTS, 1):
            continue
        conflicts = sum(len(v) - 1 for v in names.values()) \
            + sum(len(v) - 1 for v in prints.values())
        key = (conflicts, abs(statistics.median(waits)))
        if best is None or key < best[0]:
            best = (key, shift)
    return (best[1], best[0][0]) if best else (0, -1)


def _ordinals(ctx, fetches: List[tuple]) -> Tuple[int, Dict[str, Any]]:
    """What the fetches' ``run`` tags say: (how many of ``fetches`` lie
    before the first gap in a program's ordinals, for `info`: each
    program's first and last ordinal, the ordinals between them that the
    capture lacks, and the window's own, ``stats()["runs"]`` at its two
    edges, which hold the capture's where it lay inside the window)."""
    sound, at = len(fetches), {}
    seen: Dict[str, List[int]] = defaultdict(list)
    for k, (_, program, run) in enumerate(fetches):
        if seen[program] and run != seen[program][-1] + 1:
            # the lost one ended somewhere after this program's last
            sound = min(sound, at[program] + 1)
        seen[program].append(run)
        at[program] = k
    serve = ctx.get("serve") or {}
    a, b = ((serve.get(edge) or {}).get("runs") or {}
            for edge in ("before", "at_end"))
    of_window = {p: [(a.get(p) or {}).get("runs", 0), b[p]["runs"]]
                 for p in sorted(seen) if p in b}
    return sound, {
        "ordinals": {p: [min(r), max(r)] for p, r in sorted(seen.items())},
        "fetches_lost": sum(max(r) - min(r) + 1 - len(set(r))
                            for r in seen.values()),
        "window_ordinals": of_window,
        "in_window": bool(of_window) and all(
            p in of_window and of_window[p][0] <= min(r)
            and max(r) < of_window[p][1] for p, r in seen.items())}


def capture(ctx) -> Optional[Dict[str, Any]]:
    """Every ``jit_fwd`` run of the capture filed under the program its
    fingerprint goes with, by the votes of the ``.fetch`` leaves paired
    with the runs in order; the device time of the WHOLE prefill runs by
    bucket; the clock offset the pairs show; the ledger's ordinals."""
    if "_run_ledger_capture" not in ctx:
        ctx["_run_ledger_capture"] = out = _capture(ctx)
        if out:
            phases.note(ctx, "run_ledger_capture", out)
    return ctx["_run_ledger_capture"]


def _capture(ctx) -> Optional[Dict[str, Any]]:
    tr = phases.again(ctx)
    if not tr or not tr.devices:
        return None
    fetches = sorted(
        (end, str(tags["program"]), int(tags["run"]))
        for leaf in FETCHES
        for _, end, tags in phases.annotation_tags(ctx["trace_path"], leaf)
        if "program" in tags and "run" in tags)
    dev = tr.devices[0]
    runs = _runs_of(dev, "jit_fwd")
    if not fetches or not runs:
        return None
    sound, ordinals = _ordinals(ctx, fetches)
    shift, conflicts = _pair(runs, fetches[:sound])
    pairs = _paired(runs, fetches[:sound], shift)
    if not pairs:
        return None
    votes: Dict[str, Counter] = defaultdict(Counter)
    for (_, _, fp), (_, program, _) in pairs:
        votes[fp][program] += 1
    name_of = {fp: c.most_common(1)[0][0] for fp, c in votes.items()}

    # the ids leave the device when the sampler that follows the forward
    # ends (the first to start at or after it, before the next forward
    # does): fetch end less that is the two clocks' offset plus the copy
    samplers = sorted((s, e) for e, s, _ in _runs_of(dev, SAMPLER))
    fwd_starts = sorted(s for _, s, _ in runs)
    offsets = []
    for (r_end, _, _), (f_end, _, _) in pairs:
        ready = r_end
        j = bisect.bisect_left(samplers, (r_end, r_end))
        nxt = bisect.bisect_left(fwd_starts, r_end)
        if j < len(samplers) and (nxt == len(fwd_starts)
                                  or samplers[j][0] < fwd_starts[nxt]):
            ready = samplers[j][1]
        offsets.append((f_end - ready) / 1e6)

    lo, hi = T.window_of(tr)
    kept: Dict[str, List[float]] = defaultdict(list)
    dropped: Counter = Counter()
    unfiled = 0
    for end, start, fp in runs:
        program = name_of.get(fp)
        if program is None:
            unfiled += 1
        elif lo < start and end < hi:
            kept[program].append((end - start) / 1e6)
        else:
            dropped[program] += 1
    q1, _, q3 = statistics.quantiles(offsets, n=4) if len(offsets) > 1 \
        else (offsets[0],) * 3
    by_bucket = {bucket_of(p): v for p, v in kept.items() if bucket_of(p)}
    return {
        "fwd_runs": len(runs), "fetches": len(fetches), "shift": shift,
        "conflicts": conflicts, "unfiled": unfiled, **ordinals,
        # the device's runs of a program less the ledger's count between
        # its first and last fetch: what the device's side of the capture
        # holds beyond the host's at its two edges (seen: -1 to +8)
        "runs_less_ordinals": {
            p: len(kept.get(p, ())) + dropped[p] - (last - first + 1)
            for p, (first, last) in ordinals["ordinals"].items()},
        "programs": {fp: name_of.get(fp)
                     for fp in sorted({fp for _, _, fp in runs})},
        "kept": {p: len(v) for p, v in sorted(kept.items())},
        "dropped": dict(dropped),
        "decode_ms": statistics.fmean(kept[DECODE]) if kept.get(DECODE)
        else None,
        "device_ms_by_bucket": {str(b): statistics.fmean(v)
                                for b, v in sorted(by_bucket.items())},
        "prefill_device_ms": sum(sum(v) for v in by_bucket.values()),
        "prefill_tokens": sum(b * len(v) for b, v in by_bucket.items()),
        "clock_offset_ms": {"median": statistics.median(offsets),
                            "spread": q3 - q1, "least": min(offsets),
                            "pairs": len(offsets)}}


def prefill_device_ms_per_ktok(ctx) -> Optional[float]:
    cap = capture(ctx)
    if not cap or not cap["prefill_tokens"]:
        return None
    return 1e3 * cap["prefill_device_ms"] / cap["prefill_tokens"]
