"""The one general generator of requests: it reads a traffic file's
parameters (lengths, rates, sharing, sampling) and makes the requests of a
run from ``--seed``.

Every seed gets the SAME set of sizes and arrival gaps, in another order:
lengths are the quantiles of the file's distribution (a stratified sample,
so the work of a run does not depend on the seed's luck), gaps are the
quantiles of the exponential distribution at the file's rate, and the
seed pairs prompt lengths with output lengths and sampling settings,
orders them and draws the token ids.  Where the file gives ``order_block``,
the seed's order is stratified in time as well: every ``order_block``
consecutive requests hold one length from each of ``order_block`` equal
strata of the distribution, so that a window that sees only a part of the
pool sees the file's mix and not the seed's luck.  Where the file gives
``order_seed``, the pairing and the order are drawn from that number and
are the same for every ``--seed``, which then draws only the token ids and
the sampling seeds: for a closed loop whose window ends before the pool
does, where the order decides which requests the window holds and so IS
the amount of work (the driver's check of PR 23 read 5.4% between seeds
and far less between two runs of one).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def _length_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of the distribution, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"length distribution {spec['dist']!r}: the "
                         "generator knows 'lognormal'")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    vals = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def _sampling_plan(shares: List[Dict[str, Any]], n: int) -> List[Dict]:
    """n sampling settings in the shares the file gives (largest remainder),
    to be permuted by the caller."""
    counts = [int(math.floor(s["share"] * n)) for s in shares]
    order = sorted(range(len(shares)),
                   key=lambda i: -(shares[i]["share"] * n - counts[i]))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    plan: List[Dict] = []
    for s, c in zip(shares, counts):
        plan.extend([{k: v for k, v in s.items() if k != "share"}] * c)
    return plan


def _seeded_order(values, block: int, rng: np.random.Generator):
    """``values`` in the seed's order.  With ``block`` (a divisor of their
    number) every ``block`` consecutive ones hold one value from each of
    ``block`` strata of the sorted values, in a shuffled order; without,
    a plain permutation."""
    values = np.sort(np.asarray(values), kind="stable")
    n = len(values)
    if not block or n % block or block >= n:
        return rng.permutation(values)
    per = n // block                 # blocks in the pool = values a stratum
    strata = values.reshape(block, per)
    to_block = np.stack([rng.permutation(per) for _ in range(block)])
    out = np.empty((per, block), values.dtype)
    for b in range(per):
        out[b] = rng.permutation(
            strata[np.arange(block), np.argsort(to_block, axis=1)[:, b]])
    return out.reshape(n)


def _sizes(traffic: Dict[str, Any], n: int, rng: np.random.Generator
           ) -> List[Dict[str, Any]]:
    """The run's n requests as sizes (prompt length, output length,
    sampling), in the seed's order: the same set of lengths and settings
    for every seed; which prompt goes with which output and which setting,
    and in what order they come, is the seed's."""
    block = int(traffic.get("order_block") or 0)
    prompts = _seeded_order(_length_quantiles(traffic["prompt_len"], n),
                            block, rng)
    outputs = _seeded_order(_length_quantiles(traffic["output_len"], n),
                            block, rng)
    plan = _sampling_plan(traffic["sampling"], n)
    out = []
    for i, k in enumerate(_seeded_order(np.arange(n), block, rng)):
        p_len = int(prompts[i])
        out.append({"prompt_len": p_len,
                    "max_tokens": int(min(outputs[i],
                                          traffic["max_total"] - p_len)),
                    "temperature": float(plan[k].get("temperature", 0.0)),
                    "top_p": float(plan[k].get("top_p", 1.0)),
                    "top_k": int(plan[k].get("top_k", 0))})
    return out


def fill(size: Dict[str, Any], index: int, vocab: int, shared: List[int],
         rng: np.random.Generator) -> Dict[str, Any]:
    """One request of the given sizes, its token ids drawn from ``rng``."""
    body = rng.integers(0, vocab,
                        max(size["prompt_len"] - len(shared), 1)).tolist()
    payload = {"prompt": (shared + body)[:size["prompt_len"]],
               "max_tokens": size["max_tokens"],
               "temperature": size["temperature"], "top_p": size["top_p"],
               "top_k": size["top_k"],
               "seed": int(rng.integers(0, 2 ** 31 - 1))}
    return {"index": index, "payload": payload, "size": size,
            "greedy": size["temperature"] == 0.0}


def _requests(traffic: Dict[str, Any], n: int, vocab: int, seed: int
              ) -> List[Dict[str, Any]]:
    """The n sizes in the seed's order (the file's own, where it gives
    ``order_seed``), filled with the seed's tokens."""
    rng = np.random.default_rng([seed, 0x7261])
    order = traffic.get("order_seed")
    sizes = _sizes(traffic, n, rng if order is None
                   else np.random.default_rng([int(order), 0x7261]))
    shared = rng.integers(
        0, vocab, int(traffic.get("shared_prefix_tokens") or 0)).tolist()
    return [fill(size, i, vocab, shared, rng)
            for i, size in enumerate(sizes)]


def open_loop(traffic: Dict[str, Any], vocab: int, seed: int,
              seconds: float) -> List[Dict[str, Any]]:
    """The requests due inside ``seconds`` at the file's fixed rate, each
    with its due time: round(rate x seconds) of them, their gaps the
    exponential distribution's quantiles in the seed's order, scaled so
    that the last one is due before the window ends."""
    rate = float(traffic["rate_rps"])
    n = max(int(round(rate * seconds)), 1)
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError("arrivals: the generator knows 'poisson'")
    rng = np.random.default_rng([seed, 0x6172])
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    # The same n gaps for every seed (the first is the wait before the
    # first request), scaled once so that the last request is due inside
    # the window.
    total = float(gaps.sum()) / rate
    gaps = rng.permutation(gaps) / rate
    due = np.cumsum(gaps) * min(1.0, seconds * (n - 0.5) / n / total)
    reqs = _requests(traffic, n, vocab, seed)
    for r, d in zip(reqs, due):
        r["due_s"] = float(d)
    return reqs


def closed_loop(traffic: Dict[str, Any], vocab: int, seed: int
                ) -> List[Dict[str, Any]]:
    """The closed loop's pool in the seed's order (or the file's, see
    ``order_seed``).  The clients take the
    requests one after another from its head (client.py); a run that
    outlasts the pool starts it over with fresh token ids."""
    return _requests(traffic, int(traffic["pool"]), vocab, seed)


def prefill_buckets(traffic: Dict[str, Any], floor: int = 8) -> List[int]:
    """The engine's prefill buckets (powers of two from ``floor``) that the
    file's prompt lengths can reach: the shapes a run warms up."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    out, b = [], floor
    while True:
        if b >= lo:
            out.append(b)
        if b >= hi:
            return out
        b *= 2
