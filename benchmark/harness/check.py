"""The comparison that decides ``correct``, on the reference's side: run in
a child process of its own on the CPU backend (``JAX_PLATFORMS=cpu``),
after the window, with the configuration's plain float32 reference
(benchmark/reference/) and the weights the program's init makes from the
same seed.

    python -m benchmark.harness.check <spec.json>    -> one JSON line

train: the reference's loss and gradients on the seeded rows the worker
ran its step on; what AdamW's moments hold after one update with them, by
the formulas written down in sketch.py; and how far the sketch of the
program's moments lies from the sketch of those, leaf by leaf.
serve: for each sampled greedy request, the reference's full forward over
prompt + served tokens; at every generated position, how far the served
token's reference logit lies under the largest one.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
from typing import Any, Dict

from .manifest import ROOT


def _reference(config: Dict[str, Any]):
    from .families import family_of

    fam = family_of(config)
    ref = importlib.import_module(f"benchmark.reference.{fam.reference}")
    return fam, ref


def _params(fam, config: Dict[str, Any], seed: int):
    import jax

    cfg = fam.program_config(config, attn_impl="dense", remat=False)
    return fam.init(cfg, jax.random.PRNGKey(seed))


def main(path: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    with open(path) as f:
        spec = json.load(f)
    if jax.default_backend() != "cpu":
        raise SystemExit("the reference runs on the CPU backend")
    fam, ref = _reference(spec["config"])
    params = _params(fam, spec["config"], spec["seed"])
    if spec["kind"] == "train":
        from . import sketch
        from .train_runner import check_rows

        rows = jnp.asarray(check_rows(spec))
        opt = spec["traffic"]["step"]["optimizer"]
        loss, grads = jax.jit(
            lambda p: ref.loss_and_grads(spec["config"], p, rows))(params)
        mu, nu, norm = sketch.adam_first_step(
            grads, opt["b1"], opt["b2"], opt["grad_clip"])
        want = jax.tree_util.tree_map(np.asarray,
                                      jax.jit(sketch.sketch)(mu, nu))
        with np.load(spec["check_file"]) as flat:
            got = sketch.unflatten({k: flat[k] for k in flat.files})
        print(json.dumps(dict(sketch.compare(got, want),
                              ref_loss=float(loss),
                              ref_grad_norm=float(norm))))
        return
    worst, per_request = 0.0, []
    for s in spec["samples"]:
        seq = np.asarray(s["prompt"] + s["tokens"][:-1], np.int32)[None]
        logits = np.asarray(ref.forward(spec["config"], params,
                                        jnp.asarray(seq)))[0]
        first = len(s["prompt"]) - 1
        rows = logits[first:first + len(s["tokens"])]
        served = rows[np.arange(len(s["tokens"])), s["tokens"]]
        gap = float(np.max(rows.max(axis=-1) - served))
        per_request.append({"index": s["index"], "positions": len(seq[0]),
                            "max_gap": gap,
                            "argmax_agree": float(np.mean(
                                rows.argmax(axis=-1) == s["tokens"]))})
        worst = max(worst, gap)
    print(json.dumps({"max_logit_gap": worst, "requests": per_request}))


def judge_train(got: Dict[str, float], ref: Dict[str, Any],
                limits: Dict[str, Any]) -> Dict[str, Any]:
    """The training verdict: how far the program's check step lies from
    the reference, against the traffic file's ``check`` tolerances."""
    apart = {
        "loss": abs(ref["ref_loss"] - got["loss"]),
        "grad_norm_rel": abs(ref["ref_grad_norm"] - got["grad_norm"])
        / ref["ref_grad_norm"],
        "max_leaf_rel": ref["max_leaf_rel"],
        "median_leaf_rel": ref["median_leaf_rel"],
        "max_second_rel": ref["max_second_rel"]}
    allowed = {"loss": limits["loss_tolerance"],
               "grad_norm_rel": limits["grad_tolerance"],
               "max_leaf_rel": limits["grad_tolerance"],
               "median_leaf_rel": limits["grad_median_tolerance"],
               "max_second_rel": limits["grad_tolerance"]}
    return {"program": got, "reference": ref, "apart": apart,
            "limits": allowed,
            "problems": [
                f"the step on the seeded rows against the reference: "
                f"{what} {apart[what]} > {limit} (worst leaf "
                f"{ref['worst_leaf']})"
                for what, limit in allowed.items()
                if not apart[what] <= limit]}


def run_child(spec: Dict[str, Any], out_dir: str, timeout_s: float = 280.0
              ) -> Dict[str, Any]:
    """Write the spec, run the child, return its JSON line."""
    path = os.path.join(out_dir, "check_spec.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.check", path],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=timeout_s)
    if out.returncode != 0:
        raise RuntimeError("the reference's process failed:\n"
                           + out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    main(sys.argv[1])
