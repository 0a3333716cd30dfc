"""A copy of the benchmark in a scratch root with a tiny preset beside it,
for CPU rehearsals: the real harness code, the real readers, and cells
small enough for the CPU backend.  Everything tiny is ADDED as new files
and new entries; nothing that is there is edited, which is how a later PR
adds a cell."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TINY_CONFIG = {
    "family": "gpt2", "source": "a tiny preset for CPU rehearsals",
    "vocab_size": 512, "n_positions": 128, "n_ctx": 128, "n_embd": 64,
    "n_layer": 2, "n_head": 4, "n_inner": 128,
    "activation_function": "gelu_new", "layer_norm_epsilon": 1e-05,
    "compute_dtype": "bfloat16", "param_dtype": "float32", "reduced": []}

TINY_TRAIN = {
    "kind": "train", "global_batch": 4, "seq_len": 128,
    "data": {"blocks": 2, "rows_per_block": 8, "zipf_a": 1.2},
    "step": {"attn_impl": "flash", "remat": True, "loss_chunk": 64,
             "optimizer": {"learning_rate": 0.001, "warmup_steps": 2,
                           "total_steps": 100, "weight_decay": 0.1,
                           "grad_clip": 1.0, "b1": 0.9, "b2": 0.95}},
    "warmup_steps": 2,
    "trace": {"skip_steps": 1, "steps": 2},
    "check": {"rows": 2, "loss_tolerance": 0.01, "grad_tolerance": 0.25,
              "grad_median_tolerance": 0.05, "reason": "rehearsal"}}

_LENGTHS = {
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 4, "max": 60},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                   "min": 2, "max": 12},
    "max_total": 128,
    "sampling": [{"share": 0.8, "temperature": 0.0},
                 {"share": 0.2, "temperature": 0.8, "top_p": 0.95}],
    "shared_prefix_tokens": 0,
    "check": {"greedy_sample": 2, "max_positions": 128,
              "logit_tolerance": 0.25, "reason": "rehearsal"}}
TINY_CLOSED = dict(_LENGTHS, kind="serve_closed", clients=6, pool=16,
                   order_block=4, order_seed=1, fill_limit_s=30)
TINY_OPEN = dict(_LENGTHS, kind="serve_open", rate_rps=4.0,
                 arrivals="poisson", client_threads=16)

TINY_CELLS = {
    "tiny-train": ("tiny-pretrain", 1, {"mesh": {"fsdp": 1, "tensor": 1}}),
    "tiny-train-2x2": ("tiny-pretrain-2x2", 4,
                       {"mesh": {"fsdp": 2, "tensor": 2}}),
    "tiny-sat": ("tiny-closed", 1,
                 {"engine": {"page_size": 4, "num_pages": 128,
                             "max_batch": 4}}),
    "tiny-steady": ("tiny-open", 1,
                    {"engine": {"page_size": 4, "num_pages": 256,
                                "max_batch": 8}}),
}
_LIKE = {"tiny-train": "train-gpt2-124m",
         "tiny-train-2x2": "train-gpt2-large-fsdp2x2",
         "tiny-sat": "serve-gpt2-large-sat"}

# The open-loop cell has no cell in BENCHMARK.json yet (PERF.md, Open
# questions): its rehearsal brings its own end-to-end entries, as the PR
# that adds the cell will (with the per-layer readers it wants, as files).
OPEN_LOOP_METRICS = {
    "end_to_end": [
        {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"},
        {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock"}]}


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def build(root: str) -> str:
    """Copy the benchmark to ``root`` and add the tiny cells.  Returns
    ``root``."""
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "gpt2-tiny", "source": TINY_CONFIG["source"],
        "file": "benchmark/configs/gpt2-tiny.json", "reduced": [],
        "why": "CPU rehearsal"})
    _write(os.path.join(root, "benchmark/configs/gpt2-tiny.json"),
           TINY_CONFIG)
    for name, obj in (("tiny-pretrain", TINY_TRAIN),
                      ("tiny-pretrain-2x2", TINY_TRAIN),
                      ("tiny-closed", TINY_CLOSED),
                      ("tiny-open", TINY_OPEN)):
        _write(os.path.join(root, f"benchmark/traffic/{name}.json"), obj)
    for group, entries in OPEN_LOOP_METRICS.items():
        manifest[group].extend(dict(e, workloads=["tiny-steady"])
                               for e in entries)
    for cell, (traffic, chips, settings) in TINY_CELLS.items():
        manifest["workloads"].append({
            "name": cell, "config": "gpt2-tiny", "traffic": traffic,
            "chips": chips, "why": "CPU rehearsal"})
        _write(os.path.join(root, f"benchmark/cells/{cell}.json"), settings)
        # The tiny cell reports what the cell it rehearses reports.
        for group in ("end_to_end", "per_layer"):
            for metric in manifest[group]:
                if _LIKE.get(cell) in metric.get("workloads", ()):
                    metric["workloads"].append(cell)
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    return root


def run_cell(root: str, cell: str, *, chips: int = 1, seconds: float = 3,
             trace: int = 0, seed: int = 2 ** 31 + 7, devices: int = 1,
             timeout: float = 600):
    """``benchmark/run.py`` of the copy through rehearse_run.py: the CPU
    behind ``chips`` pretended chips (0: what the host really shows)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(HERE, "rehearse_run.py"), root,
           str(chips), "--workload", cell, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace)]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=timeout)


if __name__ == "__main__":
    dest, cell = sys.argv[1], sys.argv[2]
    if not os.path.isdir(os.path.join(dest, "benchmark")):
        build(dest)
    four = cell.endswith("2x2")
    out = run_cell(dest, cell, chips=4 if four else 1,
                   devices=4 if four else 1,
                   trace=int(sys.argv[3]) if len(sys.argv) > 3 else 0)
    sys.stderr.write(out.stderr[-6000:])
    sys.stdout.write(out.stdout)
    sys.exit(out.returncode)
