"""Serve a tiny GPT-2 through the LLM inference plane and stream
tokens — over the deployment handle and over HTTP (chunked ndjson).

Run:  python examples/serve_llm.py   (on a TPU host the replica leases
one chip; elsewhere it computes on the CPU)

The deployment hosts one continuous-batching GenerationEngine per
replica (paged KV cache, step-granularity admission); requests carry
token-id prompts and sampling parameters, responses stream one frame
per token.  Autoscaling: pass serve.AutoscalingConfig to
``llm_deployment(autoscaling=...)`` and replica count follows queue
depth + streams in flight.  See README "LLM serving".
"""

import dataclasses
import json
import urllib.request

import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm import EngineConfig, llm_deployment
from ray_tpu.models.gpt2 import GPT2Config


def main() -> None:
    cfg = dataclasses.replace(GPT2Config.tiny(), remat=False,
                              dtype=jnp.float32)
    ray_tpu.init(mode="cluster", num_cpus=4)
    try:
        handle = serve.run(
            llm_deployment(
                name="llm", model="gpt2", model_cfg=cfg,
                engine_cfg=EngineConfig(page_size=16, num_pages=128,
                                        max_batch=8)),
            route_prefix="/llm")

        # --- stream over the handle (in-cluster clients)
        print("handle stream:")
        for frame in handle.stream({"prompt": [5, 9, 101],
                                    "max_tokens": 8,
                                    "temperature": 0.8, "top_k": 40,
                                    "seed": 7}):
            print("  ", frame)

        # --- stream over HTTP (chunked ndjson; curl-able)
        port = serve.start_http_proxy()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps({"prompt": [5, 9, 101],
                             "max_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        print(f"http stream (port {port}):")
        with urllib.request.urlopen(req, timeout=120) as resp:
            for line in resp:
                print("  ", line.decode().rstrip())

        print("engine stats:",
              ray_tpu.get(handle.method("stats").remote()))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
