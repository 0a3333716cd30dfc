"""benchmark/reference/gpt2_ref.py against models/gpt2.py at a tiny size on
the CPU: forward logits, loss, gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.families import family_of
from benchmark.reference import gpt2_ref
from ray_tpu.models.gpt2 import GPT2

CONFIG = {"family": "gpt2", "vocab_size": 512, "n_positions": 64,
          "n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
          "layer_norm_epsilon": 1e-05, "compute_dtype": "float32"}


@pytest.fixture(scope="module")
def setup():
    fam = family_of(CONFIG)
    cfg = fam.program_config(CONFIG, attn_impl="dense", remat=False)
    params = fam.init(cfg, jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, (2, 33)), jnp.int32)
    return fam, cfg, params, tokens


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def test_same_equations_same_numbers(setup):
    """With the program's LayerNorm epsilon (flax's default 1e-6) the two
    are the same equations in float32: they agree to float32 rounding
    (1e-5 absolute on logits of size ~1; nothing looser is needed, and a
    bf16 matmul anywhere would miss it by three orders of magnitude)."""
    fam, cfg, params, tokens = setup
    config = dict(CONFIG, layer_norm_epsilon=1e-06)
    ours = GPT2(cfg).apply(params, tokens[:, :-1])
    ref = gpt2_ref.forward(config, params, tokens[:, :-1])
    assert float(jnp.max(jnp.abs(ours - ref))) < 1e-5
    loss = fam.loss(cfg, params, {"tokens": tokens}, loss_chunk=0)
    ref_loss, ref_grads = gpt2_ref.loss_and_grads(config, params, tokens)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    grads = jax.grad(lambda p: fam.loss(cfg, p, {"tokens": tokens},
                                        loss_chunk=0))(params)
    assert _max_diff(grads, ref_grads) < 1e-5


def test_published_epsilon_is_a_noted_departure(setup):
    """GPT-2's published layer_norm_epsilon is 1e-5 and the reference uses
    it; the program uses 1e-6.  With random initial weights the residual
    stream's variance is ~5e-4, so the departure shows: ~1e-2 on logits,
    ~3e-4 on the loss.  Both lie well inside the run-time tolerances (0.25
    on a logit gap, 0.02 on the loss), which is why the benchmark can hold
    the program to the published model."""
    fam, cfg, params, tokens = setup
    ours = GPT2(cfg).apply(params, tokens[:, :-1])
    ref = gpt2_ref.forward(CONFIG, params, tokens[:, :-1])
    diff = float(jnp.max(jnp.abs(ours - ref)))
    assert 1e-4 < diff < 3e-2
    loss = fam.loss(cfg, params, {"tokens": tokens}, loss_chunk=0)
    assert abs(float(loss) - float(gpt2_ref.loss(CONFIG, params, tokens))) \
        < 2e-3


def test_chunked_loss_and_bf16_stay_inside_the_run_time_tolerance(setup):
    """What a training cell compares: the program in bf16 with its chunked
    loss against the float32 reference, tolerance 0.02 on the loss."""
    fam, _, params, tokens = setup
    cfg = fam.program_config(dict(CONFIG, compute_dtype="bfloat16"),
                             attn_impl="dense", remat=True)
    loss = fam.loss(cfg, params, {"tokens": tokens}, loss_chunk=16)
    assert abs(float(loss) - float(gpt2_ref.loss(CONFIG, params, tokens))) \
        < 0.02
