"""Pure training-step construction: optimizer, TrainState, sharded jit.

TPU-first: one compiled XLA program per step — loss, grads (via
jax.value_and_grad through remat'd blocks), optax update, all under a
single jit with donated state so HBM holds one copy of params+moments.
Parallelism arrives via the shardings the state is placed under
(``train.distributed.fitted_state_specs`` from a family's partition
rules; DP grads become psums XLA inserts from the shardings — no
hand-written collectives here).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params, optimizer) -> "TrainState":
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=optimizer.init(params))


_UNDECAYED = ("expert_bias", "map_bias", "map_gate")


def _decayed(params):
    """Which leaves the decoupled weight decay shrinks: every one but a
    leaf the model holds out of the gradient (``ops/moe.py``
    ``expert_bias``) -- with no gradient Adam's update is zero, and decay
    alone would still pull such a leaf toward 0 on every step -- and the
    biases and gates of a residual kind's maps (``models/xing.py``
    ``map_bias``, ``map_gate``): no matrices, and 0 is no neutral value of
    theirs (a gate at 0 cuts a map off its input)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in _UNDECAYED,
        params)


def make_optimizer(learning_rate: float = 3e-4,
                   warmup_steps: int = 100,
                   total_steps: int = 10000,
                   weight_decay: float = 0.1,
                   grad_clip: float = 1.0,
                   b1: float = 0.9, b2: float = 0.95) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=learning_rate,
        warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=learning_rate * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mask=_decayed),
    )


def make_train_step(loss_fn: Callable, optimizer, has_aux: bool = False
                    ) -> Callable[[TrainState, Dict], Tuple[TrainState, Dict]]:
    """loss_fn(params, batch) -> scalar, or with ``has_aux`` (scalar,
    {name: scalar}), whose entries join the step's metrics (a model's
    own losses: OLMoE's router losses).  Returns step(state, batch)."""

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        # The scopes name the step's parts in a device trace and in an
        # HLO dump; they change no operation.
        with jax.named_scope("loss_and_grad"):
            loss, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
                state.params, batch)
        loss, aux = loss if has_aux else (loss, {})
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        with jax.named_scope("grad_norm"):
            grad_norm = optax.global_norm(grads)
        metrics = {**aux, "loss": loss, "grad_norm": grad_norm}
        return TrainState(step=state.step + 1, params=params,
                          opt_state=opt_state), metrics

    return step


def make_sharded_train_step(loss_fn, optimizer, mesh=None,
                            donate: bool = True, telemetry: bool = True,
                            state_shardings=None,
                            batch_sharding=None, has_aux: bool = False):
    """Jit the step; with a mesh, shardings propagate from the state
    placement (GSPMD), so no explicit in_shardings are needed.

    The multi-process path (train.distributed) passes the rule-derived
    ``state_shardings`` (and optionally a ``batch_sharding``)
    explicitly: jit then PINS the input/output state layout instead of
    inferring it, so the donated input buffer and the returned state
    provably share a layout (no resharding copy per step) and the step
    metrics come back fully replicated — the form every rank can read
    with ``float()`` and feed the goodput/MFU telemetry below.

    With ``telemetry`` (default), each call is timed host-side and
    attributed to the goodput ledger: the first invocation (trace +
    XLA compile) lands in the ``compile`` phase and sets the
    ``rt_train_compile_seconds`` gauge; later invocations land in
    ``compute``, and the call into the executable is the leaf
    ``train.step.dispatch`` of the loop's phase accumulator
    (``session.step_account``; ``train.stats()``), tagged with the
    period's number: the one ``train.report`` carries in a session, the
    call's own count outside one, where each call closes a period.
    """
    step = make_train_step(loss_fn, optimizer, has_aux)
    jit_kwargs: Dict[str, Any] = {}
    if state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        if batch_sharding is not None:
            jit_kwargs["in_shardings"] = (state_shardings,
                                          batch_sharding)
        out_mesh = mesh
        if out_mesh is None:
            leaves = jax.tree_util.tree_leaves(state_shardings)
            out_mesh = leaves[0].mesh if leaves else None
        if out_mesh is not None:
            # One replicated sharding is a tree prefix for the whole
            # metrics dict.
            jit_kwargs["out_shardings"] = (
                state_shardings,
                NamedSharding(out_mesh, PartitionSpec()))
    jitted = jax.jit(step, donate_argnums=(0,) if donate else (),
                     **jit_kwargs)
    if not telemetry:
        return jitted

    import time as _time

    from ..util import goodput, xprof
    from ..util.spans import annotate
    from .session import step_account

    mesh_axes = None
    if mesh is not None:
        try:
            from .distributed import mesh_axis_sizes

            mesh_axes = mesh_axis_sizes(mesh)
        except Exception:
            pass

    # The one execution path: the first call compiles ahead of time
    # (``lower().compile()``, so the xprof plane harvests cost/memory/
    # collective facts from the executable that runs, with no second
    # compile) and every call runs that executable.  A compile error,
    # a run error, or inputs the executable was not compiled for
    # surface as themselves: the state is donated, so there is nothing
    # to retry with.
    aot = [None]
    calls = [0]

    def timed_step(state, batch):
        first = aot[0] is None
        account, period = step_account()
        with goodput.ledger().phase("compile" if first else "compute"):
            if first:
                account.void()      # a period that compiles is no step
                t0 = _time.perf_counter()
                with annotate("train.step.compile"), \
                        xprof.traced_notes() as notes:
                    aot[0] = jitted.lower(state, batch).compile()
                timed_step.notes = notes
                timed_step.compile_seconds = _time.perf_counter() - t0
            with account.leaf("train.step.dispatch",
                              step=calls[0] if period is None else period):
                out = aot[0](state, batch)
        calls[0] += 1
        if period is None:
            account.close()
        if first:
            try:
                from ..util.metrics import Gauge

                dt = _time.perf_counter() - t0
                Gauge("rt_train_compile_seconds",
                      "Host-side duration of the first (tracing + "
                      "XLA compile) step invocation.").set(dt)
                info = xprof.register_compiled("train_step", aot[0],
                                               mesh_axes=mesh_axes,
                                               compile_seconds=dt,
                                               notes=timed_step.notes)
                timed_step.collective_counts = \
                    (info or {}).get("collective_counts")
            except Exception:
                pass    # registering with xprof is best-effort
        return out

    # The executable every call runs (None before the first call), what
    # its compile took, the collectives it holds by kind ({"all-reduce":
    # n, ..., "collective-permute": n}) and what the traced code said of
    # it ({"loss": {path, token_shards, chunk}} from GPT-2's loss), all as
    # xprof's "train_step" row has them: callers check the program that
    # ran.
    timed_step.compiled = lambda: aot[0]
    timed_step.compile_seconds = None
    timed_step.collective_counts = None
    timed_step.notes = None
    return timed_step
