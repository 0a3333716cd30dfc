"""The attention core the blocks call: causal attention over q, k, v
already projected, position-encoded and split into heads.

``attention``, under the scope ``attn.core`` (``latent_attention``, at
the end, is its sibling for a cache that holds no K and V):

- with a ``cache`` (the engine's prefill and decode): this step's K/V
  are written into the paged pool the layers carry (``llm/kv_cache.py
  paged_store``).  A DECODE step (one query row a sequence) then attends
  against the history: on the ``tpu`` backend through the Pallas kernel
  that reads each sequence's pages where they lie
  (``ops/paged_attention.py paged_decode``), on every other backend
  through ``paged_attend``, the gather that is the kernel's plain
  definition.  A PREFILL (more than one row: the engine's always starts
  at position 0) attends causally among its OWN rows and reads nothing
  from the pool: through the flash kernel from ``_FLASH_FROM`` rows on
  the ``tpu`` backend, the dense definition below and elsewhere
  (``_prefill_impl``), so no ``[T, max_context]`` score array is made
  and a program's size follows its bucket, not the engine's
  ``max_context``; grouped K and V go to the flash kernel as they are,
  which reads a query head's group where it lies (no copy repeated to
  the query heads).  A layer with a sliding ``window`` keeps a RING of
  ``window`` positions a sequence in place of them all (``_ring``).
  Runs unsharded — the serving engine hosts one replica per chip;
- without one (training, the full forward): grouped KV heads are
  repeated to the query heads, q/k/v are constrained as the activation
  table says, and ``cfg.attn_impl`` picks ``dense`` (XLA-fused,
  GSPMD-partitioned), ``flash`` (the Pallas kernel, per shard),
  ``ring`` (context parallel over the ``seq`` mesh axis, SURVEY.md
  §5.7) or ``ulysses`` (head/seq all-to-all).

A kernel GSPMD cannot partition runs under ``shard_map``; its spec comes
from the same table and the same fitting rule as the constraints
(``parallel/sharding.py logical_spec``), so a head count the ``tensor``
axis does not divide stays whole there as everywhere else.

``attention_qkv`` is training's entry for a block whose ONE projection
makes q, k and v (GPT-2's ``c_attn``).  The flash kernels read the three
where that projection left them, on one device and across a mesh alike:
there the projection is applied through a view of its weight that puts
each ``tensor`` shard's own heads' q, k and v columns side by side
(``models/gpt2.py FusedQKV``), so what crosses the axis for the split
into heads is that weight, once a layer and pass, and no activation.

``cfg`` is a GPT2Config, a LlamaConfig or a GraniteConfig: ``attn_impl``,
``mesh`` and ``dtype`` are read.  Position encoding is the block's
business: what comes in is attended as it is (Granite's layers pass q and
k with none).

``latent_attention`` (Kimi-K2's MLA, ``models/kimi.py``) has TWO paths
over the same weights, and which step takes which is decided by the
step's shape alone:

- a DECODE step (one query row a sequence, with a cache) is ABSORBED: it
  attends in the latent space.  ``q_nope`` goes through the key half of
  the expansion ``W_kvb`` into ``r_kv`` numbers a head (``mla.absorb``),
  all heads meet the one row a position the pool holds (``kv.attend``:
  ``ops/paged_attention.py paged_decode_latent`` on the ``tpu`` backend,
  ``llm/kv_cache.py latent_attend`` elsewhere), and the result comes
  back through the value half (``mla.absorb``).  No key or value of
  any head is ever made;
- everything else is EXPANDED: a PREFILL (with a cache, more than one
  row: the engine's prefill always starts at position 0, so the
  prompt's own rows are all it attends) stores its latent rows
  (``kv.store``), expands them through ``W_kvb`` to per-head keys of
  ``d_n + d_r`` and values of ``d_v`` (``mla.expand``) and attends
  causally among them, reading nothing from the pool; training and the
  full forward (no cache) do the same without the store.  On the
  ``tpu`` backend a prefill of ``_FLASH_FROM`` rows or more goes through
  the flash kernel, v padded to the keys' width (no ``[T, T]`` score
  array reaches HBM); shorter ones, and every other backend, take the
  dense definition.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from ..parallel.sharding import logical_spec, with_logical_constraint


def _sharded(fn, mesh, logical, shape):
    from jax import shard_map

    spec = logical_spec(mesh, logical, shape)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


_FLASH = dict(causal=True, block_q=1024, block_k=1024)   # _attention says why


def _attention(cfg, q, k, v, scale=None, impl=None, window=None):
    """q: [B, T, H, D]; k: [B, T, Hkv, D]; v: [B, T, Hkv, Dv] -> [B, T, H,
    Dv] (Dv <= D: the kernels take one width, so a narrower v is padded
    for them and the padding cut off the result; H a multiple of Hkv: the
    flash kernel on one device reads each group's K/V where they lie,
    every other path repeats them to the query heads).  ``impl``: in
    place of ``cfg.attn_impl``.  ``window``: key ``j`` meets query ``i``
    iff ``0 <= i - j < window`` (None: ``j <= i``)."""
    impl = impl or cfg.attn_impl
    dv = v.shape[-1]
    if impl != "dense" and dv != q.shape[-1]:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
        return _attention(cfg, q, k, v, scale, impl, window)[..., :dv]
    if impl == "dense":
        k, v = _to_query_heads(q, k, v)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores * (q.shape[-1] ** -0.5 if scale is None else scale)
        t = q.shape[1]
        mask = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) >= \
            jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        if window is not None:
            mask = mask & (
                jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (t, t), 1) < window)
        scores = jnp.where(mask[None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if impl == "flash":
        # Pallas blockwise kernel (ops/flash_attention.py, whose module
        # docstring says the layout: heads merged into the minor
        # dimension, read where they lie): no [T, T] score matrix in
        # HBM.  GRID blocks of the whole sequence
        # (clamped to 1024): measured on v5e at pretraining shapes (PR
        # 34; T = 1024, 384 heads of 64, the three kernels' ms a step
        # of 12 layers), grid blocks of 256 take 135.7 and of 512 72.1
        # where one of 1024, computed whole and masked, takes 67.1: a
        # head is only a few microseconds of work to a program, so
        # skipping blocks in the GRID loses more than it saves.  The
        # causal triangle is walked INSIDE the program instead: the
        # kernel cuts a diagonal block of 512 rows or more into strips
        # of 256 and leaves out what lies above the diagonal (45.7).
        # Longer sequences stream in 1024-blocks, blocks above the
        # diagonal skipped, each diagonal one walked the same.
        from ..ops import flash_attention

        flash = functools.partial(flash_attention, scale=scale, **_FLASH)
        if window is not None:
            flash = functools.partial(flash, window=window)
        if cfg.mesh is None or cfg.mesh.size == 1:
            return flash(q, k, v)
        # A Mosaic kernel is not partitioned automatically: across a
        # mesh it runs per shard, batch and heads split as the table
        # says (attention is independent over both; the sequence stays
        # whole — splitting it is ring attention's job).
        k, v = _to_query_heads(q, k, v)
        return _sharded(flash, cfg.mesh, ("batch", None, "heads", None),
                        q.shape)(q, k, v)
    from ..parallel.ring_attention import ring_attention
    from ..parallel.ulysses import ulysses_attention

    if window is not None:
        raise ValueError(f"attn_impl={impl!r} knows no window")
    k, v = _to_query_heads(q, k, v)
    if cfg.mesh is None:
        raise ValueError(f"attn_impl={impl!r} needs cfg.mesh")
    if scale is not None:
        raise ValueError(f"attn_impl={impl!r} keeps the scale "
                         "1/sqrt(d)")
    inner = (ring_attention if impl == "ring"
             else ulysses_attention)
    return _sharded(functools.partial(inner, causal=True), cfg.mesh,
                    ("batch", "seq", None, None), q.shape)(q, k, v)


def qkv_by_head(cfg) -> bool:
    """Whether a block whose ONE projection makes q, k and v makes them
    by shard of the heads for ``attention_qkv``: where the flash kernels
    run per shard of a mesh."""
    return cfg.attn_impl == "flash" and cfg.mesh is not None \
        and cfg.mesh.size > 1


def attention_qkv(cfg, qkv, heads: int):
    """Training's ``attention`` for a block whose ONE projection makes q,
    k and v (GPT-2's ``c_attn``): -> [B, T, H*D], what the output
    projection reads.  ``attn_impl="flash"`` reads q, k and v where they
    lie in ``qkv`` (``ops/flash_attention.py flash_attention_qkv``):

    - on one device ``qkv`` is [B, T, 3*H*D], the projection's output as
      it stands;
    - across a mesh (``qkv_by_head``) it is [shards, B, T, 3*Hs*D], Hs =
      H / shards heads a shard, which the projection made with the
      shards and the batch where the table puts the heads and the batch
      (``models/gpt2.py FusedQKV``: the weight went to the heads, no
      activation does).  Under ``shard_map`` a shard's block, [1, b, T,
      3*Hs*D], IS the ``qkv`` of its own heads: the same kernel call as
      on one device, and its [b, T, Hs*D] outputs side by side on
      ``tensor`` are the rows the output projection's kernel is split
      by.  A head count the axis does not divide is ONE shard
      (``logical_shards``) and stays whole on every device, like any
      other activation the axis does not divide.

    The other implementations get [B, T, 3*H*D] split into heads, as
    ``attention`` takes them."""
    assert (qkv.ndim == 4) == qkv_by_head(cfg), qkv.shape
    b, t = qkv.shape[-3:-1]
    if cfg.attn_impl == "flash":
        from ..ops import flash_attention_qkv

        flash = functools.partial(flash_attention_qkv, **_FLASH)
        with jax.named_scope("attn.core"):
            if qkv.ndim == 3:
                return flash(qkv, heads)
            from jax import shard_map

            return shard_map(
                lambda x: flash(x[0], heads // qkv.shape[0]),
                mesh=cfg.mesh, check_vma=False,
                in_specs=logical_spec(
                    cfg.mesh, ("heads", "batch", None, None), qkv.shape),
                out_specs=logical_spec(cfg.mesh, ("batch", None, "heads"),
                                       (b, t, heads)))(qkv)
    with jax.named_scope("attn.qkv"):
        q, k, v = (x.reshape(b, t, heads, -1)
                   for x in jnp.split(qkv, 3, axis=-1))
    return attention(cfg, q, k, v)[0].reshape(b, t, -1)


def _decode_kernel(q, k_pages) -> bool:
    """Whether the cached branch takes the paged-decode kernel: by q's
    shape, the pool's and the backend, nothing else."""
    from ..ops import paged_attention

    return jax.default_backend() == "tpu" \
        and paged_attention.supported(q, k_pages)


def _to_query_heads(q, k, v):
    """GQA: the K/V groups repeated to the query heads."""
    rep = q.shape[2] // k.shape[2]
    if rep != 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _ring(positions, window: int):
    """Where a window layer's rows go and what a decode step reads of
    them.  The layer's pages are a RING of ``window`` rows a sequence:
    position ``p`` lies at ring row ``p mod window``.  Of a step's rows
    only the last ``window`` real ones are stored (an earlier one would
    land on a later one's row: a prefill's start, which the band hides
    from every later query anyway), so after the store the ring holds
    exactly the positions ``(p - window, p]`` of the newest row ``p``,
    each once, and softmax does not ask in which order.  Returns (the
    store's positions [B, T], < 0: dropped; the newest row's count of
    live ring rows less one [B, 1]: what ``paged_attend`` masks by)."""
    newest = jnp.max(positions, axis=1, keepdims=True)
    kept = (positions >= 0) & (positions > newest - window)
    return (jnp.where(kept, positions % window, -1),
            jnp.minimum(positions, window - 1))


def attention(cfg, q, k, v, cache=None, scale=None, window=None,
              scope=None):
    """q: [B, T, H, D]; k, v: [B, T, Hkv, D] (H a multiple of Hkv).
    Returns (att [B, T, H, D], new_cache): ``new_cache`` is the updated
    (k_pages, v_pages) when ``cache`` ({"k_pages", "v_pages", "layer",
    "page_table", "positions"}) is given, else None.  ``scale``
    multiplies q k^T (None: ``D ** -0.5``; a model that states its own,
    models/granite.py, passes it), on every branch.

    ``window`` (None: every earlier key): key ``j`` meets query ``i`` iff
    ``0 <= i - j < window``.  With a cache the layer's pages are then a
    ring (``_ring``): ``page_table`` names ``window`` rows of pages a
    sequence however long it grows, a prefill attends among its own rows
    under the band and stores the last ``window`` of them, a decode step
    stores at ``p mod window`` and reads ``min(p + 1, window)`` rows.
    Without one it is the dense definition's mask (a kernel whose
    backward knows no window must not train a full triangle in silence:
    any other ``attn_impl`` raises).  ``scope``: a name the cached branch
    is filed under, inside ``attn.core`` (a model with layers of two
    kinds tells them apart in a capture: ``attn.window``, ``attn.full``)."""
    with jax.named_scope("attn.core"), \
            (jax.named_scope(scope) if scope and cache is not None
             else contextlib.nullcontext()):
        if cache is not None:
            # The pool stores the Hkv GROUPED heads; each query head
            # meets its group at attend time, so GQA shrinks the pooled
            # cache by H/Hkv.
            from ..llm.kv_cache import paged_attend, paged_store
            from ..ops import paged_attention

            stored = newest = cache["positions"]
            if window is not None:
                stored, newest = _ring(stored, window)
            k_pages, v_pages = paged_store(
                cache["k_pages"], cache["v_pages"], cache["layer"],
                k, v, cache["page_table"], stored)
            if q.shape[1] > 1:
                # A prefill: from position 0 (``llm/engine.py
                # _prefill_annotated`` refuses another), so the rows
                # attend among themselves and nothing is read from the
                # pool; a bucket's padding lies behind the real ones,
                # where the causal mask hides it from them
                # (``latent_attention`` likewise).
                att = _attention(cfg, q, k, v, scale,
                                 _prefill_impl(q.shape[1]), window)
            elif _decode_kernel(q, k_pages):
                # A padded row's position is -1: length 0, zeros out.
                att = paged_attention.paged_decode(
                    q, k_pages, v_pages, cache["layer"],
                    cache["page_table"], newest[:, 0] + 1, scale=scale)
            else:
                att = paged_attend(q, k_pages, v_pages, cache["layer"],
                                   cache["page_table"], newest,
                                   scale=scale)
            return att, (k_pages, v_pages)
        if window is not None and cfg.attn_impl != "dense":
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} would train a window of "
                f"{window} as a full triangle: its backward knows no "
                "window; train it through attn_impl='dense'")
        k, v = _to_query_heads(q, k, v)
        heads = ("batch", "seq", "heads", None)
        q = with_logical_constraint(q, heads, cfg.mesh)
        k = with_logical_constraint(k, heads, cfg.mesh)
        v = with_logical_constraint(v, heads, cfg.mesh)
        return _attention(cfg, q, k, v, scale, window=window), None


# A cached prefill of this many rows or more takes the flash kernel on
# the ``tpu`` backend (whole 128-row tiles and then some; the engine's
# buckets are powers of two).
_FLASH_FROM = 256


def _latent_kernel(q_lat, pages) -> bool:
    """Whether the absorbed branch takes the latent paged-decode kernel:
    by shapes and the backend, nothing else."""
    from ..ops import paged_attention

    return jax.default_backend() == "tpu" \
        and paged_attention.latent_supported(q_lat, pages)


def _prefill_impl(t: int) -> str:
    """What a cached prefill of ``t`` rows attends through."""
    return "flash" if jax.default_backend() == "tpu" \
        and t >= _FLASH_FROM else "dense"


def latent_attention(cfg, q_nope, q_pe, c_kv, k_pe, w_kvb, scale,
                     cache=None):
    """Multi-head latent attention over what the block projected:
    ``q_nope`` [B, T, H, d_n], ``q_pe`` [B, T, H, d_r] (after RoPE),
    ``c_kv`` [B, T, r_kv] (after its norm), ``k_pe`` [B, T, d_r] (after
    RoPE; ONE vector shared by the heads), and the expansion ``w_kvb``
    [r_kv, H, d_n + d_v] (a head's keys, then its values).  ``scale``
    multiplies the scores.  Returns (att [B, T, H, d_v], the latent pool
    updated or None).  ``cache``: {"latent_pages", "layer",
    "page_table", "positions"}.  The module docstring says which step
    takes which path; the two are the same mathematics
    (tests/test_kimi.py)."""
    d_n = q_nope.shape[-1]
    w_k, w_v = w_kvb[..., :d_n], w_kvb[..., d_n:]
    with jax.named_scope("attn.core"):
        pages = None
        if cache is not None:
            from ..llm.kv_cache import latent_attend, latent_store

            pages = latent_store(cache["latent_pages"], cache["layer"],
                                 c_kv, k_pe, cache["page_table"],
                                 cache["positions"])
            if q_nope.shape[1] == 1:
                with jax.named_scope("mla.absorb"):
                    q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w_k)
                if _latent_kernel(q_lat, pages):
                    from ..ops import paged_attention

                    # A padded row's position is -1: length 0, zeros.
                    o_lat = paged_attention.paged_decode_latent(
                        q_lat, q_pe, pages, cache["layer"],
                        cache["page_table"],
                        cache["positions"][:, 0] + 1, scale=scale)
                else:
                    o_lat = latent_attend(
                        q_lat, q_pe, pages, cache["layer"],
                        cache["page_table"], cache["positions"], scale)
                with jax.named_scope("mla.absorb"):
                    return jnp.einsum("bthr,rhv->bthv", o_lat, w_v), pages
        with jax.named_scope("mla.expand"):
            k_nope = jnp.einsum("btr,rhd->bthd", c_kv, w_k)
            v = jnp.einsum("btr,rhv->bthv", c_kv, w_v)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_pe[:, :, None, :], k_nope.shape[:3] + k_pe.shape[2:]
                ).astype(k_nope.dtype)], axis=-1)
            q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)],
                                axis=-1)
        if cache is not None:
            # From position 0: the rows attend among themselves, and a
            # bucket's padding lies behind the real ones, where the
            # causal mask hides it from them.
            return _attention(cfg, q, k, v, scale,
                              _prefill_impl(q.shape[1])), pages
        heads = ("batch", "seq", "heads", None)
        q = with_logical_constraint(q, heads, cfg.mesh)
        k = with_logical_constraint(k, heads, cfg.mesh)
        v = with_logical_constraint(v, heads, cfg.mesh)
        return _attention(cfg, q, k, v, scale), None
