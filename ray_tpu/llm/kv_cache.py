"""Paged KV cache — fixed-size pages from one preallocated device pool.

vLLM-style memory management adapted to JAX/TPU: the K/V history of
every running sequence lives in ONE device buffer per model (K and V
each [n_layer, num_pages, page_size, n_kv_head * head_dim]), carved
into fixed-size pages.  The heads are FOLDED into the minor dimension:
with a 64-wide head last, the TPU tiles the pool page-minor and every
scatter and gather pays a transpose of the whole layer; a page that is
a row-major [page_size, h_kv*d] tile is updated where it lies.  A
sequence maps logical token positions to physical pages through its
page table (position p lives in page ``table[p // page_size]`` at slot
``p % page_size``), so sequences grow without reallocation or copying,
free pages are recycled at step granularity, and fragmentation is
bounded by one partial page per sequence.  Because the pool shape is static, the jitted decode step
compiles once — admission/retirement only edits page tables and host
accounting.

Three functions implement the data path, called from the one attention
core (``models/attention.py``, cached branch), once a layer each:
``paged_store`` scatters fresh K/V into pages; then, for a decode step
(one query row a sequence) on the ``tpu`` backend,
``ops/paged_attention.py paged_decode``, a Pallas kernel, reads each
sequence's pages where they lie, through the page table and up to the
sequence's length; for a prefill, and on every other backend,
``paged_attend`` (pure jnp, here) gathers the batch's pages and runs
masked attention: it is the kernel's plain definition and what the
kernel is tested against.  All three take the WHOLE pool and a layer
index, and the forward carries that one pool from layer to layer:
slicing a layer out and stacking the layers again makes XLA build a new
pool beside the donated one.

A model with recurrent layers (``models/granite.py``: Mamba-2 mixers)
keeps a SECOND kind of cache beside the pages: one slot a sequence in the
state pool, ``conv`` [state layers, slots, d_conv-1, conv_dim] (the conv
window, in the model's dtype) and ``ssm`` [state layers, slots, H, P, N]
(float32), of fixed size whatever the sequence's length
(``init_state``).  A model whose recurrent layers keep a window and NO
state (``models/lfm2.py``: short-conv mixers, ``ssm_shape == ()``) has a
state pool of the one array ``conv``: ``state_arrays`` names what a
spec's pool holds, and the engine carries, donates and aliases those and
nothing else.  The K/V pool is then built for the attention layers
only.  The model reads and writes a row's slot where it lies, through a
``[B]`` slot index (an index outside the pool: a padded row, nothing
changed), and carries both arrays whole through its layers as the K/V
pool is carried; ``SlotPool`` is the host-side allocator, a sequence
takes a slot with its first pages and gives it back with them.  The slot
is also the sequence's row of the decode batch, for every model: a
sequence keeps its row while it runs, so the ids one decode step leaves
on the device are the next step's tokens row for row (llm/engine.py).

A model with LATENT attention (``models/kimi.py``: MLA) keeps ONE pool
and no V pool beside it: ``latent_pages`` [layers, pages, page, row],
where a position's row of a layer is the compressed vector every head
shares, ``c_kv`` (``latent_dim`` numbers, after its norm), then the one
rotary key ``k_pe`` (``rope_dim`` numbers, after RoPE), then zeros up to
whole tiles of 128 lanes (512 + 64 -> 640: ``CacheSpec.row_width``; the
padding is the pool's, the store's and the kernel's one shared
decision, and ``kv_row_bytes`` counts it).  ``pool_arrays`` names what a
spec's paged pool holds and ``init_pool`` builds it; ``latent_store``
scatters the rows; a decode step attends IN the latent space
(``ops/paged_attention.py paged_decode_latent`` on the ``tpu`` backend:
the row is read once, for the scores and, its first ``latent_dim``
lanes, for the values; ``latent_attend`` here is its plain definition);
a prefill starts at position 0 and needs nothing from the pool
(``models/attention.py latent_attention``).

The two kinds of pool are independent, and a model may have BOTH
(``models/kimi_linear.py``: latent attention in 7 layers, delta-rule
mixers in 20): its spec has ``latent_dim`` > 0, so the paged pool is the
one array ``latent_pages`` over the ``kv_layers`` latent layers, AND
``state_layers`` > 0, so there is a state pool of ``conv`` (the three
convolutions' windows, [state layers, slots, 3, 3 H d_k] in the model's
dtype) and ``ssm`` (each head's ``d_k x d_v`` matrix, [state layers,
slots, H, d_k, d_v] float32: 2 MB a slot a layer at 32 heads of 128).  A
layer indexes its pool by its number among its own kind; the forward
takes ``latent_pages``, the page table and the positions, then ``conv``,
``ssm`` and the slots, and returns the three arrays in that order, all
donated and aliased (``llm/engine.py jit_forward``).

A model with SLIDING-WINDOW layers (``models/cohere.py``: three layers
in four see the last 4,096 positions) keeps its K/V in TWO GROUPS of
layers, each with arrays, a page count, a table and a host allocator of
its own: the ``kv_layers`` that attend every earlier position hold them
all, in ``k_pages`` / ``v_pages`` through ``page_table``, as above; the
``window_layers`` hold a RING of ``window`` positions a sequence, in
``window_k_pages`` / ``window_v_pages`` [window layers, max_batch x ring
pages, page, h_kv*d] through ``window_table`` [B, ring pages] (position
``p`` at ring row ``p mod window``: ``models/attention.py _ring``; the
store and the decode kernel are the ones above, handed ring positions and
``min(length, window)``).  A sequence takes its whole ring with its first
pages and gives it back with them, so the second group's size follows
from ``max_batch`` and the window alone, and what a window layer holds
stops growing at the window whatever the sequence's length.  A spec
without window layers has ONE group, exactly as above.

``PagePool`` is the host-side allocator, one a group; it exports
``rt_llm_kv_pages_{used,total}`` gauges (tag ``group``: ``full``,
``window``) on every alloc/free so KV occupancy is visible in ``rt
telemetry`` and the doctor can see leaks.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


def init_cache(n_layer: int, num_pages: int, page_size: int,
               n_kv_head: int, head_dim: int, dtype: Any) -> Dict[str, Any]:
    """Preallocate the pooled K/V buffers, each
    [n_layer, num_pages, page_size, n_kv_head * head_dim] (zeros; pages
    are recycled without clearing — the position mask in paged_attend
    and the lengths in paged_decode make stale contents unreachable)."""
    shape = (n_layer, num_pages, page_size, n_kv_head * head_dim)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


WINDOW_ARRAYS = ("window_k_pages", "window_v_pages")


def pool_arrays(spec) -> Tuple[str, ...]:
    """The paged pool's arrays for a cache spec, in the order the forward
    takes and returns them: K and V (then the window group's K and V,
    where the spec has window layers), or the one latent pool."""
    if spec.latent_dim:
        return ("latent_pages",)
    return ("k_pages", "v_pages") + (WINDOW_ARRAYS if spec.window_layers
                                     else ())


def pool_tables(spec) -> Tuple[str, ...]:
    """The page tables the forward takes after the pool's arrays: one a
    group."""
    return ("page_table",) + (("window_table",) if spec.window_layers
                              else ())


def ring_pages(spec, page_size: int) -> int:
    """Pages of one sequence's ring in the window group (0: no group)."""
    return pages_for(spec.window, page_size) if spec.window_layers else 0


def init_pool(spec, num_pages: int, page_size: int, dtype: Any,
              window_pages: int = 0) -> Dict[str, Any]:
    """The paged pool a cache spec asks for (``pool_arrays``), zeros:
    ``num_pages`` of the group that keeps every position, ``window_pages``
    of the window group where the spec has one."""
    if spec.latent_dim:
        return {"latent_pages": jnp.zeros(
            (spec.kv_layers, num_pages, page_size, spec.row_width), dtype)}
    pool = init_cache(spec.kv_layers, num_pages, page_size,
                      spec.kv_heads, spec.head_dim, dtype)
    if spec.window_layers:
        ring = init_cache(spec.window_layers, window_pages, page_size,
                          spec.kv_heads, spec.head_dim, dtype)
        pool.update(zip(WINDOW_ARRAYS, ring.values()))
    return pool


def state_arrays(spec) -> Tuple[str, ...]:
    """The state pool's arrays for a cache spec (``models.CacheSpec``),
    in the order the forward takes and returns them: ``conv`` where the
    recurrent layers keep a window, ``ssm`` where they keep a state."""
    if not spec.state_layers:
        return ()
    return tuple(name for name, shape in (("conv", spec.conv_shape),
                                          ("ssm", spec.ssm_shape))
                 if shape)


def init_state(spec, slots: int, dtype: Any) -> Dict[str, Any]:
    """The state pool of a model whose cache spec has recurrent layers
    (``state_arrays``; no array for an empty shape): zeros; a slot is
    not cleared when it changes hands — a forward that starts at
    position 0 starts from zeros whatever the slot holds
    (models/layers.py ``slot_conv``, models/granite.py)."""
    dtypes = {"conv": dtype, "ssm": jnp.float32}
    return {name: jnp.zeros((spec.state_layers, slots)
                            + tuple(getattr(spec, name + "_shape")),
                            dtypes[name])
            for name in state_arrays(spec)}


def _row_index(pages, page_table, positions):
    """Where each position's row lies in the pool ``pages``: (page index,
    slot in the page), [B, T] each; a padded position (< 0) gets the
    page index one past the pool, which a ``mode="drop"`` scatter drops."""
    num_pages, page_size = pages.shape[1], pages.shape[2]
    pos = jnp.maximum(positions, 0)
    page_ix = jnp.take_along_axis(page_table, pos // page_size, axis=1)
    # Out-of-range index => dropped write for padded slots.
    page_ix = jnp.where(positions >= 0, page_ix, num_pages)
    return page_ix, pos % page_size


def paged_store(k_pages, v_pages, layer, k_new, v_new, page_table,
                positions):
    """Scatter new K/V ([B, T, h_kv, d]) into layer ``layer`` of the
    WHOLE pool ([L, pages, page, h_kv*d]) as rows [B, T, h_kv*d], and
    return the pool.

    ``positions`` is [B, T] absolute token positions; negative entries
    are padding and are dropped (scatter mode="drop" via an
    out-of-range page index), so one call serves prefill (T = padded
    prompt length) and batched decode (T = 1, padded rows) alike.
    """
    b, t = positions.shape
    with jax.named_scope("kv.store"):
        page_ix, slot = _row_index(k_pages, page_table, positions)
        k_pages = k_pages.at[layer, page_ix, slot].set(
            k_new.reshape(b, t, -1).astype(k_pages.dtype), mode="drop")
        v_pages = v_pages.at[layer, page_ix, slot].set(
            v_new.reshape(b, t, -1).astype(v_pages.dtype), mode="drop")
    return k_pages, v_pages


def paged_attend(q, k_pages, v_pages, layer, page_table, positions,
                 scale=None):
    """Causal attention of q ([B, T, h, d]) against layer ``layer`` of
    the paged cache.

    Gathers each sequence's pages as folded rows ([B, P, page, h_kv*d])
    and only then views them as [B, P*page, h_kv, d]; masks by ABSOLUTE
    position: cache slot j is visible to a query at position p iff
    j <= p, which both enforces causality and hides unwritten/stale
    slots (every position <= p has been written by construction).  GQA
    caches store h_kv heads and repeat to h at attend time, exactly
    like the full forward.  ``scale`` multiplies the scores (None:
    ``d ** -0.5``)."""
    b, t, h, d = q.shape
    with jax.named_scope("kv.attend"):
        ks = k_pages[layer, page_table]   # [B, P, page, h_kv*d]
        vs = v_pages[layer, page_table]
        p, page = ks.shape[1], ks.shape[2]
        h_kv = ks.shape[3] // d
        ks = ks.reshape(b, p * page, h_kv, d)
        vs = vs.reshape(b, p * page, h_kv, d)
        if h_kv != h:                      # GQA: repeat KV groups
            rep = h // h_kv
            ks = jnp.repeat(ks, rep, axis=2)
            vs = jnp.repeat(vs, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ks,
                            preferred_element_type=jnp.float32)
        scores = scores * (d ** -0.5 if scale is None else scale)
        kv_pos = jnp.arange(p * page, dtype=jnp.int32)
        mask = kv_pos[None, None, None, :] <= positions[:, None, :, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vs)


def latent_store(pages, layer, c_kv, k_pe, page_table, positions):
    """Scatter the latent rows of ``positions`` ([B, T]; < 0: padding,
    dropped) into layer ``layer`` of the WHOLE latent pool ([L, pages,
    page, row]) and return it: a row is ``c_kv`` [B, T, latent] then
    ``k_pe`` [B, T, rope] then zeros up to the pool's row width."""
    b, t = positions.shape
    with jax.named_scope("kv.store"):
        page_ix, slot = _row_index(pages, page_table, positions)
        pad = pages.shape[3] - c_kv.shape[-1] - k_pe.shape[-1]
        rows = jnp.concatenate(
            [c_kv.astype(pages.dtype), k_pe.astype(pages.dtype),
             jnp.zeros((b, t, pad), pages.dtype)], axis=-1)
        return pages.at[layer, page_ix, slot].set(rows, mode="drop")


def latent_attend(q_lat, q_pe, pages, layer, page_table, positions,
                  scale: float):
    """Attention in the latent space against layer ``layer`` of the
    latent pool, the plain definition of ``ops/paged_attention.py
    paged_decode_latent``: every head's query (``q_lat`` [B, T, H,
    latent], its no-rope part absorbed through the key expansion, and
    ``q_pe`` [B, T, H, rope]) meets the SAME row a position; scores
    ``(q_lat . c_kv + q_pe . k_pe) * scale`` in float32, cache slot j
    visible to a query at position p iff j <= p; the values are the
    ``c_kv`` read for the scores.  Returns ``o_lat`` [B, T, H, latent]
    in q's dtype."""
    b, t, h, r = q_lat.shape
    dr = q_pe.shape[-1]
    with jax.named_scope("kv.attend"):
        rows = pages[layer, page_table]          # [B, P, page, row]
        p, page = rows.shape[1], rows.shape[2]
        rows = rows.reshape(b, p * page, -1)
        c_kv, k_pe = rows[..., :r], rows[..., r:r + dr]
        scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat, c_kv,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                               preferred_element_type=jnp.float32)) * scale
        kv_pos = jnp.arange(p * page, dtype=jnp.int32)
        mask = kv_pos[None, None, None, :] <= positions[:, None, :, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_lat.dtype)
        return jnp.einsum("bhqk,bkr->bqhr", probs, c_kv)


def pages_for(n_tokens: int, page_size: int) -> int:
    return max(1, -(-n_tokens // page_size))


class PagePool:
    """Host-side allocator for the device page buffer.

    All-or-nothing allocation (a sequence either gets every page it
    asked for or stays queued — partial grants would deadlock two
    growing sequences against each other), LIFO free list for locality,
    occupancy exported as ``rt_llm_kv_pages_used`` /
    ``rt_llm_kv_pages_total`` gauges on every transition, tagged with the
    ``group`` of layers whose pages these are.
    """

    def __init__(self, num_pages: int, page_size: int,
                 group: str = "full"):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("num_pages and page_size must be > 0")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._tags = {"group": group}
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._lock = threading.Lock()
        # Gauge handles cached once — alloc/free is the decode hot
        # path; re-constructing a Metric there would pay the global
        # registry lock per transition.
        self._gauges = None
        try:
            from ..util.metrics import Gauge

            self._gauges = (
                Gauge("rt_llm_kv_pages_used",
                      "KV-cache pages currently allocated to "
                      "sequences.", tag_keys=("group",)),
                Gauge("rt_llm_kv_pages_total",
                      "Total KV-cache pages in the device pool.",
                      tag_keys=("group",)))
        except Exception:
            pass
        self._publish(self.num_pages)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages or None (never a partial grant)."""
        if n <= 0:
            return []
        with self._lock:
            if len(self._free) < n:
                return None
            pages = [self._free.pop() for _ in range(n)]
            free_now = len(self._free)
        self._publish(free_now)
        return pages

    def free(self, pages: List[int]) -> None:
        if not pages:
            return
        with self._lock:
            self._free.extend(pages)
            free_now = len(self._free)
            if free_now > self.num_pages:
                raise AssertionError(
                    f"page pool over-freed: {free_now} free of "
                    f"{self.num_pages}")
        self._publish(free_now)

    @property
    def available(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used(self) -> int:
        return self.num_pages - self.available

    def _publish(self, free_now: int) -> None:
        if self._gauges is None:
            return
        try:
            self._gauges[0].set(float(self.num_pages - free_now),
                                tags=self._tags)
            self._gauges[1].set(float(self.num_pages), tags=self._tags)
        except Exception:
            pass


class SlotPool:
    """Host-side allocator of the slots: one a running sequence, its row
    of the decode batch and (where the model keeps one) of the state
    pool; lowest free first; occupancy exported as
    ``rt_llm_state_slots_used`` / ``rt_llm_state_slots_total`` beside the
    pages'.  Called from the engine thread only."""

    def __init__(self, slots: int):
        self.slots = int(slots)
        self._free: List[int] = list(range(self.slots - 1, -1, -1))
        self._gauges = None
        try:
            from ..util.metrics import Gauge

            self._gauges = (
                Gauge("rt_llm_state_slots_used",
                      "Slots (decode-batch rows; recurrent-state "
                      "slots) currently held by sequences."),
                Gauge("rt_llm_state_slots_total",
                      "Slots (decode-batch rows; recurrent-state "
                      "slots) of the engine."))
        except Exception:
            pass
        self._publish()

    def take(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._publish()
        return slot

    def give(self, slot: Optional[int]) -> None:
        if slot is None:
            return
        if slot in self._free:
            raise AssertionError(f"state slot {slot} given back twice")
        self._free.append(slot)
        self._free.sort(reverse=True)
        self._publish()

    @property
    def used(self) -> int:
        return self.slots - len(self._free)

    def _publish(self) -> None:
        if self._gauges is None:
            return
        try:
            self._gauges[0].set(float(self.used))
            self._gauges[1].set(float(self.slots))
        except Exception:
            pass
