"""What a sequence keeps on the device between steps, and the one object
that owns it: ``SequenceCache``.

A sequence's HOLDING is of three kinds, and a model's ``models.CacheSpec``
says which of them its layers need:

- PAGES OF A GROUP of layers, vLLM-style: the group's history lives in
  preallocated device buffers carved into fixed-size pages ([layers,
  pages, page_size, row]), and a sequence maps position ``p`` to page
  ``table[p // page_size]``, slot ``p % page_size`` through its page
  table, so it grows a page at a time without copying and the jitted
  step compiles once for the static pool.  A row is a layer's K and V of
  one position with the heads FOLDED into the minor dimension
  (``k_pages`` / ``v_pages``, row ``h_kv * d``: with a 64-wide head last
  the TPU tiles the pool page-minor and every scatter pays a transpose),
  or, for latent attention, the ONE row every head shares
  (``latent_pages``: ``c_kv`` then ``k_pe`` then zeros up to whole tiles
  of 128 lanes, ``CacheSpec.row_width``; the store, the kernel and
  ``kv_row_bytes`` share that padding).
- A RING: the layers of the window group see the last ``window``
  positions, kept at ring row ``p mod window`` in ``window_k_pages`` /
  ``window_v_pages`` through ``window_table``
  (``models/attention.py _ring``).  A sequence takes its whole ring with
  its first pages and gives it back with them, so that group's pool is
  ``max_batch`` rings and what it holds stops growing at the window.
- A SLOT: its row of the decode batch and of the device's token array,
  for every model, which it keeps while it runs; and, where the layers
  are recurrent, of the state pool: ``conv`` [state layers, slots, ...]
  in the model's dtype and ``ssm`` [state layers, slots, ...] float32,
  whichever the spec gives a shape (``state_arrays``), of fixed size
  whatever the sequence's length.  A slot is not cleared when it changes
  hands: a forward from position 0 starts from zeros.

The kinds are independent; a spec may ask for any mix (K/V; K/V with
``conv`` and ``ssm``, or ``conv`` alone; latent; latent with a state;
K/V in two groups).  ``pool_arrays``, ``pool_tables`` and
``state_arrays`` name a spec's arrays and tables in the one order
``llm/engine.py jit_forward`` takes and returns them: the paged arrays,
the tables, the positions, then the state arrays and the slots; every
array is donated, carried WHOLE from layer to layer with a layer index
(slicing a layer out and stacking again makes XLA build a second pool),
and updated where it lies.  ``PagePool`` (one a group) and ``SlotPool``
are the host-side allocators, with occupancy gauges.

The data path is called from the one attention core
(``models/attention.py``, cached branch), once a layer: ``paged_store``
/ ``latent_store`` scatter the new rows; a decode step on the ``tpu``
backend reads each sequence's pages where they lie
(``ops/paged_attention.py``); ``paged_attend`` / ``latent_attend`` here
are the kernels' plain definitions and every other backend's path.  A
prefill starts at position 0, attends among its own rows and reads
nothing of the pool.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


class Holding:
    """What one sequence holds of the device's caches: its pages in the
    group that keeps every position (they grow with it), its ring in the
    window group (whole or absent) and its slot (None: it is not
    running).  ``SequenceCache`` fills and empties it."""

    __slots__ = ("pages", "ring", "slot")

    def __init__(self):
        self.pages: List[int] = []
        self.ring: List[int] = []
        self.slot: Optional[int] = None


class SequenceCache:
    """Everything the sequences of one engine keep on the device: the
    arrays a cache spec asks for (``paged`` then ``state``, in the
    forward's order), a ``PagePool`` a group (``pools``: ``full``, and
    ``window`` where the spec has window layers: ``max_batch`` rings of
    ``ring_pages``), the ``SlotPool`` (``slots``: ``max_batch``), the page
    tables of a launch and the counters of what the decode steps read and
    hold.  Called from the engine thread; ``stats`` from any."""

    def __init__(self, spec, *, num_pages: int, page_size: int,
                 max_batch: int, max_context: Optional[int], max_seq: int,
                 dtype: Any, mixer_weight_bytes: int = 0):
        self._spec, self.page_size = spec, page_size
        self.max_context = min(max_context or max_seq, max_seq,
                               num_pages * page_size)
        self.pages_per_seq = pages_for(self.max_context, page_size)
        self.ring_pages = ring_pages(spec, page_size)
        self.pools = {"full": PagePool(num_pages, page_size)}
        if self.ring_pages:
            self.pools["window"] = PagePool(max_batch * self.ring_pages,
                                            page_size, group="window")
        self.slots = SlotPool(max_batch)
        self.paged = init_pool(spec, num_pages, page_size, dtype,
                               max_batch * self.ring_pages)
        self.state = init_state(spec, max_batch, dtype)
        # What the decode steps' attention reads of the pool
        # (stats()["attention"]): ``kv_rows_read`` is what the
        # paged-decode kernel's copies move (each running row's whole
        # pages up to its length, this step's token included, times the
        # layers; one row = what one position of one layer occupies,
        # ``kv_row_bytes``: its K and its V, or its one latent row with
        # the padding, whose widths are then beside it), ``kv_rows_held``
        # what a gather of every row's whole page table moves.  With
        # window layers both count BOTH groups, each by what its layers
        # read (``min(n_cached + 1, window)`` rows, in whole pages) and
        # hold (its ring); the window group's part is beside them, with
        # ``window_positions_dropped``: the rows a layer that kept every
        # position would have read and these did not.
        self._attention = {
            "decode_runs": 0, "kv_rows_read": 0, "kv_rows_held": 0,
            "kv_row_bytes": sum(a.shape[-1] * a.dtype.itemsize
                                for name, a in self.paged.items()
                                if name not in WINDOW_ARRAYS),
            **({"latent_dim": spec.latent_dim, "rope_dim": spec.rope_dim}
               if spec.latent_dim else {}),
            **({"window": spec.window, "window_layers": spec.window_layers,
                "window_rows_read": 0, "window_rows_held": 0,
                "window_positions_dropped": 0}
               if spec.window_layers else {})}
        # What their recurrent layers move (stats()["state"], absent
        # without such layers): ``state_rows_updated`` = running rows x
        # recurrent layers, one row = what one sequence keeps in one such
        # layer (``state_row_bytes``, read and written once a step);
        # ``mixer_weight_bytes`` = one layer's mixer weights, the engine's
        # word.
        self._state_counts = {
            "decode_runs": 0, "state_rows_updated": 0,
            "state_row_bytes": sum(int(a[0, 0].size) * a.dtype.itemsize
                                   for a in self.state.values()),
            "mixer_weight_bytes": mixer_weight_bytes} if self.state else {}

    # ------------------------------------------------ a sequence's holding
    def fits(self, n_tokens: int) -> bool:
        """Whether a prompt of ``n_tokens`` can ever be taken: False is
        "larger than the whole pool", not "not now"."""
        return pages_for(n_tokens, self.page_size) \
            <= self.pools["full"].num_pages

    def take(self, held: Holding, n_tokens: int) -> bool:
        """Everything a prompt of ``n_tokens`` needs, or nothing: its
        pages, then its whole ring, then a slot (as many slots, and
        rings, as rows); what was taken goes back where a later one is
        refused."""
        pool, window = self.pools["full"], self.pools.get("window")
        pages = pool.alloc(pages_for(n_tokens, self.page_size))
        if pages is None:
            return False
        ring = window.alloc(self.ring_pages) if window else []
        slot = None if ring is None else self.slots.take()
        if slot is None:
            pool.free(pages)
            if ring:
                window.free(ring)
            return False
        held.pages, held.ring, held.slot = pages, ring, slot
        return True

    def grow(self, held: Holding, position: int) -> bool:
        """Room for ``position``'s row (the ring has it already): a page
        more where it opens one; False where the pool is dry."""
        while len(held.pages) <= position // self.page_size:
            page = self.pools["full"].alloc(1)
            if page is None:
                return False
            held.pages.extend(page)
        return True

    def release(self, held: Holding) -> None:
        """Give everything back (a re-prefill rebuilds the state from
        position 0).  A second call finds nothing to give."""
        self.pools["full"].free(held.pages)
        held.pages = []
        if held.ring:
            self.pools["window"].free(held.ring)
            held.ring = []
        self.slots.give(held.slot)
        held.slot = None

    # ------------------------------------------------------- a launch
    def tables(self, rows: List[tuple], n_rows: int) -> tuple:
        """The page tables of a launch, one a group: ``rows`` are (owner,
        its row) pairs, an owner carrying its ``Holding`` as ``held``; the
        other rows zeros."""
        table = np.zeros((n_rows, self.pages_per_seq), np.int32)
        for owner, row in rows:
            table[row, :len(owner.held.pages)] = owner.held.pages
        if not self.ring_pages:
            return (table,)
        rings = np.zeros((n_rows, self.ring_pages), np.int32)
        for owner, row in rows:
            rings[row] = owner.held.ring
        return table, rings

    def args(self, tables: tuple, positions, slots) -> tuple:
        """The forward's arguments after the tokens (``jit_forward``):
        ``slots`` is each row's slot of the state pool (a row without a
        sequence: an index outside it), passed where there is one."""
        args = (*self.paged.values(), *tables, positions)
        return args + (*self.state.values(), slots) if self.state else args

    def take_back(self, outputs: list) -> list:
        """Keep the donated arrays a forward returned after its logits,
        and hand back what is left."""
        n, m = len(self.paged), len(self.paged) + len(self.state)
        self.paged = dict(zip(self.paged, outputs[:n]))
        self.state = dict(zip(self.state, outputs[n:m]))
        return outputs[m:]

    def count_decode(self, positions) -> None:
        """Count one decode step by its ``positions`` [max_batch, 1]: a
        running row's is what it has cached, an idle row's negative."""
        spec, page, counts = self._spec, self.page_size, self._attention
        window = spec.window if spec.window_layers else 0
        running = pages_read = ring_read = 0
        for cached in positions[:, 0].tolist():
            if cached >= 0:         # it reads its pages, this token's too
                running += 1
                pages_read += -(-(cached + 1) // page)
                if window:
                    ring_read += -(-min(cached + 1, window) // page)
        rows = page * spec.kv_layers
        counts["decode_runs"] += 1
        counts["kv_rows_read"] += pages_read * rows
        counts["kv_rows_held"] += len(positions) * self.pages_per_seq * rows
        if window:
            rows = page * spec.window_layers
            held = len(positions) * self.ring_pages * rows
            counts["kv_rows_read"] += ring_read * rows
            counts["kv_rows_held"] += held
            counts["window_rows_read"] += ring_read * rows
            counts["window_rows_held"] += held
            counts["window_positions_dropped"] += \
                (pages_read - ring_read) * rows
        if self.state:
            self._state_counts["decode_runs"] += 1
            self._state_counts["state_rows_updated"] += \
                running * spec.state_layers

    def stats(self) -> Dict[str, Any]:
        """Its part of the engine's stats(): pages used and in all (of
        the group that keeps every position, then by group where there
        are two), ``attention``, and ``state`` where there is a state
        pool."""
        used = {group: {"used": pool.used, "total": pool.num_pages}
                for group, pool in self.pools.items()}
        return {
            "kv_pages_used": used["full"]["used"],
            "kv_pages_total": used["full"]["total"],
            **({"kv_pages": used} if len(used) > 1 else {}),
            "attention": dict(self._attention),
            **({"state": {"slots_total": self.slots.slots,
                          "slots_used": self.slots.used,
                          **self._state_counts}} if self.state else {})}
