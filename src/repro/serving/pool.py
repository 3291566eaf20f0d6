"""Paged device-resident prefill-state pool (vLLM-style paging for ITFI).

The host LRU (`scheduler.PrefillStateCache`) keeps per-user prefill
states as numpy rows and re-assembles every pane with host concats — one
host->device transfer per pane, per admission AND per hit. This module
is the device-resident successor: one **preallocated pool** of
``n_slots`` prefill-state slots lives on the devices for the engine's
lifetime, and pane assembly/writeback are slot-indexed **one-hot
contractions** inside jit:

    gather:  pane_row[b]  = Σ_n onehot[b, n] · pool[n]     (assembly)
    scatter: pool'[n]     = (1 - covered[n]) · pool[n]
                            + Σ_b onehot[b, n] · pane_row[b]  (writeback)

On a mesh: one-hot einsums, never batch-dependent ``take``/scatter ops
— the same zero-collective discipline the engine's inject/decode paths
use: a dynamic gather on a partitioned operand makes GSPMD all-gather
the whole pool, while the einsum partitions by output rows. The pool's
slot axis is REPLICATED over the data axes (`rules.slot_pool_pspecs`),
the gathered pane comes out data-sharded, and the compiled programs
carry **zero collectives** — asserted from HLO by
``tools/slot_pool_check.py``. On a single device there is nothing to
partition, so the gather drops to a direct ``take`` (an O(pane)
indexed copy instead of the einsum's O(n_slots x pane) contraction —
bitwise identical, both are exact copies); the scatter keeps the
one-hot form everywhere (fixed shapes for any writeback width, and it
only runs on admissions).

Bitwise exactness: multiplying by 0/1 and adding 0 is exact in every
float dtype at full precision (the contractions ask for
``Precision.HIGHEST``: the TPU's default float32 matmul is one bfloat16
pass), and integer/bool leaves contract in int32 — a gathered row
is bit-identical to the slot contents, and a scattered slot is
bit-identical to the pane row. The pooled serving path therefore serves
slates bitwise equal to the host-LRU path (property-tested in
tests/test_state_pool.py).

Only **prefill** states are pooled (sequence length fixed at
``prefill_len``): post-inject states grow the sequence axis and are
never written back, which is exactly the cache-key invariant — an entry
keyed ``(user, generation)`` is a pure function of the user's
snapshot-row history and the params; fresh suffixes never enter a slot.

:class:`PagedStateCache` is the slot table on top: an LRU mapping
``(user, generation) -> slot`` with a free-slot allocator,
slot-pressure eviction (a full pool IS the byte budget: fixed slots =
fixed bytes), and the same counter/rekey surface as the host
``PrefillStateCache`` — so the PR 5 warm handoff composes unchanged:
``rekey_generation`` renames slot-table keys and **never touches the
device arrays**.
"""
from __future__ import annotations

from collections import OrderedDict, deque
from typing import Any, Dict, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.serving.engine import ServingEngine
from repro.serving.tracing import span


# ----------------------------------------------------------------------
# One-hot gather/scatter jit bodies
# ----------------------------------------------------------------------

# The copies below are contractions with 0/1 weights, exact only at full
# float32 precision: at DEFAULT precision the TPU runs a float32 matmul as
# one bfloat16 pass, which rounds every copied value to 8 mantissa bits.
_EXACT = jax.lax.Precision.HIGHEST


def _sel(leaf, onehot, slot_axis: int):
    """Gather pane rows from a pool leaf: one-hot einsum over the slot
    axis (axis 1 for cache leaves, axis 0 for the flat planes). Bool and
    integer leaves contract in int32 so every dtype round-trips exactly."""
    dt = leaf.dtype
    cast = dt == jnp.bool_
    work = leaf.astype(jnp.int32) if cast else leaf
    w = onehot.astype(work.dtype)
    if slot_axis == 1:
        out = jnp.einsum("bn,rn...->rb...", w, work, precision=_EXACT)
    else:
        out = jnp.einsum("bn,n...->b...", w, work, precision=_EXACT)
    return out.astype(dt) if cast else out


def _upd(pool_leaf, rows_leaf, onehot, covered, slot_axis: int):
    """Scatter pane rows into pool slots: slots covered by the one-hot
    are overwritten, the rest pass through untouched (0/1 arithmetic —
    exact in every dtype, int32 for bool/int leaves)."""
    dt = pool_leaf.dtype
    cast = dt == jnp.bool_
    pl = pool_leaf.astype(jnp.int32) if cast else pool_leaf
    rl = rows_leaf.astype(jnp.int32) if cast else rows_leaf
    w = onehot.astype(pl.dtype)
    keep = (1 - covered).astype(pl.dtype)
    if slot_axis == 1:
        contrib = jnp.einsum("bn,rb...->rn...", w, rl, precision=_EXACT)
        keep = keep.reshape((1, -1) + (1,) * (pl.ndim - 2))
    else:
        contrib = jnp.einsum("bn,b...->n...", w, rl, precision=_EXACT)
        keep = keep.reshape((-1,) + (1,) * (pl.ndim - 1))
    out = pl * keep + contrib
    return out.astype(dt) if cast else out


def _gather_impl(caches, valid, next_pos, last, onehot):
    return ({"caches": jax.tree.map(lambda x: _sel(x, onehot, 1), caches),
             "valid": _sel(valid, onehot, 0),
             "next_pos": _sel(next_pos, onehot, 0),
             "logits": None},
            _sel(last, onehot, 0))


def _gather_take_impl(caches, valid, next_pos, last, idx):
    """Single-device gather: a direct indexed copy. The one-hot einsum
    exists to keep GSPMD from all-gathering a partitioned pool — on one
    device there is nothing to partition, and the einsum's
    O(n_slots x pane) contraction is pure waste next to this O(pane)
    take. Bitwise identical (both are exact copies of slot contents)."""
    return ({"caches": jax.tree.map(lambda x: jnp.take(x, idx, axis=1),
                                    caches),
             "valid": jnp.take(valid, idx, axis=0),
             "next_pos": jnp.take(next_pos, idx, axis=0),
             "logits": None},
            jnp.take(last, idx, axis=0))


def _scatter_impl(caches, valid, next_pos, last,
                  st_caches, st_valid, st_next_pos, st_logits, onehot):
    covered = onehot.sum(axis=0)  # (n_slots,) 0/1: slots written this call
    return (jax.tree.map(lambda p_, r_: _upd(p_, r_, onehot, covered, 1),
                         caches, st_caches),
            _upd(valid, st_valid, onehot, covered, 0),
            _upd(next_pos, st_next_pos, onehot, covered, 0),
            # the slot keeps the prefill's LAST-position logits — the
            # next-item scores when a request carries no fresh suffix —
            # sliced here so callers never sync the full (B,S,Vp) plane
            _upd(last, st_logits[:, -1, :], onehot, covered, 0))


def _bytes_by_kind(cfg, caches, valid, next_pos, last, n_slots: int,
                   ) -> Dict[str, int]:
    """One slot's bytes by kind of state: ``ssm`` (conv tails and
    recurrent state of the SSM layers), ``kv`` (keys and values of the
    attention layers), ``logits`` (the pre-inject next-item scores) and
    ``mask`` (the prefill pad mask and next position)."""
    from repro.models.model import pattern_sig
    out = {"ssm": 0, "kv": 0, "logits": last.nbytes // n_slots,
           "mask": (valid.nbytes + next_pos.nbytes) // n_slots}
    for p, (kind, _) in enumerate(pattern_sig(cfg)):
        out["kv" if kind == "attn" else "ssm"] += sum(
            x.nbytes for x in jax.tree.leaves(caches[f"pos{p}"])) // n_slots
    return out


# ----------------------------------------------------------------------
# The pool
# ----------------------------------------------------------------------

class DeviceStatePool:
    """Preallocated device buffers for ``n_slots`` prefill-state rows.

    Shapes/dtypes come from ``engine.prefill_state_shapes()`` (an
    ``eval_shape`` of the real prefill body, so the pool can never drift
    from what prefill produces). On a mesh the pool is allocated in its
    ``slot_pool_pspecs`` layout — slot axis replicated over data,
    model dims TP-sharded — and the gather's ``out_shardings`` are the
    engine's pane layouts, so gathered state feeds ``inject``/
    ``finalize`` with no resharding. The pool is **donated** through
    ``scatter``: writeback updates the buffers in place, one pool-sized
    working set, not two.

    ``scatter`` inputs are re-placed to replicated-over-data at the call
    boundary (`device_put`): the writeback einsum contracts over the
    pane's batch axis, and a batch-sharded operand would force an
    all-reduce *inside* the compiled program. The explicit transfer
    keeps the compiled scatter collective-free — the same pattern as the
    engine's own call-boundary placement.
    """

    def __init__(self, engine: ServingEngine, n_slots: int):
        b = engine.scfg.max_batch
        if n_slots < b:
            raise ValueError(
                f"pool_slots={n_slots} must be >= max_batch={b}: a single "
                f"pane can pin one slot per row during assembly")
        self.engine = engine
        self.n_slots = int(n_slots)
        self.data_shards = engine.data_shards
        logits_s, caches_s = engine.prefill_state_shapes()
        p = engine.scfg.prefill_len
        vp = logits_s.shape[-1]

        mesh = engine.mesh
        if mesh is None:
            alloc = lambda shape, dtype, spec: jnp.zeros(shape, dtype)
            oh_ns = pane_out = None
        else:
            from repro.sharding.rules import slot_pool_pspecs
            sp = slot_pool_pspecs(engine.cfg, mesh)
            ns = lambda spec: jax.tree.map(
                lambda s: NamedSharding(mesh, s), spec,
                is_leaf=lambda x: isinstance(x, P))
            self._cache_ns, self._valid_ns = ns(sp.caches), ns(sp.valid)
            self._rows_ns, self._logits_ns = ns(sp.rows), ns(sp.logits)
            self._st_logits_ns = NamedSharding(mesh, P(None, None, None))
            alloc = lambda shape, dtype, spec: jax.device_put(
                jnp.zeros(shape, dtype), spec)
            oh_ns = NamedSharding(mesh, P(None, None))
            pane_out = ({"caches": engine._seq_ns, "valid": engine._tok_ns,
                         "next_pos": engine._row_ns, "logits": None},
                        engine._tok_ns)

        slotted = lambda s: (s.shape[0], self.n_slots) + s.shape[2:]
        self.caches = (jax.tree.map(
            lambda s: alloc(slotted(s), s.dtype, None), caches_s)
            if mesh is None else jax.tree.map(
                lambda s, nsh: alloc(slotted(s), s.dtype, nsh),
                caches_s, self._cache_ns))
        self.valid = alloc((self.n_slots, p), jnp.bool_,
                           None if mesh is None else self._valid_ns)
        self.next_pos = alloc((self.n_slots,), jnp.int32,
                              None if mesh is None else self._rows_ns)
        self.last_logits = alloc((self.n_slots, vp), logits_s.dtype,
                                 None if mesh is None else self._logits_ns)
        self.slot_bytes_by_kind = _bytes_by_kind(
            engine.cfg, self.caches, self.valid, self.next_pos,
            self.last_logits, self.n_slots)
        self.slot_nbytes = sum(self.slot_bytes_by_kind.values())

        if mesh is None:
            # no mesh -> no partitioning constraint: gather by direct
            # take (O(pane), not O(n_slots x pane)); scatter keeps the
            # one-hot update (fixed shapes regardless of how many rows
            # a pane writes back, and it only runs on admissions)
            self._gather = jax.jit(_gather_take_impl)
            self._scatter = jax.jit(_scatter_impl,
                                    donate_argnums=(0, 1, 2, 3))
        else:
            pool_in = (self._cache_ns, self._valid_ns, self._rows_ns,
                       self._logits_ns)
            self._gather = jax.jit(
                _gather_impl, in_shardings=pool_in + (oh_ns,),
                out_shardings=pane_out)
            self._scatter = jax.jit(
                _scatter_impl,
                in_shardings=pool_in + (self._cache_ns, self._valid_ns,
                                        self._rows_ns, self._st_logits_ns,
                                        oh_ns),
                out_shardings=pool_in, donate_argnums=(0, 1, 2, 3))
        self.gathers = 0
        self.scatters = 0

    # ------------------------------------------------------------------
    def _onehot(self, slots: Sequence[int]) -> np.ndarray:
        b = self.engine.scfg.max_batch
        if len(slots) > b:
            raise ValueError(
                f"{len(slots)} rows exceed max_batch={b}")
        oh = np.zeros((b, self.n_slots), np.float32)
        for row, s in enumerate(slots):
            oh[row, s] = 1.0
        return oh

    def gather(self, slots: Sequence[int]) -> Tuple[Dict[str, Any], Any]:
        """Assemble a pane from slot ids (row ``i`` reads ``slots[i]``;
        short panes pad by repeating ``slots[0]``). Returns
        ``(state, last)``: a sequence-form engine state (sharded to the
        pane layout on a mesh) plus the per-row pre-inject next-item
        logits."""
        b = self.engine.scfg.max_batch
        if not slots:
            raise ValueError("gather of an empty pane")
        if len(slots) > b:
            raise ValueError(f"{len(slots)} rows exceed max_batch={b}")
        slots = list(slots) + [slots[0]] * (b - len(slots))
        with span("repro.pool.gather"):
            if self.engine.mesh is None:
                state, last = self._gather(
                    self.caches, self.valid, self.next_pos,
                    self.last_logits, jnp.asarray(slots, jnp.int32))
            else:
                state, last = self._gather(self.caches, self.valid,
                                           self.next_pos, self.last_logits,
                                           self._onehot(slots))
        self.gathers += 1
        return state, last

    def scatter(self, state: Dict[str, Any], slots: Sequence[int]) -> None:
        """Write prefill-pane rows into slots (row ``i`` -> ``slots[i]``;
        trailing pad rows of the pane are simply not listed). In-place:
        the pool buffers are donated into the update."""
        with span("repro.pool.scatter"):
            oh = self._onehot(slots)
            caches, valid = state["caches"], state["valid"]
            next_pos, logits = state["next_pos"], state["logits"]
            if self.engine.mesh is not None:
                # replicate the pane over the data axes OUTSIDE the
                # compiled program (see class docstring)
                caches = jax.device_put(caches, self._cache_ns)
                valid = jax.device_put(valid, self._valid_ns)
                next_pos = jax.device_put(next_pos, self._rows_ns)
                logits = jax.device_put(logits, self._st_logits_ns)
            (self.caches, self.valid, self.next_pos,
             self.last_logits) = self._scatter(
                self.caches, self.valid, self.next_pos, self.last_logits,
                caches, valid, next_pos, logits, oh)
        self.scatters += 1


# ----------------------------------------------------------------------
# The slot table
# ----------------------------------------------------------------------

class PagedStateCache:
    """LRU slot table over a :class:`DeviceStatePool` — the pooled
    counterpart of ``scheduler.PrefillStateCache``.

    Same key discipline (``(user, generation)``), same counter surface
    (hits/misses/evictions/invalidations/rekeys), same warm-handoff
    entry points (``rekey_generation`` / ``invalidate_except``) — but
    the values are **slot indices**, not host arrays, so every table
    operation is O(metadata): rekeying a generation renames dict keys
    and never moves a byte of device state, and invalidation just
    returns slots to the free list (the buffers are overwritten on next
    admission, not zeroed).

    Eviction is **slot-pressure**: the pool is the byte budget (fixed
    slots × fixed slot size). When the free list is empty, allocation
    evicts the least-recently-used entry whose slot is not ``pinned`` —
    the pin set (slots referenced by the pane being assembled) makes
    mid-assembly eviction safe: a slot this pane reads or just wrote can
    never be reallocated out from under it. With ``n_slots >=
    max_batch`` (enforced by the pool) an allocation can always succeed.
    """

    def __init__(self, pool: DeviceStatePool):
        self.pool = pool
        self.budget = pool.n_slots      # warm() clamps to this, like the LRU
        self.byte_budget = pool.n_slots * pool.slot_nbytes
        self.shards = pool.data_shards
        self._entries: "OrderedDict[Tuple[int, int], int]" = OrderedDict()
        self._free: deque = deque(range(pool.n_slots))
        # host-side pending-inject sidecar for the O(delta) re-warm: the
        # deferred snapshot-delta tokens ride NEXT TO the slot table
        # (token lists are host metadata; the device slot itself is the
        # untouched old-generation prefill state). Keys mirror _entries
        # and are pruned wherever an entry dies, so a recycled slot can
        # never inherit a previous tenant's pending tokens.
        self._pending: Dict[Tuple[int, int], list] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.rekeys = 0
        # handoff window: retained old-generation keys of changed users
        # (retain_changed rekey) — first victims under slot pressure
        self._handoff_stale: set = set()
        self.stale_evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Tuple[int, int]) -> bool:
        return key in self._entries

    @property
    def bytes_per_shard(self) -> int:
        """Resident entry bytes. The slot axis is replicated over the
        data axes, so per-shard == per-slot total (unlike the host LRU,
        whose pane rows shard over ``data``) — the price of the
        zero-collective gather, paid in HBM."""
        return len(self._entries) * self.pool.slot_nbytes

    # ------------------------------------------------------------------
    def lookup(self, user: int, gen: int) -> Optional[int]:
        slot = self._entries.get((user, gen))
        if slot is None:
            self.misses += 1
            return None
        self._entries.move_to_end((user, gen))
        self.hits += 1
        return slot

    def _alloc(self, pinned: Set[int]) -> int:
        if self._free:
            return self._free.popleft()
        # rollover-aware victim order: a retained dual-generation entry
        # (changed user, old generation) evicts before any live entry —
        # LRU order among the stale, pin-aware like every eviction here.
        # _handoff_stale is empty outside the handoff window, so the
        # steady-state scan is the same single pass as before.
        victim = None
        if self._handoff_stale:
            victim = next((k for k, s in self._entries.items()
                           if k in self._handoff_stale and s not in pinned),
                          None)
        if victim is not None:
            self._handoff_stale.discard(victim)
            self.stale_evictions += 1
        else:
            victim = next((k for k, s in self._entries.items()
                           if s not in pinned), None)
        if victim is None:
            raise RuntimeError(
                f"no allocatable slot: all {self.pool.n_slots} slots are "
                f"pinned by the pane under assembly")
        slot = self._entries.pop(victim)
        self._pending.pop(victim, None)
        self.evictions += 1
        return slot

    def admit(self, user: int, gen: int, pinned: Set[int]) -> int:
        """Allocate a slot for ``(user, gen)`` (evicting an unpinned LRU
        entry under slot pressure) and insert it most-recently-used.
        The caller scatters the state into the returned slot."""
        old = self._entries.pop((user, gen), None)
        # a fresh admission overwrites the slot contents: any deferred
        # delta attached to the previous entry is superseded
        self._pending.pop((user, gen), None)
        slot = old if old is not None else self._alloc(pinned)
        self._entries[(user, gen)] = slot
        return slot

    def alloc_scratch(self, pinned: Set[int]) -> int:
        """A table-less slot for an ephemeral (uncacheable) pane row;
        must be returned via :meth:`free_scratch` when the pane retires."""
        return self._alloc(pinned)

    def free_scratch(self, slot: int) -> None:
        self._free.append(slot)

    # ------------------------------------------------------------------
    def invalidate_except(self, gen: int) -> int:
        """Purge every entry from a generation other than ``gen`` —
        table keys only; the slots go back on the free list untouched."""
        stale = [k for k in self._entries if k[1] != gen]
        for k in stale:
            self._free.append(self._entries.pop(k))
            self._pending.pop(k, None)
        self.invalidations += len(stale)
        self._handoff_stale = {k for k in self._handoff_stale
                               if k in self._entries}
        return len(stale)

    def rekey_generation(self, old_gen: int, new_gen: int, changed,
                         retain_changed: bool = False) -> Tuple[int, int]:
        """Warm handoff, slot-table edition: identical contract to
        ``PrefillStateCache.rekey_generation`` (same caller, same
        certification requirements, same ``retain_changed`` handoff-
        window semantics — see its docstring), but a rekey is a
        dict-key rename and an invalidation a free-list push. The
        device arrays are never read, moved, or zeroed; a retained
        entry keeps its slot out of the free list until evicted."""
        changed_set = {int(u) for u in np.asarray(changed).ravel()}
        live_new = {u for (u, g) in self._entries if g == new_gen}
        out: "OrderedDict[Tuple[int, int], int]" = OrderedDict()
        pend: Dict[Tuple[int, int], list] = {}
        stale: set = set()
        rekeyed = invalidated = 0
        for (u, g), slot in self._entries.items():
            p = self._pending.get((u, g))
            if g == new_gen:
                out[(u, g)] = slot
                if p is not None:
                    pend[(u, g)] = p
            elif g == old_gen and u not in live_new:
                if u not in changed_set:
                    out[(u, new_gen)] = slot
                    if p is not None:
                        pend[(u, new_gen)] = p
                    rekeyed += 1
                elif retain_changed:
                    out[(u, g)] = slot
                    if p is not None:
                        pend[(u, g)] = p
                    stale.add((u, g))
                else:
                    self._free.append(slot)
                    invalidated += 1
            else:
                self._free.append(slot)
                invalidated += 1
        self._entries = out
        self._pending = pend
        self._handoff_stale = stale
        self.rekeys += rekeyed
        self.invalidations += invalidated
        return rekeyed, invalidated

    def rekey_entry(self, user: int, old_gen, new_gen) -> bool:
        """Rename ONE entry ``(user, old_gen)`` -> ``(user, new_gen)``
        in place — the slot-table twin of
        ``PrefillStateCache.rekey_entry`` (same O(delta) re-warm caller,
        same certification contract). A dict-key rename: the device
        arrays never move. An existing ``new_gen`` entry for the user is
        replaced (its slot returns to the free list). Pending-inject
        tokens follow the renamed key. Returns False when no
        ``(user, old_gen)`` entry exists."""
        slot = self._entries.pop((user, old_gen), None)
        if slot is None:
            return False
        prev = self._entries.pop((user, new_gen), None)
        if prev is not None:
            self._free.append(prev)
            self._pending.pop((user, new_gen), None)
        self._entries[(user, new_gen)] = slot
        self._entries.move_to_end((user, new_gen))
        p = self._pending.pop((user, old_gen), None)
        if p is not None:
            self._pending[(user, new_gen)] = p
        self._handoff_stale.discard((user, old_gen))
        self.rekeys += 1
        return True

    def drop(self, user: int, gen) -> bool:
        """Invalidate one entry (serve-time fallback when a deferred
        delta no longer fits the inject budget). The slot returns to the
        free list untouched. Returns False when absent."""
        slot = self._entries.pop((user, gen), None)
        if slot is None:
            return False
        self._free.append(slot)
        self._pending.pop((user, gen), None)
        self._handoff_stale.discard((user, gen))
        self.invalidations += 1
        return True

    # ------------------------------------------------------------------
    # Backend-neutral delta-rewarm surface (mirrored by PrefillStateCache)
    # ------------------------------------------------------------------

    def has_entry(self, user: int, gen) -> bool:
        """Membership probe with NO side effects — no LRU bump, no
        hit/miss counters (``lookup`` counts; this peeks)."""
        return (user, gen) in self._entries

    def get_pending(self, user: int, gen) -> Optional[list]:
        """The entry's deferred-inject token list, or None."""
        return self._pending.get((user, gen))

    def set_pending(self, user: int, gen, tokens) -> None:
        """Attach (or, with an empty list, clear) the entry's deferred
        snapshot-delta tokens. Raises KeyError when the entry is absent
        — pending tokens without a state to defer onto are a bug."""
        if (user, gen) not in self._entries:
            raise KeyError(f"no entry ({user}, {gen}) to attach pending "
                           f"inject tokens to")
        if tokens:
            self._pending[(user, gen)] = list(tokens)
        else:
            self._pending.pop((user, gen), None)

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "invalidations": self.invalidations,
                "rekeys": self.rekeys,
                "handoff_stale": len(self._handoff_stale),
                "stale_evictions": self.stale_evictions,
                "bytes_per_shard": self.bytes_per_shard,
                "shards": self.shards,
                "slots": self.pool.n_slots,
                "free_slots": len(self._free),
                "slot_bytes": self.pool.slot_nbytes,
                "slot_bytes_by_kind": dict(self.pool.slot_bytes_by_kind)}
