"""Device-resident slab of per-user recurrent state — the MiRU "KV cache".

A served user's entire conversation state is one (n_h,) hidden vector, so
the serving cache is a single (n_slots, n_h) device array: slot i holds
user i's ``h`` and the engine's compiled step advances every row at once.
:class:`StateSlab` owns that array plus the slot bookkeeping:

  acquire(uid)   make ``uid`` resident and return its slot — reusing its
                 existing slot, taking a free one (zero state for a new
                 user, reloading spilled state bit-identically for a
                 returning one), or evicting the least-recently-used
                 unpinned resident when the slab is full.
  pin/unpin      streams currently scheduled into the batch are pinned:
                 the evictor never takes their slot mid-flight.
  release(uid)   drop the user's state entirely (session over).
  evict(uid)     spill the row to host memory and free the slot — the
                 engine never calls this directly; ``acquire`` does under
                 slot pressure (the LRU spill of ROADMAP item 2).

Spill/reload is bit-exact: a float32 row round-trips device → host numpy
→ device unchanged, so an evicted-and-reloaded user continues their
stream bitwise as if they had stayed resident (asserted in
tests/test_serve_slab.py, gated in benchmarks/serve_bench.py).

Invariants (checked by :meth:`check`, driven by the property suite):

  * every slot is either on the free list or mapped to exactly one uid
    (free-list conservation, no double occupancy);
  * the LRU book tracks exactly the resident uids;
  * no uid is both resident and spilled.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Hashable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracer import annotate

__all__ = ["StateSlab", "SlabFullError"]


# Row reads/writes go through jitted helpers: an eager scatter/gather on
# the slab dispatches an untraced primitive per call (~ms on CPU), which
# under admission churn — 64 evict+reload pairs in one engine step —
# costs more than the compiled step itself. The slot index is a traced
# scalar, so each helper compiles once per slab shape.
@jax.jit
def _row_set(h: jax.Array, slot, row: jax.Array) -> jax.Array:
    return h.at[slot].set(row)


@jax.jit
def _row_get(h: jax.Array, slot) -> jax.Array:
    return h[slot]


class SlabFullError(RuntimeError):
    """Every slot is occupied by a pinned (mid-batch) stream."""


class StateSlab:
    def __init__(self, n_slots: int, n_h: int, dtype: Any = jnp.float32):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.n_slots = int(n_slots)
        self.n_h = int(n_h)
        self.dtype = dtype
        #: The device-resident state array. The engine reads it as the
        #: compiled step's h0 and assigns the step's masked-writeback
        #: output straight back (the buffer is donated to the jit step).
        self.h = jnp.zeros((self.n_slots, self.n_h), dtype)
        self._zero_row = jnp.zeros((self.n_h,), dtype)
        self._slot_of: dict[Hashable, int] = {}
        self._uid_of: list[Optional[Hashable]] = [None] * self.n_slots
        # Free slots as a stack, lowest index on top — allocation order
        # is deterministic, which the batch-composition invariance tests
        # rely on to *construct* adversarial slot permutations.
        self._free: list[int] = list(range(self.n_slots))[::-1]
        self._lru: OrderedDict[Hashable, None] = OrderedDict()
        self._pinned: set[Hashable] = set()
        self._spill: dict[Hashable, np.ndarray] = {}
        self.evictions = 0
        self.reloads = 0

    # ------------------------------------------------------------------
    # Queries
    # ------------------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def resident(self) -> tuple[Hashable, ...]:
        """Resident uids in LRU → MRU order."""
        return tuple(self._lru)

    @property
    def spilled(self) -> tuple[Hashable, ...]:
        return tuple(self._spill)

    def slot(self, uid: Hashable) -> Optional[int]:
        return self._slot_of.get(uid)

    def is_resident(self, uid: Hashable) -> bool:
        return uid in self._slot_of

    def can_acquire(self, uid: Hashable) -> bool:
        """Would :meth:`acquire` succeed without raising SlabFullError?"""
        return (uid in self._slot_of or self._free
                or any(u not in self._pinned for u in self._lru))

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def acquire(self, uid: Hashable) -> int:
        """Make ``uid`` resident and MRU; return its slot."""
        slot = self._slot_of.get(uid)
        if slot is not None:
            self.touch(uid)
            return slot
        if not self._free:
            self._evict_lru()
        slot = self._free.pop()
        self._slot_of[uid] = slot
        self._uid_of[slot] = uid
        self._lru[uid] = None
        if uid in self._spill:
            # Returning user: reload the spilled row bit-identically.
            with annotate("serve.slab.reload"):
                self.h = _row_set(self.h, slot, jnp.asarray(
                    self._spill.pop(uid), self.dtype))
            self.reloads += 1
        else:
            # New user: fresh zero state (the slot may hold a departed
            # user's stale h).
            self.h = _row_set(self.h, slot, self._zero_row)
        return slot

    def touch(self, uid: Hashable) -> None:
        """Mark ``uid`` most-recently-used."""
        self._lru.move_to_end(uid)

    def pin(self, uid: Hashable) -> None:
        """Exclude a resident uid from eviction (it is in the batch)."""
        if uid not in self._slot_of:
            raise KeyError(f"cannot pin non-resident uid {uid!r}")
        self._pinned.add(uid)

    def unpin(self, uid: Hashable) -> None:
        self._pinned.discard(uid)

    def release(self, uid: Hashable) -> None:
        """Forget ``uid`` entirely — resident or spilled. No-op if
        unknown (a rejected request never acquired a slot)."""
        slot = self._slot_of.pop(uid, None)
        if slot is not None:
            self._uid_of[slot] = None
            self._free.append(slot)
            del self._lru[uid]
        self._pinned.discard(uid)
        self._spill.pop(uid, None)

    def evict(self, uid: Hashable) -> None:
        """Spill ``uid``'s row to host memory and free its slot."""
        if uid in self._pinned:
            raise ValueError(f"cannot evict pinned uid {uid!r}")
        slot = self._slot_of.pop(uid)
        with annotate("serve.slab.evict"):
            # Waits for the step in flight, which writes self.h.
            self._spill[uid] = np.asarray(_row_get(self.h, slot))
        self._uid_of[slot] = None
        self._free.append(slot)
        del self._lru[uid]
        self.evictions += 1

    def _evict_lru(self) -> None:
        for uid in self._lru:                 # LRU → MRU order
            if uid not in self._pinned:
                self.evict(uid)
                return
        raise SlabFullError(
            f"all {self.n_slots} slots are pinned mid-batch; "
            "hold the request in the queue until a stream completes")

    def preload(self, uid: Hashable, row: np.ndarray) -> None:
        """Seed ``uid``'s state as a host-spilled row. The chip-failure
        migration path: a failed chip's rows enter the replacement slab
        through the same spill dict the LRU evictor uses, so the next
        ``acquire`` reloads them with the bit-exact round-trip the spill
        path already guarantees."""
        if uid in self._slot_of:
            raise ValueError(f"uid {uid!r} is already resident")
        row = np.asarray(row)
        if row.shape != (self.n_h,):
            raise ValueError(f"row must be ({self.n_h},), got {row.shape}")
        self._spill[uid] = row

    # ------------------------------------------------------------------
    def read(self, uid: Hashable) -> np.ndarray:
        """Host copy of ``uid``'s current state (resident or spilled)."""
        slot = self._slot_of.get(uid)
        if slot is not None:
            return np.asarray(_row_get(self.h, slot))
        return np.array(self._spill[uid])

    def stats(self) -> dict:
        return {"n_slots": self.n_slots, "resident": len(self._slot_of),
                "free": len(self._free), "spilled": len(self._spill),
                "evictions": self.evictions, "reloads": self.reloads}

    def check(self) -> None:
        """Assert the structural invariants (test hook)."""
        occupied = {s for s, u in enumerate(self._uid_of) if u is not None}
        free = set(self._free)
        assert len(self._free) == len(free), "duplicate free slots"
        assert not (occupied & free), "slot both free and occupied"
        assert occupied | free == set(range(self.n_slots)), \
            "free-list conservation violated"
        assert len(self._slot_of) == len(occupied), "double occupancy"
        for uid, slot in self._slot_of.items():
            assert self._uid_of[slot] == uid, "slot_of/uid_of disagree"
        assert set(self._lru) == set(self._slot_of), \
            "LRU book != resident set"
        assert not (set(self._spill) & set(self._slot_of)), \
            "uid both resident and spilled"
        assert self._pinned <= set(self._slot_of), "pinned non-resident"

    def __repr__(self) -> str:
        return (f"<StateSlab {len(self._slot_of)}/{self.n_slots} resident, "
                f"{len(self._spill)} spilled, {self.evictions} evictions>")
