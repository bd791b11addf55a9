"""Bounded per-shard ingest queues with an explicit backpressure policy.

The serve plane's answer to "what happens when data arrives faster than
the device drains it": every edge shard owns ONE bounded queue between
its source subscription and the staging buffers, and the queue's policy
decides who pays when it fills:

    block        refuse the overflow — rejected items never enter the
                 queue and are counted ``deferred`` (the producer still
                 holds them; a Kafka-style consumer would simply not
                 advance its offset).
    drop_oldest  evict the oldest queued items to make room for the new
                 ones — freshest-data-wins, evictions counted
                 ``items_dropped``.
    degrade      drop each INCOMING item with probability depth/capacity
                 (deterministic per-queue RNG) — graceful load shedding
                 that sheds more as the queue fills, drops counted
                 ``items_dropped``.

Every drop is counted so the published bound stays honest: the executor
folds ``items_dropped`` into the Eq. 9 arrived-weight fraction α, so a
window that shed load publishes with a widened bound instead of a
silently optimistic one.

Accounting invariant (pinned in ``tests/test_serve_plane.py``):

    items_in == items_out + items_dropped + depth

(``deferred`` counts offers that never entered, so it sits outside the
identity on purpose.)

Layout: the queue is a FIFO of *chunks*, not of items. A chunk is the
items one ``put`` admitted, ``(values f32[k], strata i32[k], arrival)``:
a copy the queue owns (a source may reuse its buffers), stamped with
the call's arrival time. ``put`` and ``get_many`` cost a few array
operations per chunk, whatever its length. Overflow is trimmed from the
head (whole chunks, then a slice of the next), and a drain pops whole
chunks and slices the last one, leaving its tail, with its arrival, at
the head. The policies, the ``degrade`` RNG stream, every counter and
the invariant are item-exact: the same calls admit, drop and return
the same items in the same order as a FIFO of single items would.
"""
from __future__ import annotations

import collections

import numpy as np

POLICIES = ("block", "drop_oldest", "degrade")


class BoundedShardQueue:
    """One shard's bounded ingest queue (see module doc for policies)."""

    def __init__(self, capacity: int, policy: str = "block", seed: int = 0):
        if capacity < 1:
            raise ValueError(f"queue capacity must be >= 1, got {capacity}")
        if policy not in POLICIES:
            raise ValueError(f"unknown backpressure policy {policy!r}; "
                             f"valid: {POLICIES}")
        self.capacity = int(capacity)
        self.policy = policy
        # (values f32[k], strata i32[k], arrival) chunks, oldest first
        self._q: collections.deque = collections.deque()
        self._depth = 0
        self._rng = np.random.default_rng(seed)
        self.items_in = 0
        self.items_out = 0
        self.items_dropped = 0
        self.deferred = 0
        self.high_watermark = 0
        self.chunks_touched = 0    # chunks get_many took items from
        self.chunks_split = 0      # get_many calls that sliced a chunk

    # ------------------------------------------------------------- put --
    def put(self, values, strata, now: float) -> int:
        """Offer a batch of (value, stratum) items stamped with arrival
        time ``now``; returns the number actually enqueued."""
        values = np.asarray(values, np.float32)
        strata = np.asarray(strata, np.int32)
        offered = int(values.size)
        if offered == 0:
            return 0
        if self.policy == "block":
            accepted = min(offered, self.capacity - self._depth)
            self.deferred += offered - accepted
            self.items_in += accepted
            self._append(values[:accepted], strata[:accepted], now)
        else:
            self.items_in += offered
            if self.policy == "degrade":
                keep = (self._rng.random(offered)
                        >= self._depth / self.capacity)
                values, strata = values[keep], strata[keep]
                self.items_dropped += offered - values.size
            accepted = int(values.size)
            self._append(values, strata, now)
            self._trim()
        self.high_watermark = max(self.high_watermark, self._depth)
        return accepted

    def _append(self, values, strata, now: float):
        """Queue a copy of the batch as one chunk (none if it is empty)."""
        if values.size:
            self._q.append((values.copy(), strata.copy(), now))
            self._depth += values.size

    def _trim(self):
        """Drop the oldest items past ``capacity``: whole chunks while
        they fit in the excess, then the head of the next."""
        excess = self._depth - self.capacity
        while excess > 0:
            values, strata, now = self._q[0]
            if values.size <= excess:
                self._q.popleft()
                n = values.size
            else:
                self._q[0] = (values[excess:], strata[excess:], now)
                n = excess
            self._depth -= n
            self.items_dropped += n
            excess -= n

    # -------------------------------------------------------- get_many --
    def get_many(self, max_records: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Kafka-``getmany``-style batched drain: up to ``max_records``
        items in FIFO order → ``(values f32[n], strata i32[n],
        arrivals f64[n])``."""
        n = max(min(int(max_records), self._depth), 0)
        values, strata, arrivals, sizes = [], [], [], []
        left = n
        while left:
            v, s, now = self._q[0]
            if v.size <= left:
                self._q.popleft()
            else:
                self._q[0] = (v[left:], s[left:], now)
                v, s = v[:left], s[:left]
                self.chunks_split += 1
            values.append(v)
            strata.append(s)
            arrivals.append(now)
            sizes.append(v.size)
            left -= v.size
        self.chunks_touched += len(values)
        self._depth -= n
        self.items_out += n
        if not n:
            return (np.empty(0, np.float32), np.empty(0, np.int32),
                    np.empty(0, np.float64))
        return (np.concatenate(values), np.concatenate(strata),
                np.repeat(np.asarray(arrivals, np.float64), sizes))

    # ------------------------------------------------------ accounting --
    @property
    def depth(self) -> int:
        return self._depth

    @property
    def accounting_ok(self) -> bool:
        """The drop-accounting law: every offered-and-admitted item is
        either drained, dropped, or still queued."""
        return self.items_in == (self.items_out + self.items_dropped
                                 + self.depth)

    def stats(self) -> dict:
        return {
            "capacity": self.capacity,
            "policy": self.policy,
            "depth": self.depth,
            "high_watermark": self.high_watermark,
            "items_in": self.items_in,
            "items_out": self.items_out,
            "items_dropped": self.items_dropped,
            "deferred": self.deferred,
        }
