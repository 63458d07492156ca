"""Prefix KV cache: block-hashed prompt-prefix reuse for decode.

The few-system-prompts-many-users traffic shape re-prefills the same
prompt head thousands of times — the dominant decode-server cost after
the per-token step itself.  :class:`PrefixKVCache` retains FINISHED
slots' KV blocks (the vLLM lineage: Kwon et al., SOSP 2023, at block
granularity rather than per-page) in a bounded byte-budget LRU, keyed
by a hash of the prompt-token prefix at ``block_tokens`` boundaries:

* **Offer** — when the scheduler frees a slot, the prompt's longest
  block-aligned prefix (bounded by the positions the slot actually
  consumed) is hashed and its KV rows extracted (one host materialize
  per retained entry — a control-plane move off the tick's hot path,
  like a rung transition).
* **Snapshot** (:meth:`put`) — a pool whose builder prefills in chunks
  hands over, where a prompt's last whole chunk ends, the slot's whole
  cache row as DEVICE arrays: K/V and compressed-key rows below the
  boundary and every recurrent leaf's value AT the boundary (40-80 MB
  an entry at a 20k-token document: nothing crosses to the host).  The
  byte budget counts device bytes the same way.
* **Probe** — at admission, the incoming prompt is hashed ONCE, front
  to back, with the running digest read off at each length some entry
  has (a 30k-token prompt is one 120 KB pass, not one pass a block);
  the longest match hands back the retained leaves and the admit
  executable installs them, so prefill drops to the unmatched
  suffix (the prefill-token counter is the ground truth the tests and
  bench assert on).  Hash collisions cannot serve wrong tokens: every
  entry stores its prefix tokens and a probe compares them exactly.
* **Invalidation** — an endpoint reload (new weights) calls
  :meth:`invalidate`; retained KV from old weights must never seed new
  decodes.

The cache is prompt-token keyed and position-absolute, so an entry is
valid for ANY later prompt sharing the prefix — the write-before-read
pool invariant covers the suffix positions, exactly as it covers slot
reuse.  Metrics: ``serving_prefix_cache_{hits,misses,evictions}_total``
counters and the ``serving_prefix_cache_bytes`` gauge, labeled by cache
name and retired by :meth:`close`.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from paddle_tpu import monitor

__all__ = ["PrefixKVCache"]

_LABELS = ("cache",)
PREFIX_HITS = monitor.counter(
    "serving_prefix_cache_hits_total",
    "decode admissions that matched a retained prompt-prefix and "
    "skipped its prefill (shared-prefix KV reuse)", _LABELS)
PREFIX_MISSES = monitor.counter(
    "serving_prefix_cache_misses_total",
    "decode admissions probed against the prefix KV cache with no "
    "block-aligned match (full prefill)", _LABELS)
PREFIX_EVICTIONS = monitor.counter(
    "serving_prefix_cache_evictions_total",
    "prefix KV entries evicted by the byte-budget LRU", _LABELS)
PREFIX_BYTES = monitor.gauge(
    "serving_prefix_cache_bytes",
    "bytes of retained prefix KV blocks (tokens + cache leaves, host "
    "or device) currently held by the prefix cache", _LABELS)
PREFIX_SNAPSHOTS = monitor.counter(
    "serving_prefix_snapshots_total",
    "prefix entries stored as device snapshots of a slot's whole cache "
    "row (recurrent leaves included), taken where a chunk-prefilled "
    "prompt's last whole chunk ends", _LABELS)


class PrefixKVCache:
    """Bounded LRU of prompt-prefix KV blocks for one decode endpoint.

    ``capacity_bytes`` bounds the sum of retained entry sizes (prefix
    tokens + extracted KV leaves); ``block_tokens`` is the hash
    granularity — prefixes are keyed only at multiples of it, so two
    prompts share an entry iff they agree on whole blocks.  One cache
    serves ONE endpoint (one weight set / pool layout); entries are not
    portable across servers.
    """

    def __init__(self, capacity_bytes: int = 64 << 20,
                 block_tokens: int = 16, name: str = "prefix"):
        if int(capacity_bytes) < 1:
            raise ValueError(
                "capacity_bytes must be >= 1, got %r" % capacity_bytes)
        if int(block_tokens) < 1:
            raise ValueError(
                "block_tokens must be >= 1, got %r" % block_tokens)
        self.capacity_bytes = int(capacity_bytes)
        self.block_tokens = int(block_tokens)
        self.name = name
        # key -> {"tokens": [m] int32, "leaves": [np arrays | None],
        #         "nbytes": int}
        self._data: "OrderedDict[str, Dict[str, object]]" = OrderedDict()
        # entry length -> entries of that length: the only lengths a
        # probe needs a digest at
        self._lengths: Dict[int, int] = {}
        self._bytes = 0
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._fallbacks = 0
        lbl = {"cache": name}
        self._c_hits = PREFIX_HITS.labels(**lbl)
        self._c_misses = PREFIX_MISSES.labels(**lbl)
        self._c_evictions = PREFIX_EVICTIONS.labels(**lbl)
        self._g_bytes = PREFIX_BYTES.labels(**lbl)
        self._c_snapshots = PREFIX_SNAPSHOTS.labels(**lbl)

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        return len(self._data)

    @staticmethod
    def _hash(tokens: np.ndarray) -> str:
        return hashlib.sha1(
            np.ascontiguousarray(tokens, np.int32).tobytes()).hexdigest()

    @staticmethod
    def _prefix_keys(prompt: np.ndarray, lengths):
        """``(m, _hash(prompt[:m]))`` for each of the ascending
        ``lengths``, from ONE pass over the prompt: the running digest is
        read off at each length."""
        digest, done = hashlib.sha1(), 0
        for m in lengths:
            digest.update(prompt[done:m].tobytes())
            done = m
            yield m, digest.copy().hexdigest()

    # ------------------------------------------------------------------
    # hot-path: begin prefix_probe (hash + dict probes under the cache
    # lock, on the scheduler thread between ticks — pure host work, no
    # device syncs, no sleeps; the KV install itself is one warmed
    # admit_prefix dispatch)
    def probe(self, prompt) -> Tuple[int, Optional[List[np.ndarray]]]:
        """:meth:`lookup`, counted at once as a hit or a miss
        (:meth:`count_probe`)."""
        hit = self.lookup(prompt)
        self.count_probe(hit[0] > 0)
        return hit

    def lookup(self, prompt) -> Tuple[int, Optional[List[np.ndarray]]]:
        """Longest retained proper prefix of ``prompt``: ``(prefix_len,
        kv_leaves)``, or ``(0, None)`` on a miss.  The match is capped
        one token short of the prompt so the suffix always re-enters
        prefill (the step consuming the LAST prompt token produces the
        first generated one — it must run).  The prompt is hashed in ONE
        pass, the digest read at each length some entry has; stored
        tokens are compared exactly, so a hash collision can never
        install another prompt's KV.  Counts nothing: the caller owes
        one :meth:`count_probe` (``DecodeServer`` pays it where it
        counts the admission, so the two counters move together)."""
        prompt = np.ascontiguousarray(prompt, np.int32)
        with self._lock:
            lengths = sorted(m for m in self._lengths if m < len(prompt))
            best = None
            for m, key in self._prefix_keys(prompt, lengths):
                ent = self._data.get(key)
                if ent is not None and np.array_equal(
                        ent["tokens"], prompt[:m]):
                    best = (m, key, ent)
            if best is not None:
                m, key, ent = best
                self._data.move_to_end(key)
                return m, list(ent["leaves"])
        return 0, None

    def count_probe(self, hit: bool) -> None:
        """One :meth:`lookup` counted: a hit or a miss."""
        with self._lock:
            if hit:
                self._hits += 1
                self._c_hits.inc()
            else:
                self._misses += 1
                self._c_misses.inc()
    # hot-path: end prefix_probe

    def count_fallback(self) -> None:
        """A prefix admission that fell back to full prefill (fault
        injection / corrupted entry) — tracked for :meth:`stats`; the
        server's own metrics count it as ``prefix_fallback``."""
        with self._lock:
            self._fallbacks += 1

    # ------------------------------------------------------------------
    def offer(self, prompt, consumed: int,
              extract: Callable[[int], List[Optional[np.ndarray]]]) -> bool:
        """Retain a freed slot's prefix KV: hash the prompt's longest
        block-aligned prefix covered by the slot's ``consumed``
        positions and store ``extract(m)`` (the pool's KV leaves for
        positions ``< m``).  Returns True when a new entry was stored.
        The extract (a host materialize) runs OUTSIDE the cache lock and
        only for new keys — repeat offers of a hot prefix are one dict
        probe."""
        B = self.block_tokens
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        m = (len(prompt) // B) * B
        m = min(m, (int(consumed) // B) * B)
        if m <= 0:
            return False
        key = self._hash(prompt[:m])
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                return False
        return self._store(key, prompt[:m].copy(), extract(m))

    def put(self, tokens, leaves) -> bool:
        """Retain a SNAPSHOT: ``leaves`` is the whole cache row of a
        slot that has consumed exactly ``tokens`` (any length: where a
        chunked prefill stopped), as device arrays — recurrent leaves
        too, which is what lets a later prompt that starts with
        ``tokens`` resume at ``len(tokens)`` instead of position 0.
        Returns True when a new entry was stored."""
        tokens = np.array(tokens, np.int32).reshape(-1)
        if len(tokens) < 1:
            return False
        stored = self._store(self._hash(tokens), tokens, list(leaves))
        if stored:
            self._c_snapshots.inc()
        return stored

    def holds(self, tokens) -> bool:
        """Whether an entry for exactly ``tokens`` is retained (the
        scheduler asks before paying for a snapshot)."""
        tokens = np.ascontiguousarray(tokens, np.int32).reshape(-1)
        with self._lock:
            ent = self._data.get(self._hash(tokens))
            return ent is not None and np.array_equal(ent["tokens"], tokens)

    def _store(self, key: str, tokens: np.ndarray, leaves) -> bool:
        nbytes = int(tokens.nbytes) + sum(
            int(leaf.nbytes) for leaf in leaves if leaf is not None)
        with self._lock:
            if key in self._data:  # lost the race to a concurrent offer
                self._data.move_to_end(key)
                return False
            self._data[key] = {
                "tokens": tokens, "leaves": leaves, "nbytes": nbytes}
            self._lengths[len(tokens)] = self._lengths.get(
                len(tokens), 0) + 1
            self._bytes += nbytes
            evicted = 0
            while self._bytes > self.capacity_bytes and self._data:
                _, ev = self._data.popitem(last=False)
                self._bytes -= int(ev["nbytes"])
                self._forget_length(len(ev["tokens"]))
                evicted += 1
            if evicted:
                self._evictions += evicted
                self._c_evictions.inc(evicted)
            self._g_bytes.set(float(self._bytes))
        return True

    def _forget_length(self, m: int) -> None:
        left = self._lengths.get(m, 0) - 1
        if left > 0:
            self._lengths[m] = left
        else:
            self._lengths.pop(m, None)

    # ------------------------------------------------------------------
    def invalidate(self) -> None:
        """Drop every entry — the endpoint-reload path: retained KV from
        the previous weights must never seed a new decode."""
        with self._lock:
            self._data.clear()
            self._lengths.clear()
            self._bytes = 0
            self._g_bytes.set(0.0)

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, object]:
        with self._lock:
            total = self._hits + self._misses
            return {
                "entries": len(self._data),
                "bytes": self._bytes,
                "capacity_bytes": self.capacity_bytes,
                "block_tokens": self.block_tokens,
                "hits": self._hits,
                "misses": self._misses,
                "evictions": self._evictions,
                "fallbacks": self._fallbacks,
                "hit_ratio": (round(self._hits / total, 6)
                              if total else None),
            }

    def close(self) -> None:
        """Retire this cache's series from the exposition."""
        lbl = {"cache": self.name}
        for metric in (PREFIX_HITS, PREFIX_MISSES, PREFIX_EVICTIONS,
                       PREFIX_BYTES, PREFIX_SNAPSHOTS):
            metric.remove_labels(**lbl)
        with self._lock:
            self._data.clear()
            self._lengths.clear()
            self._bytes = 0
