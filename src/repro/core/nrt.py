"""Near-Real-Time search: SearcherManager (paper §2.3, Fig 2b).

``maybe_reopen`` is Lucene's ``reopen``: swap in a fresh point-in-time
Searcher that can see everything indexed so far — *without* committing.
The paper measures exactly this call's latency (Fig 4b) and the query
throughput around it (Fig 4a).

**Search-at-ack (the default path).**  With a live buffer index
(``repro.storage.live_index``) the uncommitted tail is already
addressable, so the default reopen takes a ``LiveSnapshot`` of the tail
and binds it into the new Searcher — results become (committed segments ∪
live buffer), bit-identical to flush-then-search, and ack-to-visible
latency stops paying a flush.  ``force_flush=True`` keeps the historical
segment-only semantics: flush first, then reopen.  Writers without a live
structure (the reference dict-buffer ingest) transparently fall back to
flushing, so semantics never degrade.

The manager owns a ``SegmentDeviceCache`` shared by every Searcher
generation it creates: a reopen uploads ONLY the new/changed segments'
arrays to device (unchanged segments keep their resident buffers), so
reopen latency scales with the flush size, not the index size.  The live
tail is staged privately per Searcher and never enters the cache.

Reopen after WAL replay: recovery with a durable ingest buffer
(``IndexWriter(use_wal=True)``) rebuilds acked-but-uncommitted documents
into the DRAM buffer *and* the live index, exactly like documents added
moments ago — the first ``maybe_reopen()`` makes them searchable again
with no flush and no special recovery path in this layer.
"""

from __future__ import annotations

import time
from typing import Optional

from repro.core.lifecycle import SegmentInfos
from repro.core.query import profile
from repro.core.query.cache import SegmentDeviceCache
from repro.core.search import Searcher
from repro.core.writer import IndexWriter


class SearcherManager:
    """Holds the current point-in-time ``SegmentInfos`` snapshot (plus,
    on the default no-flush path, a ``LiveSnapshot`` of the acked tail).

    The manager never looks at the writer's segments directly except to
    take the next immutable snapshot at reopen — so a Searcher it handed
    out keeps bit-identical results while the writer flushes, deletes, and
    merges underneath it.  The live snapshot is equally point-in-time:
    every read it serves is watermark-filtered against later acks.
    """

    def __init__(
        self,
        writer: IndexWriter,
        use_pallas: bool = False,
        device_cache: Optional[SegmentDeviceCache] = None,
        shard: Optional[int] = None,
    ) -> None:
        self.writer = writer
        self.use_pallas = use_pallas
        self.shard = shard  # the shard this manager serves (span attr)
        # explicit None check: an empty cache is falsy (it has __len__)
        self.device_cache = (
            device_cache
            if device_cache is not None
            else SegmentDeviceCache(tile=use_pallas)
        )
        self._infos: Optional[SegmentInfos] = None
        self._searcher: Optional[Searcher] = None
        self._live = None  # LiveSnapshot the current searcher holds
        self._live_token: Optional[int] = None
        self.maybe_reopen(force_flush=False)

    @property
    def searcher(self) -> Searcher:
        assert self._searcher is not None
        return self._searcher

    @property
    def infos(self) -> SegmentInfos:
        """The snapshot the current searcher was opened on."""
        assert self._infos is not None
        return self._infos

    @property
    def live(self):
        """The ``LiveSnapshot`` the current searcher holds (None when the
        tail was empty or flushed) — the sharded layer rebinds per-shard
        views from this."""
        return self._live

    def maybe_reopen(self, force_flush: bool = False) -> float:
        """Reopen: refresh the searcher to see everything indexed so far.

        Default: the buffered tail is served straight from the live index
        (search-at-ack; no flush on the read path).  ``force_flush=True``
        restores segment-only visibility: flush the buffer first.  Falls
        back to flushing when the writer has no live structure (reference
        ingest) or the live mirror degraded — visibility semantics are
        identical either way.

        Returns the reopen latency in seconds (the paper's Fig 4b metric),
        also recorded as the ``nrt.reopen`` span.
        """
        t0 = time.perf_counter()
        with profile.span("nrt.reopen", shard=self.shard):
            self._reopen(force_flush)
        return time.perf_counter() - t0

    def _reopen(self, force_flush: bool) -> None:
        live = None
        if self.writer.buffered_docs:
            if force_flush:
                self.writer.flush()
            else:
                live = self.writer.live_snapshot()
                if live is None or live.n_docs != self.writer.buffered_docs:
                    live = None  # no/desynced live structure: flush instead
                    self.writer.flush()
        infos = self.writer.infos
        live_token = live.generation if live is not None else -1
        gen_changed = (
            self._infos is None or infos.generation != self._infos.generation
        )
        if gen_changed or live_token != self._live_token:
            self._searcher = Searcher(
                infos,
                analyzer=self.writer.analyzer,
                use_pallas=self.use_pallas,
                device_cache=self.device_cache,
                live=live,
            )
            if gen_changed:
                # evict merged-away segments, upload the new ones: reopen
                # cost is proportional to what changed, not the index size
                # (freshly merged segments were pre-warmed at merge time)
                self.device_cache.sync(infos.segments)
            self._infos = infos
            self._live = live
            self._live_token = live_token
