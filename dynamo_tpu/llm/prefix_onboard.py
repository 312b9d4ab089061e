"""Cross-worker prefix onboarding (KVBM G4): import another worker's
registered KV blocks instead of recomputing them.

Reference block_manager.rs:119-146: any worker can export a blockset and
any worker can import it, turning per-worker prefix caches into
cluster-wide cache capacity.  Mechanism here:

  * every worker serves ``kv_export`` (raw endpoint): given a chain of
    sequence hashes, it streams back the longest resident prefix as
    (meta, blob) frame pairs -- G1 pages slice on device in one bundled
    transfer, offload tiers fill the tail (engine.export_blocks);
  * the KV router already knows who holds what (its index built the
    overlap scores); when the *best-cost* worker is not the *best-overlap*
    worker, it stamps the donor's instance + block count into the request
    metadata (``prefix_donor``);
  * the serving wrapper on the chosen worker fetches the missing blocks
    from the donor into the engine's host offload tier **before** engine
    admission -- the scheduler's existing offload-onboarding path
    (scheduler.py _match_prefix G2 chain) then scatters them into HBM and
    registers them exactly as if they had been evicted locally.  No new
    scheduler states; the tested onboard path is the only onboard path.

The import staging uses the host tier (G2) via the engine's
``KVOffloadEngine`` (every put rides its dedicated offload thread), so
onboarding requires the offload plane to be armed -- either
``host_offload_blocks > 0`` or ``DYN_KV_OFFLOAD``.
"""

from __future__ import annotations

import json
import logging
from typing import Any, AsyncIterator, Dict, List, Optional


from ..offload import BlockMeta, KVStagingBuffer
from ..runtime.component import Namespace, PushRouter
from ..runtime.engine import Annotated, AsyncEngineContext, Context
from ..runtime.transports.codec import ChunkAssembler, encode_chunk_frame

logger = logging.getLogger("dynamo.prefix_onboard")

KV_EXPORT_ENDPOINT = "kv_export"
DONOR_META_KEY = "prefix_donor"  # request metadata: {"instance": i, "blocks": n}

# Block blobs ride the wire in chunk frames of this size: big models' blocks
# can exceed codec.MAX_FRAME as one payload, and the importer stages each
# block incrementally instead of buffering whole frames (same framing as the
# disagg KV delivery, runtime/transports/codec.py).
EXPORT_CHUNK_BYTES = 8 * 1024 * 1024


def kv_export_handler(engine):
    """Raw handler for the ``kv_export`` endpoint: meta carries the hash
    chain; the response alternates JSON-meta frames and the block's chunk
    frames (index + offset framed, codec.encode_chunk_frame)."""

    async def handler(
        hdr: Dict[str, Any],
        chunks: AsyncIterator[bytes],
        ctx: AsyncEngineContext,
    ) -> AsyncIterator[bytes]:
        del ctx
        async for _chunk in chunks:
            pass  # no request body expected

        async def gen() -> AsyncIterator[bytes]:
            from ..engine.kv_cache import blob_to_bytes

            hashes = [int(h) for h in (hdr.get("meta") or {}).get("hashes", [])]
            found = await engine.export_blocks(hashes)
            for seq_hash, blob, meta in found:
                # the importer re-derives the extents from (shape, dtype)
                raw = blob_to_bytes(blob)
                yield json.dumps(
                    {
                        "seq_hash": int(seq_hash),
                        "dtype": str(blob.dtype),
                        "shape": list(blob.shape),
                        "chunk_bytes": EXPORT_CHUNK_BYTES,
                        "total_bytes": len(raw),
                        "meta": meta,
                    }
                ).encode()
                view = memoryview(raw)
                # zero-byte blobs emit no chunk frames: the importer's
                # assembler is already complete at meta time.  Chunk i
                # covers bytes [i*CB, (i+1)*CB) -- the same bounds
                # KVStagingBuffer.for_byte_chunks derives on the importer.
                for idx, off in enumerate(
                    range(0, len(view), EXPORT_CHUNK_BYTES)
                ):
                    yield encode_chunk_frame(
                        idx, off, view[off : off + EXPORT_CHUNK_BYTES]
                    )

        return gen()

    return handler


class PrefixOnboardEngine:
    """Serving wrapper: fetch donor blocks into the host tier, then delegate.

    Sits between the endpoint and the engine (compose freely with
    DisaggDecodeEngine -- onboarding concerns the prefix, disagg the
    remainder of the prefill)."""

    def __init__(
        self,
        inner,  # the serving engine to delegate to (engine or disagg wrapper)
        namespace: Namespace,
        component: str,
        engine=None,  # the JaxEngine owning pool/offload (defaults to inner)
    ) -> None:
        self.inner = inner
        self.engine = engine if engine is not None else inner
        self.namespace = namespace
        self.component = component
        self._export_router: Optional[PushRouter] = None
        self.onboarded_blocks = 0  # observability
        self.failed_fetches = 0

    async def _router(self) -> PushRouter:
        if self._export_router is None:
            client = await (
                self.namespace.component(self.component)
                .endpoint(KV_EXPORT_ENDPOINT)
                .client()
            )
            self._export_router = PushRouter(client)
        return self._export_router

    async def close(self) -> None:
        if self._export_router is not None:
            await self._export_router.client.close()
            self._export_router = None

    async def generate(self, request: Context[Any]) -> AsyncIterator[Annotated]:
        donor = (request.metadata or {}).get(DONOR_META_KEY)
        if donor and self.engine.offload is not None:
            try:
                await self._onboard(request, donor)
            except Exception:
                # onboarding is an optimization: a donor failure must never
                # fail the request -- it just recomputes the prefix
                self.failed_fetches += 1
                logger.exception("prefix onboarding failed; recomputing")
        return await self.inner.generate(request)

    async def _onboard(self, request: Context[Any], donor: Dict[str, Any]) -> None:
        from ..tokens.hashing import hash_blocks

        data = request.data
        token_ids = (
            data.token_ids
            if hasattr(data, "token_ids")
            else list((data or {}).get("token_ids") or [])
        )
        block_size = self.engine.sched.block_size
        n = min(int(donor.get("blocks", 0)), max(0, (len(token_ids) - 1) // block_size))
        if n <= 0:
            return
        _, seq_hashes = hash_blocks(token_ids, block_size)
        seq_hashes = seq_hashes[:n]
        pool = self.engine.kv.allocator
        offload = self.engine.offload_engine
        # only fetch what neither HBM nor the local tiers already hold; the
        # donor chain must stay contiguous, so cut at the first local hit
        # gap is fine -- we request the full chain and the donor returns its
        # own longest prefix
        missing = [
            h
            for h in seq_hashes
            if not (
                getattr(pool, "is_registered", lambda _h: False)(h)
                or offload.contains(h)
            )
        ]
        if not missing:
            return
        if donor.get("source") == "remote":
            # the donor is the shared G4 object store, not a peer worker:
            # fetch over the offload engine's remote tier instead of the
            # kv_export endpoint
            await self._onboard_remote(missing)
            return
        router = await self._router()
        stream = await router.direct_raw(
            int(donor["instance"]),
            request.id,
            {"hashes": [int(h) for h in missing]},
            b"",
            AsyncEngineContext(request.id),
        )
        import jax.numpy as jnp

        pending_meta: Optional[Dict[str, Any]] = None
        staging: Optional[KVStagingBuffer] = None
        asm: Optional[ChunkAssembler] = None
        fetched = 0

        def _store() -> None:
            nonlocal fetched, pending_meta, staging, asm
            # the host-ring copy (and any disk demotion it cascades into)
            # runs on the offload engine's thread, never this event loop
            offload.submit_put(
                int(pending_meta["seq_hash"]),
                staging.payload(),
                BlockMeta.from_dict(pending_meta["meta"]),
            )
            fetched += 1
            pending_meta = staging = asm = None

        async for frame in stream:
            if pending_meta is None:
                pending_meta = json.loads(frame)
                dtype = jnp.dtype(pending_meta["dtype"])
                if "chunk_bytes" not in pending_meta:
                    # legacy donor: the whole blob rides the next frame
                    staging = asm = None
                    continue
                staging = KVStagingBuffer.for_byte_chunks(
                    pending_meta["shape"], dtype,
                    int(pending_meta["chunk_bytes"]),
                )
                asm = ChunkAssembler(staging.memoryview, staging.bounds)
                if asm.complete:  # zero-byte blob: no chunk frames follow
                    _store()
            elif asm is None:
                from ..engine.kv_cache import blob_from_bytes

                offload.submit_put(
                    int(pending_meta["seq_hash"]),
                    blob_from_bytes(
                        frame, pending_meta["shape"], pending_meta["dtype"]
                    ),
                    BlockMeta.from_dict(pending_meta["meta"]),
                )
                fetched += 1
                pending_meta = None
            else:
                asm.add(frame)
                if asm.complete:
                    _store()
        if pending_meta is not None:
            # stream ended mid-block (donor died): the partial block is
            # dropped; everything already stored still onboards
            logger.warning(
                "donor stream ended mid-block for %x; partial block dropped",
                int(pending_meta.get("seq_hash", 0)),
            )
        self.onboarded_blocks += fetched
        if fetched:
            # barrier: the submitted puts must be resident before the
            # engine's admission-time tier lookup runs (off-loop wait; the
            # offload thread's queue is at most this request's blocks deep)
            import asyncio

            await asyncio.to_thread(offload.drain)
            logger.info(
                "onboarded %d prefix blocks from donor %x",
                fetched, int(donor["instance"]),
            )

    async def _onboard_remote(self, missing: List[int]) -> None:
        """Fetch missing prefix blocks from the G4 store into the host
        tier.  Fetches ride the kv-remote thread (futures awaited here);
        the chain cuts at the first miss -- the scheduler's prefix match
        stops at the first hole, so trailing blocks past a gap are
        useless."""
        import asyncio

        offload = self.engine.offload_engine
        remote = getattr(offload, "remote", None)
        if remote is None:
            return
        fetched = 0
        for h in missing:
            got = await asyncio.wrap_future(remote.fetch(int(h)))
            if got is None:
                self.failed_fetches += 1
                break
            blob, meta = got
            offload.submit_put(int(h), blob, meta)
            fetched += 1
        self.onboarded_blocks += fetched
        if fetched:
            await asyncio.to_thread(offload.drain)
            logger.info("onboarded %d prefix blocks from G4 store", fetched)
