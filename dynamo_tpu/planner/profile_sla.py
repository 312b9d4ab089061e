"""Pre-deployment SLA profiler (reference docs/architecture/planner.md:53-91
``profile_sla``: measure TTFT per prefill config and ITL per decode config,
then pick the operating point that satisfies the SLO).

Drives any AsyncEngine (JaxEngine on a real chip, mocker in CI) through its
public generate surface:

- **TTFT(isl)**: cold prompt of ``isl`` random tokens (fresh ids each probe,
  so prefix caching cannot flatter the number), time to the first streamed
  token.
- **ITL(batch)**: ``batch`` concurrent decode streams; steady-state
  inter-token latency = elapsed / tokens-per-stream (excluding the first
  token, which belongs to TTFT).  The JAX engine streams tokens in
  device-resident decode blocks (decode_block_size per flush), so pick
  ``osl`` spanning several blocks or the steady-state window collapses
  and ITL reads near zero.

``recommend`` returns the largest batch whose ITL meets the SLO and the
largest ISL whose TTFT meets the SLO -- the knobs the planner's scaling
thresholds are derived from.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ..protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from ..runtime.engine import Context


@dataclass
class SlaProfile:
    """One profiling run's results (the profile_sla output table)."""

    ttft_ms: Dict[int, float] = field(default_factory=dict)  # isl -> ms
    itl_ms: Dict[int, float] = field(default_factory=dict)  # batch -> ms/tok
    tok_s: Dict[int, float] = field(default_factory=dict)  # batch -> tok/s

    def recommend(
        self, ttft_slo_ms: Optional[float], itl_slo_ms: Optional[float]
    ) -> Dict[str, Any]:
        """Largest ISL/batch meeting each SLO (None = unconstrained)."""
        max_isl = None
        for isl in sorted(self.ttft_ms):
            if ttft_slo_ms is None or self.ttft_ms[isl] <= ttft_slo_ms:
                max_isl = isl
        max_batch = None
        for b in sorted(self.itl_ms):
            if itl_slo_ms is None or self.itl_ms[b] <= itl_slo_ms:
                max_batch = b
        return {
            "max_isl_within_ttft_slo": max_isl,
            "max_batch_within_itl_slo": max_batch,
            "throughput_at_max_batch": self.tok_s.get(max_batch)
            if max_batch is not None
            else None,
        }

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ttft_ms": {str(k): round(v, 2) for k, v in self.ttft_ms.items()},
            "itl_ms": {str(k): round(v, 3) for k, v in self.itl_ms.items()},
            "tok_s": {str(k): round(v, 1) for k, v in self.tok_s.items()},
        }


class SlaProfiler:
    def __init__(
        self,
        engine,
        vocab_size: int = 30000,
        warmup: bool = True,
        seed: int = 0,
    ) -> None:
        self.engine = engine
        self.vocab = max(4, vocab_size)
        self.warmup = warmup
        self.rng = np.random.RandomState(seed)

    def _req(self, isl: int, max_tokens: int) -> PreprocessedRequest:
        # fresh random ids every probe: an engine-side prefix cache must miss
        toks = self.rng.randint(2, self.vocab, (isl,)).tolist()
        return PreprocessedRequest(
            token_ids=toks,
            stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )

    @staticmethod
    def _check_error(item) -> None:
        """An error stream must FAIL the probe -- scoring it as a ~0ms
        success would make recommend() bless unservable configs."""
        if getattr(item, "is_error", None) and item.is_error():
            raise RuntimeError(
                f"probe failed: {item.error_message() or 'engine error'}"
            )

    async def _ttft_once(self, isl: int) -> float:
        stream = await self.engine.generate(Context.new(self._req(isl, 2)))
        t0 = time.monotonic()
        ttft = None
        async for item in stream:
            self._check_error(item)
            data = getattr(item, "data", None) or {}
            if ttft is None and data.get("token_ids"):
                ttft = time.monotonic() - t0
        if ttft is None:
            raise RuntimeError(f"probe produced no tokens (isl={isl})")
        return ttft * 1e3

    async def _decode_run(self, batch: int, osl: int, isl: int) -> tuple:
        """Returns (itl_ms, tok_s) for ``batch`` concurrent streams.

        ITL is measured PER STREAM -- (last token - first token) over the
        stream's own decode interval -- then averaged.  A windowed global
        measure would understate ITL whenever the engine admits the batch
        in waves (batch > engine slots): early waves finish decoding before
        the last wave's first token."""
        results: List[tuple] = []  # (first_ts, last_ts, n_tokens)

        async def one():
            stream = await self.engine.generate(
                Context.new(self._req(isl, osl))
            )
            n = 0
            first = last = None
            async for item in stream:
                self._check_error(item)
                data = getattr(item, "data", None) or {}
                got = len(data.get("token_ids") or [])
                if got:
                    last = time.monotonic()
                    if first is None:
                        first = last
                n += got
            if first is None:
                raise RuntimeError(f"probe produced no tokens (batch={batch})")
            results.append((first, last, n))

        t0 = time.monotonic()
        await asyncio.gather(*[one() for _ in range(batch)])
        t_end = time.monotonic()
        itls = [
            (last - first) / (n - 1)
            for first, last, n in results
            if n >= 2 and last > first
        ]
        if not itls:
            # every stream delivered in one flush: osl doesn't span multiple
            # decode blocks, so there is no inter-flush interval to measure.
            # A confident 0.0 here would bless any batch against any SLO.
            raise RuntimeError(
                f"ITL unmeasurable at batch={batch}: every stream arrived in"
                f" a single flush; raise --osl to span several decode blocks"
            )
        itl_ms = (sum(itls) / len(itls)) * 1e3
        done = sum(n for _, _, n in results)
        return itl_ms, done / max(1e-9, t_end - t0)

    async def profile(
        self,
        isls: List[int] = (128, 512),
        batches: List[int] = (1, 4, 8),
        osl: int = 64,
        ttft_repeats: int = 3,
    ) -> SlaProfile:
        prof = SlaProfile()
        if self.warmup:  # compile prefill buckets + decode once, unmeasured
            for isl in isls:
                await self._ttft_once(isl)
            await self._decode_run(max(batches), osl=8, isl=min(isls))
        for isl in isls:
            samples = [await self._ttft_once(isl) for _ in range(ttft_repeats)]
            prof.ttft_ms[isl] = min(samples)  # best-of: host-clock jitter
        for b in batches:
            itl, tok_s = await self._decode_run(b, osl=osl, isl=min(isls))
            prof.itl_ms[b] = itl
            prof.tok_s[b] = tok_s
        return prof
